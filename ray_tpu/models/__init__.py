"""Model families (pure JAX, TPU-first): gpt2, llama (GQA/RoPE/SwiGLU, for
training only: it has no serving programs), moe (OLMoE / Mixtral sparse MoE:
dropless sort-and-grouped-matmul routing, one-hot dispatch under expert
parallelism), deepseek (DeepSeek-V3's layer
for serving: latent attention over a latent cache, shared experts), brumby
(Brumby's layer for serving: power retention over a recurrent state),
granite (Granite 4.0-H's layers for serving: Mamba-2 state a slot beside
grouped-head keys and values a token, in one cache), kimi (Kimi Linear's
layers for serving: delta-rule state a slot beside un-rotated latent rows a
token, and a share of each layer's routed experts), keye (Keye-VL-2.0's
language model for serving: grouped-head attention over the 2,048 rows a
learned indexer chooses of a slot's, three leaves a token, routed
experts), solar (Solar Open2's layers for serving: kimi's programs with a
third mixer, gated un-rotated grouped-head attention over keys and values by
head beside the delta-rule state), nemotron (Nemotron-H's layers for serving:
a layer is a Mamba-2 mixer in groups, an attention mixer or an expert block
alone, the routed experts two-matrix relu^2 MLPs inside a narrow latent;
`mamba2` is the Mamba-2 mixer that granite and nemotron share), longcat
(LongCat-Flash's layers for serving: a double layer of two latent-attention
sublayers and two dense FFNs whose expert block is read at one sublayer and
added at the next one's end, zero-compute experts beside the routed ones;
`mla` is the latent-attention layer with positions that deepseek and longcat
share, and the latent rows' write and read), exaone (K-EXAONE's layers for
serving: three sliding-window softmax layers, rotated, to each global one,
un-rotated, in one stack; the window layers' rows a ring a slot, which the
pool keeps as a snapshot beside the global layers' rows by the block;
sigmoid-routed experts beside a shared one), mimo (MiMo-V2's layers for
serving: five sliding-window layers to each global one, the two kinds with
4 and 8 key-value heads and a fused projection each, a key of 192 lanes held
with the positions on the lanes beside a value of 128 a row, a learned sink a
head in the sliding layers' softmax, sigmoid-routed experts and no shared
one)."""

from ray_tpu.models import gpt2

__all__ = ["gpt2", "llama", "moe", "deepseek", "brumby", "granite", "kimi", "keye",
           "solar", "nemotron", "mamba2", "longcat", "mla", "exaone", "mimo",
           "serving_family"]

# The families `serve/llm.LLMEngine` takes: a preset's first word -> the
# module and its config class. A module serves when it has that class
# with a `preset`, `init_params`, `resident_params`, `resident_specs`,
# `init_cache`, `decode_step`, `prefill_chunk` (gpt2's signatures), and its
# word on what the cache's leaves are, each [layers, slots, ...]:
# `CACHE_TOKEN_AXIS`, the leaves that hold a value a token and which of
# their axes counts the tokens (a prefix leaves rows behind, which the pool
# keeps by the block; a slot's stale rows lie past its position), and
# `CACHE_STATE` (optional, default none), the leaves that hold a slot's
# recurrent state and have no token axis (a prefix leaves the state at its
# end behind, which the pool keeps as a snapshot; a slot is zeroed when a
# request is placed in it, and a step leaves an inactive slot's state as it
# was). A family may name both kinds (granite, kimi: the pool then keeps, under
# one hash, a prefix's rows by the block and the state at its end, and a hit
# needs both; exaone, mimo: the state is a sliding-window layer's last rows, a ring). A leaf neither names is the programs' own (`counts`).
#
# What a family borrows and what it holds. `models/lm.py` ("The serving
# families") has what every family needs and none owns: seeded weights made
# a layer at a time into a stack (`normal`, `ones`, `layer_program`,
# `stack_layers`, `resident_params`, `layer_weights`), the product that
# keeps a float32 activation whole (`dot`, `weight`; `ops/pieces.py`), the
# short convolution (`short_conv`), and the lanes of a chunk: the contract
# of `prefill_chunk`, the split into every slot's first lane and the rest
# (`split_lanes`, `join_lanes`, `last_valid_lane`) and the loop over the
# slots that prefill (`each_slot`, `slot_lanes`, `put_lanes`), whose
# docstring is the rule on where a body takes its weights from. A family
# module holds its config and presets, its `_init_layer` bodies, its
# cache's leaves, its layers' arithmetic and its two programs, and nothing
# that another family has too; it imports no underscore name of another.
_SERVING = {"gpt2": ("gpt2", "GPT2Config"),
            "kanana": ("deepseek", "DeepseekConfig"),
            "deepseek": ("deepseek", "DeepseekConfig"),
            "brumby": ("brumby", "BrumbyConfig"),
            "granite": ("granite", "GraniteConfig"),
            "kimi": ("kimi", "KimiConfig"),
            "keye": ("keye", "KeyeConfig"),
            "solar": ("solar", "KimiConfig"),
            "nemotron": ("nemotron", "NemotronConfig"),
            "longcat": ("longcat", "LongcatConfig"),
            "kexaone": ("exaone", "ExaoneConfig"),
            "mimo": ("mimo", "MimoConfig")}


def serving_family(preset: str):
    """(family name, module, config class) of a serving preset."""
    import importlib

    word = preset.split("-", 1)[0]
    if word not in _SERVING:
        raise ValueError(f"no serving family has the preset {preset!r}: "
                         f"presets start with one of {sorted(_SERVING)}")
    module, config = _SERVING[word]
    mod = importlib.import_module(f"ray_tpu.models.{module}")
    return module, mod, getattr(mod, config)


def __getattr__(name):
    if name in ("llama", "moe", "deepseek", "brumby", "granite", "kimi",
                "keye", "solar", "nemotron", "mamba2", "longcat", "mla",
                "exaone", "mimo"):
        import importlib

        return importlib.import_module(f"ray_tpu.models.{name}")
    raise AttributeError(f"module 'ray_tpu.models' has no attribute {name!r}")
