"""Model families (pure JAX, TPU-first): gpt2, llama (GQA/RoPE/SwiGLU),
moe (OLMoE / Mixtral sparse MoE: dropless sort-and-grouped-matmul routing,
one-hot dispatch under expert parallelism), deepseek (DeepSeek-V3's layer
for serving: latent attention over a latent cache, shared experts)."""

from ray_tpu.models import gpt2

__all__ = ["gpt2", "llama", "moe", "deepseek", "serving_family"]

# The families `serve/llm.LLMEngine` takes: a preset's first word -> the
# module and its config class. A module serves when it has that class
# with a `preset`, `init_params`, `resident_params`, `resident_specs`,
# `init_cache`, `decode_step`, `prefill_chunk` (gpt2's signatures) and
# `CACHE_TOKEN_AXIS`: the cache's leaves that hold a value a token, each
# [layers, slots, ...], and which of their axes counts the tokens.
_SERVING = {"gpt2": ("gpt2", "GPT2Config"),
            "kanana": ("deepseek", "DeepseekConfig"),
            "deepseek": ("deepseek", "DeepseekConfig")}


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
    if name in ("llama", "moe", "deepseek"):
        import importlib

        return importlib.import_module(f"ray_tpu.models.{name}")
    raise AttributeError(f"module 'ray_tpu.models' has no attribute {name!r}")
