"""The GPT-2 family: what the benchmark needs to know about one model family.

Four things live here and nowhere in the harness:

1. the plain reference (`reference_logits`, `reference_loss`): GPT-2 as
   published (Radford et al. 2019; learned position embeddings, pre-LN
   blocks, causal softmax attention, tanh-GELU MLP of four times the
   width, final LayerNorm, unembedding tied to the token table, mean
   next-token cross-entropy), in plain `jax.numpy` and float32 under
   `jax.default_matmul_precision("highest")`. It imports nothing from
   `ray_tpu.models`; it reads the program's parameter tree, whose layout
   (`wte`, `wpe`, `blocks` stacked on a leading layer axis, `ln_f`) is the
   one thing it takes from the program;
2. the arithmetic (`train_flops_per_token`): what a token costs, and what
   is counted;
3. how the program trains this family (`build_train`) and serves it
   (`build_app`, `BenchServer`) through its normal entry points;
4. the tokenizer the serving cells pass to the engine (`CharTokenizer`).
"""

from __future__ import annotations

import math

# ----------------------------------------------------------- configuration


def program_sizes(model: dict) -> dict:
    """A configuration file's `model` object (Hugging Face's key names, as
    in the source) in the names of the program's `GPT2Config`."""
    return {"vocab_size": model["padded_vocab_size"],
            "n_layer": model["n_layer"], "n_head": model["n_head"],
            "d_model": model["n_embd"],
            "d_ff": model.get("n_inner") or 4 * model["n_embd"]}


def program_config(model: dict, **extra):
    from ray_tpu.models import gpt2

    return gpt2.GPT2Config(**program_sizes(model),
                           max_seq_len=model["n_positions"], **extra)


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Floating-point operations the forward and backward passes need for
    one token of a `seq_len` sequence: 6 for each weight of a matrix
    multiplication (per layer 4·d² of attention and 8·d² of the MLP; the
    unembedding's V·d at the published vocabulary, not the padded one)
    plus 12·L·d·T for the attention scores and their product with the
    values (PaLM's convention: the causal mask is not discounted). Not
    counted: biases, LayerNorms, the position table, the embedding gather,
    the optimizer, and anything recomputed by rematerialization."""
    d, layers = model["n_embd"], model["n_layer"]
    inner = model.get("n_inner") or 4 * d
    matmul_weights = layers * (4 * d * d + 2 * d * inner) \
        + model["vocab_size"] * d
    return 6.0 * matmul_weights + 12.0 * layers * d * seq_len


# --------------------------------------------------------------- reference


def _layer_norm(x, scale, bias, eps=1e-5):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def reference_logits(params, tokens, n_head: int):
    """tokens [B, T] int32 -> logits [B, T, vocab] float32."""
    import jax
    import jax.numpy as jnp

    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    with jax.default_matmul_precision("highest"):
        wte, wpe = f32(params["wte"]), f32(params["wpe"])
        batch, seq = tokens.shape
        x = wte[tokens] + wpe[:seq][None]
        width = x.shape[-1]
        head = width // n_head
        causal = jnp.tril(jnp.ones((seq, seq), bool))

        def block(x, p):
            p = f32(p)
            h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"])
            qkv = h @ p["attn"]["wqkv"] + p["attn"]["bqkv"]
            q, k, v = (a.reshape(batch, seq, n_head, head)
                       .transpose(0, 2, 1, 3)
                       for a in jnp.split(qkv, 3, axis=-1))
            scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(head)
            scores = jnp.where(causal, scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            mixed = (probs @ v).transpose(0, 2, 1, 3).reshape(
                batch, seq, width)
            x = x + mixed @ p["attn"]["wo"] + p["attn"]["bo"]
            h = _layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"])
            h = _gelu_tanh(h @ p["mlp"]["wi"] + p["mlp"]["bi"])
            return x + h @ p["mlp"]["wo"] + p["mlp"]["bo"], None

        x, _ = jax.lax.scan(block, x, params["blocks"])
        ln_f = f32(params["ln_f"])
        return _layer_norm(x, ln_f["scale"], ln_f["bias"]) @ wte.T


def reference_loss(params, tokens, n_head: int):
    """tokens [B, T+1] int32 -> mean next-token cross-entropy, float32."""
    import jax
    import jax.numpy as jnp

    logits = reference_logits(params, tokens[:, :-1], n_head)
    logp = jax.nn.log_softmax(logits, axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(gold)


# ---------------------------------------------------------------- training

# The step program's own loss on its first batch (bf16 activations and
# matmuls, at the seed's initial weights, before any update) against the
# float32 reference's on the same batch. Measured on the v5e in PR 22,
# over nine seeds and both training cells: the two differ by 2e-5..4.4e-4
# at a loss of ~11 (the widest on the whole batch of 32 at XL on four
# chips). The tolerance is over four times the widest; matmuls or a softmax
# below bf16, float16 accumulation, a wrong mask or a missing layer move
# the loss by 1e-2 or more.
TRAIN_LOSS_TOLERANCE = 2e-3


def seeded_params(cfg, seed: int, out_shardings=None):
    """The seed's initial weights, made on the device in one jitted call
    of the program's own `init_params`: what the trainer's `init_fn`, the
    replica and both reference checks start from."""
    import jax

    from ray_tpu.models import gpt2

    kwargs = {} if out_shardings is None else {"out_shardings": out_shardings}
    return jax.jit(lambda key: gpt2.init_params(key, cfg), **kwargs)(
        jax.random.key(seed))


class TrainProgram:
    """The program's train step for one configuration on this process's
    devices, built through `train/spmd.compile_gpt2_train`."""

    def __init__(self, model: dict, job: dict, devices, seed: int):
        import jax

        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        from ray_tpu.train.spmd import compile_gpt2_train, default_optimizer

        self.jax, self.model, self.seed = jax, model, seed
        self.seq_len = job["seq_len"]
        self.global_batch = job["global_batch"]
        self.cfg = program_config(model, remat=True,
                                  remat_policy=job["remat"])
        mesh_axes = job.get("mesh") or {"dp": len(devices)}
        self.mesh = build_mesh(MeshConfig(**mesh_axes), devices=devices)
        self.program = compile_gpt2_train(
            self.cfg, self.mesh,
            optimizer=default_optimizer(total_steps=job["total_steps"]))
        self.batch_sharding = self.program.batch_sharding

    def init_state(self):
        return self.program.init_fn(self.jax.random.key(self.seed))

    def compile_step(self, state):
        """The step compiled ahead of time for the job's batch: the
        executable the loop calls, whose `memory_analysis()` sizes it."""
        import jax.numpy as jnp

        data = {"tokens": self.jax.ShapeDtypeStruct(
            (self.global_batch, self.seq_len + 1), jnp.int32,
            sharding=self.batch_sharding)}
        return self.program.step_fn.lower(state, data).compile()

    def put_batch(self, tokens):
        return {"tokens": self.jax.device_put(tokens, self.batch_sharding)}

    def check_against_reference(self, tokens, step_loss: float,
                                slice_size: int) -> dict:
        """The reference's loss on the whole of the step program's first
        batch, at the seed's initial weights sharded as the job shards
        them, against the loss the compiled step itself reported for that
        batch (its first step computes it before any update). The
        reference takes the batch `slice_size` sequences at a time, so
        that float32 logits never need more than a slice's memory."""
        jax = self.jax
        params = seeded_params(self.cfg, self.seed,
                               self.program.state_sharding.params)
        n_head = self.model["n_head"]
        reference = jax.jit(lambda p, b: reference_loss(
            p, b["tokens"], n_head))
        total = 0.0
        for at in range(0, len(tokens), slice_size):
            part = tokens[at:at + slice_size]
            total += float(reference(params, self.put_batch(part))) \
                * len(part)
        want = total / len(tokens)
        return {"program_loss": step_loss, "reference_loss": want,
                "sequences": len(tokens), "tolerance": TRAIN_LOSS_TOLERANCE,
                "ok": abs(step_loss - want) <= TRAIN_LOSS_TOLERANCE}


def build_train(model: dict, job: dict, devices, seed: int) -> TrainProgram:
    return TrainProgram(model, job, devices, seed)


# ----------------------------------------------------------------- serving


class CharTokenizer:
    """One character per token id, both ways: id `i` is the code point
    0x20000 + i (a plane with no surrogates and room for any vocabulary
    under 2^16·3). An SSE event's text length is therefore its token
    count, no token is ever held back as a partial character, and the
    client recovers the ids of a reply from its text. Prompts go as
    `prompt_ids`, so `encode` only serves the engine's empty-prompt path.
    `eos_id` is GPT-2's own end-of-text id."""

    BASE = 0x20000
    eos_id = 50256

    def encode(self, text: str) -> list:
        import numpy as np

        return (np.frombuffer(text.encode("utf-32-le"), np.uint32)
                .astype(np.int64) - self.BASE).tolist()

    def decode(self, ids) -> str:
        # the engine decodes a stream's whole reply at every poll: at C speed
        import numpy as np

        return (np.asarray(ids, np.int64) + self.BASE).astype(
            "<u4").tobytes().decode("utf-32-le")


def build_app(config: dict, seed: int, num_tpu_chips: int):
    """`serve/llm.build_openai_app`'s deployment, option for option, with
    `BenchServer` in `OpenAIServer`'s place (the function takes no server
    class; see PERF.md's open questions)."""
    from ray_tpu.serve.api import deployment

    model, deploy = config["model"], config["deployment"]
    actor_options = {"num_cpus": 1}
    if num_tpu_chips:
        actor_options["num_tpu_chips"] = num_tpu_chips
    model_id = config["name"]
    from families.gpt2_server import BenchServer

    dep = deployment(BenchServer, name=f"openai-{model_id}",
                     num_replicas=1, ray_actor_options=actor_options,
                     max_ongoing_requests=deploy["max_batch"] * 2,
                     slo_config=None)
    return dep.bind(
        model_id=model_id, preset=deploy["preset"],
        model_overrides=program_sizes(model), max_batch=deploy["max_batch"],
        max_seq_len=deploy["max_seq_len"], checkpoint=None, seed=seed,
        tokenizer=CharTokenizer(), scheduler=deploy["scheduler"],
        enable_prefix_caching=deploy["enable_prefix_caching"],
        prefill_chunk_size=deploy["prefill_chunk_size"],
        kv_blocks=deploy["kv_blocks"],
        kv_block_size=deploy["kv_block_size"])


def request_body(request: dict) -> dict:
    """The `/v1/completions` body of one generated request."""
    return {"prompt_ids": request["prompt_ids"],
            "max_tokens": request["max_tokens"],
            "temperature": request["temperature"],
            "top_p": request["top_p"], "stream": True}


REQUEST_PATH = "/v1/completions"

# the served token's logit under the reference must be within this of its
# row's maximum: 0.125 is eight bf16 steps at the top logit's magnitude
# (2..4 for seeded weights); a wrong token sits whole units below
# (chip_smoke.py's check and reasoning, PR 21).
SERVE_LOGIT_TOLERANCE = 0.125


def check_served(config: dict, seed: int, served: list) -> dict:
    """With the chip free: the seed's weights, each served sequence once
    through the reference, and at every generated position the served
    token's logit against the row's maximum. Random weights give
    near-ties, so token equality would be a coin toss; this is not."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt2

    model, deploy = config["model"], config["deployment"]
    cfg = gpt2.GPT2Config.preset(          # as the replica builds it
        deploy["preset"], **program_sizes(model),
        max_seq_len=deploy["max_seq_len"])
    params = seeded_params(cfg, seed)
    n_head = model["n_head"]
    forward = jax.jit(lambda p, t: reference_logits(p, t, n_head))
    if not served:
        return {"ok": False, "error": "no greedy reply ended in the window"}
    rows = [s["prompt_ids"] + s["token_ids"] for s in served]
    width = -(-max(len(r) for r in rows) // 128) * 128
    worst, tokens_checked = 0.0, 0
    for s, row in zip(served, rows):      # causal: padding after a row
        tokens = np.zeros((1, width), np.int32)        # cannot reach it
        tokens[0, :len(row)] = row
        logits = np.asarray(forward(params, jnp.asarray(tokens)))[0]
        n_prompt = len(s["prompt_ids"])
        for j, token in enumerate(s["token_ids"]):
            at = logits[n_prompt - 1 + j]
            if not np.all(np.isfinite(at)):
                return {"ok": False, "error": "non-finite reference logits"}
            worst = max(worst, float(at.max() - at[token]))
            tokens_checked += 1
    return {"ok": worst <= SERVE_LOGIT_TOLERANCE, "worst_gap": worst,
            "tolerance": SERVE_LOGIT_TOLERANCE,
            "tokens_checked": tokens_checked, "replies": len(served)}
