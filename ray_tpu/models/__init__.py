"""Model families (pure JAX, TPU-first): gpt2, llama (GQA/RoPE/SwiGLU),
moe (OLMoE / Mixtral sparse MoE: dropless sort-and-grouped-matmul routing,
one-hot dispatch under expert parallelism)."""

from ray_tpu.models import gpt2

__all__ = ["gpt2", "llama", "moe"]


def __getattr__(name):
    if name in ("llama", "moe"):
        import importlib

        return importlib.import_module(f"ray_tpu.models.{name}")
    raise AttributeError(f"module 'ray_tpu.models' has no attribute {name!r}")
