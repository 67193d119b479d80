"""Model zoo: the assigned architectures as composable JAX modules."""

from .model import (
    init_lm,
    lm_specs,
    lm_loss,
    lm_prefill,
    lm_decode_step,
    lm_mixed_step,
    chunked_prefill_ok,
    lm_caches,
    lm_cache_specs,
)

__all__ = [
    "init_lm",
    "lm_specs",
    "lm_loss",
    "lm_prefill",
    "lm_decode_step",
    "lm_mixed_step",
    "chunked_prefill_ok",
    "lm_caches",
    "lm_cache_specs",
]
