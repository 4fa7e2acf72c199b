"""Dense pre-norm decoder: grouped-query attention, then a SwiGLU
feed-forward, every layer alike, keys and values the only cache.

Mistral-7B (arXiv:2310.06825; v0.3 has no sliding window): RMSNorm,
rotary embedding in the rotate-half form, query head h reads key/value
head h // (heads / kv_heads), untied head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..harness import reference as R
from ..harness import work
from ..harness.paged_calls import decode_step, make_cache, prefill  # noqa: F401

# -- the plain reference ------------------------------------------------


def layer(x, w, c):
    """One decoder layer on one sequence [S, D]; weights of any float
    type, computed in float32. Returns (x, 0: no balance loss)."""
    w = jax.tree.map(lambda a: a.astype(R.F32), w)
    x = x.astype(R.F32)
    x = x + R.attention(R.rms_norm(x, w["ln1"], c["rms_norm_eps"]), w, c)
    h = R.rms_norm(x, w["ln2"], c["rms_norm_eps"])
    return x + R.swiglu(h, w["gate"], w["up"], w["down"]), \
        jnp.zeros((), R.F32)


logits_at, loss = R.decoder_of(layer)


# -- the counts ---------------------------------------------------------

def layer_params(c: dict) -> int:
    """Attention, the three SwiGLU matrices, two norms."""
    return (work.attn_params(c)
            + 3 * c["hidden_size"] * c["intermediate_size"]
            + 2 * c["hidden_size"])


def param_count(c: dict, active: bool = False) -> int:
    return work.decoder_params(c, layer_params(c), active)


def model_flops_per_token(c: dict, seq_len: int) -> float:
    return work.train_flops_per_token(c, param_count(c, True), seq_len)


kv_bytes_per_token = work.kv_bytes_per_token


def state_bytes_per_slot(c: dict) -> int:
    """Nothing beside keys and values."""
    return 0
