"""DeepSeekMoE decoder (arXiv:2401.06066): the dense decoder's attention
with one key/value head a query head; the feed-forward is
``n_shared_experts`` always-on experts plus the ``num_experts_per_tok``
highest of ``n_routed_experts`` by a softmax router. Keys and values are
the only cache.

Departures, as the configuration's file states them (all three are how
the program computes it, none is the benchmark's): the chosen experts'
weights renormalised to sum to one, whatever the source's
``norm_topk_prob`` (``renormalise_routed_weights``); capacity dispatch
with drops (a slot over its expert's capacity, in token-major order,
contributes nothing); a switch-style balance loss.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..harness import reference as R
from ..harness import work
from ..harness.paged_calls import decode_step, make_cache, prefill  # noqa: F401

# -- the plain reference ------------------------------------------------


def capacity(c: dict, tokens: int) -> int:
    """Slots an expert has: tokens * k / experts * factor, rounded up,
    to a whole number of 128 lanes from 128 on, at least 8 and at most
    the tokens there are (the program's stated rule)."""
    cap = math.ceil(tokens * c["num_experts_per_tok"]
                    / c["n_routed_experts"] * c["capacity_factor"] - 1e-4)
    if cap >= 128:
        cap = -(-cap // 128) * 128
    return max(8, min(tokens, cap))


def moe_ffn(x, w, c):
    """Returns (output [T, D], balance loss). Every expert is computed on
    every token and the combine matrix holds the routing: plain, and
    affordable on the sample the check uses."""
    t = x.shape[0]
    e, k = c["n_routed_experts"], c["num_experts_per_tok"]
    prob = jax.nn.softmax(x @ w["router"], -1)                 # [T, E]
    topv, topi = jax.lax.top_k(prob, k)
    if c["norm_topk_prob"] or c.get("renormalise_routed_weights"):
        topv = topv / topv.sum(-1, keepdims=True)
    chosen = jax.nn.one_hot(topi.reshape(-1), e, dtype=R.F32)  # [T*k, E]
    # token-major: a slot's place in its expert's buffer is the number of
    # earlier slots that chose the same expert
    place = ((jnp.cumsum(chosen, 0) - chosen) * chosen).sum(-1)
    keep = (place < capacity(c, t)).astype(R.F32)
    combine = (chosen * (topv.reshape(-1) * keep)[:, None]
               ).reshape(t, k, e).sum(1)                       # [T, E]
    # expert i on every token: silu(x W_gate[i]) * (x W_up[i]), W_down[i]
    inner = (jax.nn.silu(jnp.einsum("td,edf->etf", x, w["e_gate"]))
             * jnp.einsum("td,edf->etf", x, w["e_up"]))
    every = jnp.einsum("etf,efd->etd", inner, w["e_down"])
    routed = jnp.einsum("te,etd->td", combine, every)
    shared = R.swiglu(x, w["s_gate"], w["s_up"], w["s_down"])
    balance = e * jnp.sum(prob.mean(0) * chosen.reshape(t, k, e).sum(1).mean(0))
    return routed + shared, balance


def layer(x, w, c):
    """One expert layer on one sequence [S, D]; weights of any float
    type, computed in float32. Returns (x, balance loss)."""
    w = jax.tree.map(lambda a: a.astype(R.F32), w)
    x = x.astype(R.F32)
    x = x + R.attention(R.rms_norm(x, w["ln1"], c["rms_norm_eps"]), w, c)
    y, balance = moe_ffn(R.rms_norm(x, w["ln2"], c["rms_norm_eps"]), w, c)
    return x + y, balance


logits_at, loss = R.decoder_of(layer)


# -- the counts ---------------------------------------------------------

def layer_params(c: dict, active: bool = False) -> int:
    """One expert layer: attention, router, routed experts (all of them,
    or the ``num_experts_per_tok`` a token uses), shared experts, norms."""
    d, fe = c["hidden_size"], c["moe_intermediate_size"]
    routed = c["num_experts_per_tok"] if active else c["n_routed_experts"]
    return (work.attn_params(c) + d * c["n_routed_experts"]
            + routed * 3 * d * fe
            + 3 * d * c["n_shared_experts"] * fe + 2 * d)


def param_count(c: dict, active: bool = False) -> int:
    return work.decoder_params(c, layer_params(c, active), active)


def model_flops_per_token(c: dict, seq_len: int) -> float:
    return work.train_flops_per_token(c, param_count(c, True), seq_len)


kv_bytes_per_token = work.kv_bytes_per_token


def state_bytes_per_slot(c: dict) -> int:
    """Nothing beside keys and values."""
    return 0
