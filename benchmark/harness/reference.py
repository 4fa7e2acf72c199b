"""The plain reference: each configuration's forward pass (and, for
training, its loss) in straightforward ``jax.numpy`` and float32, written
from the published descriptions of the models. No kernel, no cache, no
batching, no scan, and no code shared with ``paddle_tpu/models``; it
reads the program's weights by their names and nothing else.

Mistral-7B (arXiv:2310.06825; v0.3 has no sliding window): pre-norm
decoder, RMSNorm, rotary embedding in the rotate-half form, grouped-query
attention (query head h reads key/value head h // (heads / kv_heads)),
SwiGLU feed-forward, untied head.

DeepSeekMoE (arXiv:2401.06066): the same attention with one key/value
head a query head; the feed-forward is ``n_shared_experts`` always-on
experts plus the ``num_experts_per_tok`` highest of ``n_routed_experts``
by a softmax router. Departures, as the configuration's file states them
(all three are how the program computes it, none is the benchmark's):
the chosen experts' weights renormalised to sum to one, whatever the
source's ``norm_topk_prob`` (``renormalise_routed_weights``); capacity
dispatch with drops
(a slot over its expert's capacity, in token-major order, contributes
nothing); a switch-style balance loss.

On a TPU a float32 product runs in lower precision unless asked
otherwise, so every caller runs these under
``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotary(x, theta):
    """x [S, heads, d] at positions 0..S-1, rotate-half form."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(x, w, c):
    s, d = x.shape
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nh
    q = rotary((x @ w["wq"]).reshape(s, nh, hd), c["rope_theta"])
    k = rotary((x @ w["wk"]).reshape(s, nkv, hd), c["rope_theta"])
    v = (x @ w["wv"]).reshape(s, nkv, hd)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    score = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    score = jnp.where(jnp.tril(jnp.ones((s, s), bool)), score, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, -1), v)
    return out.reshape(s, nh * hd) @ w["wo"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


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
    chosen = jax.nn.one_hot(topi.reshape(-1), e, dtype=F32)    # [T*k, E]
    # token-major: a slot's place in its expert's buffer is the number of
    # earlier slots that chose the same expert
    place = ((jnp.cumsum(chosen, 0) - chosen) * chosen).sum(-1)
    keep = (place < capacity(c, t)).astype(F32)
    combine = (chosen * (topv.reshape(-1) * keep)[:, None]
               ).reshape(t, k, e).sum(1)                       # [T, E]
    # expert i on every token: silu(x W_gate[i]) * (x W_up[i]), W_down[i]
    inner = (jax.nn.silu(jnp.einsum("td,edf->etf", x, w["e_gate"]))
             * jnp.einsum("td,edf->etf", x, w["e_up"]))
    every = jnp.einsum("etf,efd->etd", inner, w["e_down"])
    routed = jnp.einsum("te,etd->td", combine, every)
    shared = swiglu(x, w["s_gate"], w["s_up"], w["s_down"])
    balance = e * jnp.sum(prob.mean(0) * chosen.reshape(t, k, e).sum(1).mean(0))
    return routed + shared, balance


def layer(x, w, c):
    """One decoder layer on one sequence [S, D]; weights of any float
    type, computed in float32. Returns (x, balance loss or 0)."""
    w = jax.tree.map(lambda a: a.astype(F32), w)
    x = x.astype(F32)
    x = x + attention(rms_norm(x, w["ln1"], c["rms_norm_eps"]), w, c)
    h = rms_norm(x, w["ln2"], c["rms_norm_eps"])
    if "router" in w:
        y, balance = moe_ffn(h, w, c)
        return x + y, balance
    return x + swiglu(h, w["gate"], w["up"], w["down"]), jnp.zeros((), F32)


def layer_slice(params, i):
    return jax.tree.map(lambda a: a[i], params["layers"])


def hidden(params, ids, c, layer_fn=None):
    """Final hidden states [S, D] of one sequence of ids, after the last
    norm, and the summed balance loss. ``layer_fn(x, w)`` lets a caller
    pass a jitted ``layer`` (``c`` bound), so that one layer's float32
    weights exist at a time."""
    layer_fn = layer_fn or (lambda x, w: layer(x, w, c))
    x = params["embed"][ids].astype(F32)
    balance = jnp.zeros((), F32)
    for i in range(c["num_hidden_layers"]):
        x, b = layer_fn(x, layer_slice(params, i))
        balance = balance + b
    return rms_norm(x, params["ln_f"].astype(F32), c["rms_norm_eps"]), balance


def head_of(params, c):
    return params["embed"] if c.get("tie_word_embeddings") \
        else params["lm_head"]


def logits_at(params, ids, c, positions, layer_fn=None):
    """Float32 logits [len(positions), V] of one sequence."""
    x, _ = hidden(params, ids, c, layer_fn)
    return x[jnp.asarray(positions)] @ head_of(params, c).astype(F32).T


def loss(params, ids, c):
    """Mean next-token cross entropy of one sequence [S + 1], plus the
    balance loss at the configuration's ``aux_loss_alpha``."""
    x, balance = hidden(params, ids[:-1], c)
    logp = jax.nn.log_softmax(x @ head_of(params, c).astype(F32).T, -1)
    ce = -jnp.take_along_axis(logp, ids[1:, None], -1).mean()
    return ce + c.get("aux_loss_alpha", 0.0) * balance
