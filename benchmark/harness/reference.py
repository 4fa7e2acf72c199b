"""The plain reference's shared pieces: what several architectures'
references are made of, in straightforward ``jax.numpy`` and float32,
written from the published descriptions of the models. No kernel, no
cache, no batching, no scan, and no code shared with
``paddle_tpu/models``; it reads the program's weights by their names and
nothing else.

An architecture's own reference is ``benchmark/architectures/<name>.py``
(the configuration's file names it): its ``layer`` and, where it differs,
its feed-forward live there, and give these functions their ``layer_fn``.
Here: RMSNorm, the rotary embedding in the rotate-half form, causal
grouped-query attention (query head h reads key/value head
h // (heads / kv_heads); a head's size is the file's ``head_dim`` where it
states one), SwiGLU, and the loop of a pre-norm decoder over its layers
with an untied or tied head.

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
    hd = c.get("head_dim") or d // nh
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


def layer_slice(params, i):
    return jax.tree.map(lambda a: a[i], params["layers"])


def hidden(params, ids, c, layer_fn):
    """Final hidden states [S, D] of one sequence of ids, after the last
    norm, and the summed balance loss. ``layer_fn(x, w)`` is the
    architecture's ``layer`` with ``c`` bound, returning (x, balance loss
    or 0); a caller may pass it jitted, so that one layer's float32
    weights exist at a time."""
    x = params["embed"][ids].astype(F32)
    balance = jnp.zeros((), F32)
    for i in range(c["num_hidden_layers"]):
        x, b = layer_fn(x, layer_slice(params, i))
        balance = balance + b
    return rms_norm(x, params["ln_f"].astype(F32), c["rms_norm_eps"]), balance


def head_of(params, c):
    return params["embed"] if c.get("tie_word_embeddings") \
        else params["lm_head"]


def logits_at(params, ids, c, positions, layer_fn):
    """Float32 logits [len(positions), V] of one sequence."""
    x, _ = hidden(params, ids, c, layer_fn)
    return x[jnp.asarray(positions)] @ head_of(params, c).astype(F32).T


def loss(params, ids, c, layer_fn):
    """Mean next-token cross entropy of one sequence [S + 1], plus the
    balance loss at the configuration's ``aux_loss_alpha``."""
    x, balance = hidden(params, ids[:-1], c, layer_fn)
    logp = jax.nn.log_softmax(x @ head_of(params, c).astype(F32).T, -1)
    ce = -jnp.take_along_axis(logp, ids[1:, None], -1).mean()
    return ce + c.get("aux_loss_alpha", 0.0) * balance


def decoder_of(layer):
    """``(logits_at, loss)`` as an architecture's module gives them, for a
    decoder that differs from the loop above in its ``layer`` alone."""
    def logits_of(params, ids, c, positions, layer_fn=None):
        return logits_at(params, ids, c, positions,
                         layer_fn or (lambda x, w: layer(x, w, c)))

    def loss_of(params, ids, c):
        return loss(params, ids, c, lambda x, w: layer(x, w, c))

    return logits_of, loss_of
