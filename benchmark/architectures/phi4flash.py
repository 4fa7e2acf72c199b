"""Phi-4-mini-flash-reasoning (microsoft, ``model_type`` ``phi4flash``; the
SambaY decoder-hybrid-decoder stack of arXiv:2507.06607): four kinds of
layer, one layer's keys and values read by eight.

Written from the paper and the public ``modeling_phi4flash.py`` as known
here (nothing could be fetched). On a sequence ``x`` [S, D]; sizes as
published: D 2560; 40 query / 20 key-value heads of 64; FFN 10,240;
Mamba ``d_inner`` 5120, ``d_state`` 16, ``dt_rank`` 160, ``d_conv`` 4;
window 512; 32 layers, ``i`` counted from 0, ``H = 16`` (half the depth):

Every layer: ``x <- x + mixer_i(LN(x; ln1)); x <- x + (silu(g W_gate) *
(g W_up)) W_down`` with ``g = LN(x; ln2)``, ``[W_gate | W_up]`` stored
fused as ``gate_up``; LN is LayerNorm with gain and bias, eps 1e-5. No
positional encoding. ``x_0 = embed[ids]``; after the last layer ``LN(x;
ln_f)`` and ``logits = x embed^T``.

1. ``i`` even, ``i <= H`` -- Mamba-1 (S6): ``[x | z] = h W_in``; ``x_t <-
   silu(sum_j conv_w[j] x_{t-3+j} + conv_b)`` (causal, depthwise, zeros
   before t = 0); ``[d | B | C] = x W_x``; ``D_t = softplus(d W_dt +
   b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(D_t A) . s_{t-1} + (D_t
   x_t) (x) B_t`` with ``s`` [d_state, d_inner], ``s_{-1} = 0``; ``y_t =
   C_t s_t + D . x_t``; ``out = (y * silu(z)) W_out``. Layer ``H`` also
   hands ``m = y`` to every GMU at the same position.
2. ``i`` odd, ``i < H`` -- window attention: ``[q | k | v] = h W_qkv +
   b_qkv``; differential: query heads pair up (2j, 2j+1) and read
   key/value pair ``j // 2`` (heads 2p, 2p+1): ``a1 = softmax(q1 k1^T / 8
   + mask) [v1 | v2]``, ``a2 = softmax(q2 k2^T / 8 + mask) [v1 | v2]``,
   ``a_j = (1 - l0) * RMSNorm_128(a1 - l a2; subln)``, ``l = exp(lq1 .
   lk1) - exp(lq2 . lk2) + l0``, ``l0 = 0.8 - 0.6 exp(-0.3 i)``; the mask
   lets position t see t - 511 .. t; ``out = [a_0 .. a_19] W_o + b_o``.
3. ``i = H + 1`` -- the same with a full causal mask. ITS keys and
   values are the only ones the cross-decoder reads.
4. ``i`` even, ``i >= H + 2`` -- GMU: ``out = (m * silu(h W_1)) W_2``.
5. ``i`` odd, ``i >= H + 3`` -- cross-attention: ``q = h W_q + b_q``
   only; keys and values are layer ``H + 1``'s; full causal mask; the
   differential form with the layer's own lambda vectors and ``subln``.

Departures, none in the mathematics: weights are read by the program's
names (a segment's layers stacked under ``params[segment][part]``;
``A_log`` lies ``[d_state, d_inner]``, ``conv_w`` ``[d_conv, d_inner]``
with tap j on the input ``d_conv - 1 - j`` tokens back, ``lam`` rows
lq1, lk1, lq2, lk2); the recurrence runs token by token, attention as
whole [S, S] products over heads of 64, no kernel, no cache, no scan over
layers. What the config does not settle is listed under ``assumed`` in
the configuration's file. No code of ``paddle_tpu/models`` is used;
everything is float32, and every caller sets
``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp

from ..harness import reference as R
from ..harness.session import say

# -- the plain reference ------------------------------------------------


def layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def feed_forward(x, w, c):
    g = layer_norm(x, w["ln2_g"], w["ln2_b"], c["layer_norm_eps"])
    gu = g @ w["gate_up"]
    f = c["intermediate_size"]
    return x + (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w["down"]


def mamba(h, w, c):
    """Kind 1 on h [S, D]: (out [S, D], y [S, d_inner])."""
    s = h.shape[0]
    di = c["mamba_expand"] * c["hidden_size"]
    n, k, r = c["mamba_d_state"], c["mamba_d_conv"], c["mamba_dt_rank"]
    xz = h @ w["in_proj"]
    x, z = xz[:, :di], xz[:, di:]
    padded = jnp.concatenate([jnp.zeros((k - 1, di), R.F32), x])
    x = jax.nn.silu(sum(w["conv_w"][j] * padded[j:j + s] for j in range(k))
                    + w["conv_b"])
    dbc = x @ w["x_proj"]
    delta = jax.nn.softplus(dbc[:, :r] @ w["dt_proj"] + w["dt_bias"])
    a = -jnp.exp(w["A_log"])                              # [d_state, di]

    def token(state, t):
        x_t, d_t, b_t, c_t = t
        state = jnp.exp(d_t[None, :] * a) * state \
            + b_t[:, None] * (d_t * x_t)[None, :]
        return state, c_t @ state + w["D"] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((n, di), R.F32),
                        (x, delta, dbc[:, r:r + n], dbc[:, r + n:]))
    return (y * jax.nn.silu(z)) @ w["out_proj"], y


def differential(q, k, v, w, index, c, window=None):
    """q [S, heads * 64] against k, v [S, kv_heads * 64] of the same
    positions: [S, heads * 64]."""
    s = q.shape[0]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // nh
    q = q.reshape(s, nh // 2, 2, hd)
    k = jnp.repeat(k.reshape(s, nkv // 2, 2, hd), 2, axis=1)
    v = jnp.repeat(v.reshape(s, nkv // 2, 2 * hd), 2, axis=1)
    score = jnp.einsum("qjtd,kjtd->jtqk", q, k) / math.sqrt(hd)
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    score = jnp.where(seen, score, -jnp.inf)
    a = jnp.einsum("jtqk,kjd->qjtd", jax.nn.softmax(score, -1), v)
    l0 = 0.8 - 0.6 * jnp.exp(-0.3 * index)
    lq1, lk1, lq2, lk2 = w["lam"]
    lam = jnp.exp(lq1 @ lk1) - jnp.exp(lq2 @ lk2) + l0
    d = a[:, :, 0] - lam * a[:, :, 1]                      # [S, pairs, 128]
    d = d * jax.lax.rsqrt((d * d).mean(-1, keepdims=True)
                          + c["layer_norm_eps"]) * w["subln"]
    return ((1.0 - l0) * d).reshape(s, nh * hd)


def kind_of(i: int, c) -> str:
    half = c["num_hidden_layers"] // 2
    if i < half:
        return "mamba" if i % 2 == 0 else "window"
    if i <= half + 1:
        return "mamba_mem" if i == half else "full"
    return "gmu" if i % 2 == 0 else "cross"


def weights_of(params, i: int, c):
    """Layer i's weights out of the program's tree."""
    half = c["num_hidden_layers"] // 2
    kind = kind_of(i, c)
    seg, part, j = {
        "mamba": ("mamba_window", "mamba", i // 2),
        "mamba_mem": ("mamba_mem", "mamba", 0),
        "window": ("mamba_window", "attn", i // 2),
        "full": ("full", "attn", 0),
        "gmu": ("gmu_cross", "gmu", (i - half - 2) // 2),
        "cross": ("gmu_cross", "attn", (i - half - 3) // 2)}[kind]
    return jax.tree.map(lambda a: a[j], params[seg][part])


def layer(x, w, c, index=0, carry=None, kind=None):
    """Layer ``index`` (of kind ``kind``, by default what ``kind_of`` says
    of a whole number) on one sequence [S, D]; weights of any float type,
    computed in float32. ``carry`` is what earlier layers hand on: ``m``
    and the full layer's keys and values. Returns (x, carry); called with
    three arguments (the interface the harness names) it is layer 0."""
    w = jax.tree.map(lambda a: a.astype(R.F32), w)
    x = x.astype(R.F32)
    carry = dict(carry or {})
    kind = kind or kind_of(index, c)
    h = layer_norm(x, w["ln1_g"], w["ln1_b"], c["layer_norm_eps"])
    hd = c["hidden_size"] // c["num_attention_heads"]
    nq, nk = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    if kind in ("mamba", "mamba_mem"):
        out, y = mamba(h, w, c)
        if kind == "mamba_mem":
            carry["m"] = y
    elif kind == "gmu":
        out = (carry["m"] * jax.nn.silu(h @ w["w1"])) @ w["w2"]
    else:
        if kind == "cross":
            q = h @ w["wq"] + w["bq"]
            k, v = carry["k"], carry["v"]
        else:
            qkv = h @ w["wqkv"] + w["bqkv"]
            q, k, v = qkv[:, :nq], qkv[:, nq:nq + nk], qkv[:, nq + nk:]
            if kind == "full":
                carry["k"], carry["v"] = k, v
        a = differential(q, k, v, w, index, c,
                         c["sliding_window"] if kind == "window" else None)
        out = a @ w["wo"] + w["bo"]
    return feed_forward(x + out, w, c), carry


_HEAD_ROWS = 32768


class _Frozen(dict):
    """A configuration as a static argument of ``jax.jit``: two with the
    same content are the same key."""

    def _key(self):
        return json.dumps(self, sort_keys=True, default=str)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key() == other._key()


# one layer at a time, so that one layer's float32 weights exist at a time;
# one compiled program a kind (the layer's index is an argument)
_layer = jax.jit(layer, static_argnames=("c", "kind"))


def _hidden(params, ids, c):
    x = params["embed"][ids].astype(R.F32)
    carry, c = {}, _Frozen(c)
    for i in range(c["num_hidden_layers"]):
        x, carry = _layer(x, weights_of(params, i, c), c=c,
                          index=jnp.float32(i), carry=carry,
                          kind=kind_of(i, c))
    return layer_norm(x, params["ln_f_g"].astype(R.F32),
                      params["ln_f_b"].astype(R.F32), c["layer_norm_eps"])


def logits_at(params, ids, c, positions, layer_fn=None):
    """Float32 logits [len(positions), V] of one sequence of ids, the 32
    layers walked by kind with one layer's float32 weights at a time
    (``layer_fn``, the harness's jitted ``layer``, cannot carry ``m``
    and the shared keys and is not used)."""
    x = _hidden(params, ids, c)[jnp.asarray(positions)]
    head = params["embed"]
    return jnp.concatenate(
        [x @ head[i:i + _HEAD_ROWS].astype(R.F32).T
         for i in range(0, head.shape[0], _HEAD_ROWS)], -1)


def loss(params, ids, c):
    """Mean next-token cross entropy of one sequence [S + 1]."""
    logits = logits_at(params, ids[:-1], c, jnp.arange(ids.shape[0] - 1))
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                ids[1:, None], -1).mean()


# -- the counts ---------------------------------------------------------

def _sizes(c: dict):
    d, nh = c["hidden_size"], c["num_attention_heads"]
    return d, d // nh, c["mamba_expand"] * d, c["mamba_d_state"]


def layer_counts(c: dict) -> dict:
    """How many layers of each kind the stack has."""
    half = c["num_hidden_layers"] // 2
    return {"mamba": half // 2 + 1, "window": half // 2, "full": 1,
            "gmu": half // 2 - 1, "cross": half // 2 - 1}


def layer_params(c: dict) -> dict:
    """Parameters of one layer of each kind, its SwiGLU and its two
    LayerNorms (gain and bias) included."""
    d, hd, di, n = _sizes(c)
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    k, r = c["mamba_d_conv"], c["mamba_dt_rank"]
    block = 3 * d * c["intermediate_size"] + 4 * d
    lam = 4 * hd + 2 * hd
    return {
        "mamba": block + d * 2 * di + di * k + di + di * (r + 2 * n)
        + r * di + di + n * di + di + di * d,
        "window": block + d * (nh + 2 * nkv) * hd + (nh + 2 * nkv) * hd
        + nh * hd * d + d + lam,
        "gmu": block + 2 * d * di,
        "cross": block + 2 * (d * nh * hd) + nh * hd + d + lam}


def param_count(c: dict, active: bool = False) -> int:
    """Every parameter: the layers, the one table (embedding and head)
    and the last norm. ``active`` is the same number: the table is
    multiplied by once, as the head."""
    per, n = layer_params(c), layer_counts(c)
    per["full"] = per["window"]
    return (sum(n[k] * per[k] for k in n)
            + c["vocab_size"] * c["hidden_size"] + 2 * c["hidden_size"])


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    """Keys and values of one cached token: ONE layer keeps pages."""
    d, hd, _, _ = _sizes(c)
    return 2 * c["num_key_value_heads"] * hd * bytes_per_value


def ring_tokens(c: dict) -> int:
    """Tokens a window layer's ring holds: the window and one page."""
    return c["sliding_window"] + c.get("ring_page", 16)


def state_bytes_per_slot(c: dict) -> int:
    """What a sequence keeps beside its pages, whatever its length: a ring
    of keys and values a window layer, and a float32 state and the
    convolution's last ``d_conv - 1`` inputs a Mamba layer."""
    _, _, di, n = _sizes(c)
    cnt = layer_counts(c)
    return (cnt["window"] * ring_tokens(c) * kv_bytes_per_token(c)
            + cnt["mamba"] * (4 * n * di + 2 * (c["mamba_d_conv"] - 1) * di))


def attn_flops_per_key(c: dict) -> float:
    """FLOPs of one layer's differential attention a key read by one query
    position: every query head's 64-wide score and 128-wide value product
    (the zero halves the paged kernel multiplies by are not counted)."""
    _, hd, _, _ = _sizes(c)
    return 2.0 * c["num_attention_heads"] * (hd + 2 * hd)


def recurrence_flops_per_token(c: dict) -> float:
    """About 6 FLOPs a state element a Mamba layer a token."""
    _, _, di, n = _sizes(c)
    return 6.0 * layer_counts(c)["mamba"] * n * di


def model_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward FLOPs a token of a sequence of ``seq_len``
    would take (6 a parameter; attention over half the sequence in the
    layers that see all of it and over the window in those that do not,
    three times its forward pass; the recurrence likewise). The family
    has no train step; the number is the interface's."""
    cnt = layer_counts(c)
    keys = (cnt["full"] + cnt["cross"]) * seq_len / 2 \
        + cnt["window"] * min(seq_len / 2, c["sliding_window"])
    return 6.0 * param_count(c, True) + 3.0 * attn_flops_per_key(c) * keys \
        + 3.0 * recurrence_flops_per_token(c)


# -- the serve check's three calls into the program -------------------------

def make_cache(cfg, num_pages: int, page_size: int, sequences: int):
    from paddle_tpu.inference.paged import init_pool
    from paddle_tpu.models import phi4flash

    return init_pool(cfg, num_pages, page_size,
                     state_shapes=phi4flash.state_shapes(cfg),
                     state_rows=sequences,
                     pool_layout=phi4flash.pool_layout(cfg))


def prefill(family, params, ids, cfg, cache, page_rows, slen):
    from paddle_tpu.inference.paged import cache_prefill

    return cache_prefill(family, params, ids, cfg, cache, page_rows, slen,
                         jnp.arange(ids.shape[0]))


def decode_step(family, params, cache, block_tables, lengths, tokens, cfg):
    from paddle_tpu.inference.paged import cache_decode_step

    return cache_decode_step(family, params, cache, block_tables, lengths,
                             tokens, cfg, jnp.arange(tokens.shape[0]))


# -- kernel and program work, found by ``roofline.work`` ---------------------

def _window_keys(ctx) -> float:
    """Keys the window layers' kernel had to read while tracing: every
    slot's live tokens up to the window. The harness counts no minimum a
    slot, so this is min(all live tokens read, window x tokens decoded):
    it reads high by the slots younger than the window, a few of 128 in
    this mix (at most ~2%)."""
    n, c = ctx["counters"], ctx["config"]
    return min(n["kv_token_steps"],
               float(c["sliding_window"]) * n["traced_tokens_decoded"])


def window_attn_bytes(params, ctx, trace):
    """Bytes ``paged_decode_attn_window`` had to read: the keys and values
    inside the window, once a window layer a decoded token."""
    c = ctx["config"]
    byts = layer_counts(c)["window"] * kv_bytes_per_token(c) \
        * _window_keys(ctx)
    secs = trace.matching(params["line"], params["pattern"])[0]
    return byts / ctx["peaks"]["hbm_bytes"], secs


def paged_attn_bytes(params, ctx, trace):
    """Bytes the paged decode kernels had to read, both names (the
    pattern ``^%paged_decode_attn`` finds the window's too): the one
    pool's live keys and values once for each of the layers that read it
    (the full layer and every cross layer: exact), plus the window's."""
    c, cnt = ctx["config"], layer_counts(ctx["config"])
    shared = (cnt["full"] + cnt["cross"]) * kv_bytes_per_token(c) \
        * ctx["counters"]["kv_token_steps"]
    window = cnt["window"] * kv_bytes_per_token(c) * _window_keys(ctx)
    say(f"paged_attn_bytes: shared pool {shared:.6g} B "
        f"({cnt['full'] + cnt['cross']} layers x "
        f"{kv_bytes_per_token(c)} B x kv_token_steps), window "
        f"{window:.6g} B ({cnt['window']} layers, at most ~2% high)")
    secs = trace.matching(params["line"], params["pattern"])[0]
    return (shared + window) / ctx["peaks"]["hbm_bytes"], secs


def ssm_state_bytes(params, ctx, trace):
    """Bytes the in-place state update has to move: every Mamba layer's
    float32 state of a sequence read once and written once a decoded
    token."""
    c = ctx["config"]
    _, _, di, n = _sizes(c)
    byts = (ctx["counters"]["traced_tokens_decoded"] * 2.0 * 4.0
            * layer_counts(c)["mamba"] * n * di)
    secs = trace.matching(params["line"], params["pattern"])[0]
    return byts / ctx["peaks"]["hbm_bytes"], secs


def decode_step_flops(params, ctx, trace):
    """The decode program's share of the peak: 2 FLOPs a parameter a
    decoded token (the table once, as the head), the differential
    products over the live tokens the eight pool readers read and over
    the window layers' keys, and the recurrence."""
    c, n, cnt = ctx["config"], ctx["counters"], layer_counts(ctx["config"])
    dense = 2.0 * param_count(c, True) * n["traced_tokens_decoded"]
    shared = attn_flops_per_key(c) * (cnt["full"] + cnt["cross"]) \
        * n["kv_token_steps"]
    window = attn_flops_per_key(c) * cnt["window"] * _window_keys(ctx)
    rec = recurrence_flops_per_token(c) * n["traced_tokens_decoded"]
    say(f"decode_step_flops: parameters {dense:.6g}, shared-pool attention "
        f"{shared:.6g}, window attention {window:.6g}, recurrence {rec:.6g}")
    secs = trace.matching(params["line"], params["pattern"])[0]
    return (dense + shared + window + rec) / ctx["peaks"]["flops"], secs
