"""Falcon-H1 (tiiuae, ``model_type`` ``falcon_h1``): a Mamba-2 mixer and a
grouped-query attention IN PARALLEL on the same normed input in every
block, then a SwiGLU feed-forward; muP multipliers from the config scale
each branch. Keys and values a token AND a recurrent state a sequence.

Written from the public ``modeling_falcon_h1.py`` as known here (nothing
could be fetched). One block, on a sequence ``x`` [S, D]; sizes at 34B: D
5120; 20 query / 4 KV heads of 128; ``d_ssm`` 4096 = 32 heads x 128,
2 groups, ``d_state`` 256, ``d_conv`` 4; feed-forward 21504:

1. ``h = RMSNorm(x; ln1)``.
2. Mixer. ``u = (h * ssm_in_multiplier) @ in_proj^T``, laid out
   ``[z d_ssm | x d_ssm | B groups*d_state | C groups*d_state | dt heads]``;
   the five segments are multiplied by ``ssm_multipliers[0..4]`` (the
   source's ``mup_vector``). ``[x B C]_t <- silu(sum_j conv_w[j] * [x B
   C]_{t-(d_conv-1)+j} + conv_b)``: causal, depthwise, zeros before t = 0.
   A head i (of group i // (heads / groups)): ``dt_t = softplus(dt_t +
   dt_bias_i)``, ``A_i = -exp(A_log_i)``, ``H_t = exp(dt_t A_i) H_{t-1} +
   dt_t x_t (x) B_t`` with ``H`` [head_dim, d_state] and ``H_{-1} = 0``;
   ``y_t = H_t C_t + D_i x_t``. Then ``y <- RMSNorm_grouped(y * silu(z);
   norm)``, the mean square over each group's ``d_ssm / groups`` channels;
   ``m = (y @ out_proj) * ssm_out_multiplier``.
3. Attention. ``q = h' @ wq``, ``k = (h' @ wk) * key_multiplier``,
   ``v = h' @ wv`` with ``h' = h * attention_in_multiplier``; rope
   (rotate-half) on q and k; causal grouped-query softmax(q k^T /
   sqrt(head_dim)) v; ``a = (. @ wo) * attention_out_multiplier``.
4. ``x <- x + m + a``.
5. ``g = RMSNorm(x; ln2)``; ``x <- x + ((silu((g @ gate) *
   mlp_multipliers[0]) * (g @ up)) @ down) * mlp_multipliers[1]``.
6. Model: ``x_0 = embed[ids] * embedding_multiplier``; after the last
   block ``RMSNorm(x; ln_f)``; ``logits = (x @ lm_head^T) *
   lm_head_multiplier``.

Departures, none in the mathematics: the recurrence runs token by token
(``lax.scan``), never in chunks; weights are read by the program's names
(``in_proj`` is ``[outputs, inputs]`` as the head is; ``conv_w`` is
``[d_conv, channels]``, tap j on the input d_conv-1-j tokens back); ``time_step_limit`` is (0, inf), so dt is not clipped. What
the config does not settle (the segment order, the gate before the norm,
the groups of the norm) is listed under ``assumed`` in the configuration's
file. No code of ``paddle_tpu/models`` is used; everything is float32, and
every caller sets ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..harness import reference as R
from ..harness import work

# -- the plain reference ------------------------------------------------


def mixer(h, w, c):
    """Step 2 on one sequence h [S, D]: m [S, D]."""
    ds, groups, n = c["mamba_d_ssm"], c["mamba_n_groups"], c["mamba_d_state"]
    heads, p, k = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_conv"]
    gn, s = groups * n, h.shape[0]
    u = (h * c["ssm_in_multiplier"]) @ w["in_proj"].T
    mz, mx, mb, mc, mdt = c["ssm_multipliers"]
    z = u[:, :ds] * mz
    xbc = jnp.concatenate([u[:, ds:2 * ds] * mx,
                           u[:, 2 * ds:2 * ds + gn] * mb,
                           u[:, 2 * ds + gn:2 * ds + 2 * gn] * mc], -1)
    dt = u[:, 2 * ds + 2 * gn:] * mdt
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), R.F32), xbc])
    xbc = jax.nn.silu(sum(w["conv_w"][j] * padded[j:j + s] for j in range(k))
                      + w["conv_b"])
    x = xbc[:, :ds].reshape(s, heads, p)
    b = jnp.repeat(xbc[:, ds:ds + gn].reshape(s, groups, n),
                   heads // groups, axis=1)                  # [S, heads, N]
    cc = jnp.repeat(xbc[:, ds + gn:].reshape(s, groups, n),
                    heads // groups, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # [S, heads]
    a = -jnp.exp(w["A_log"])

    def token(state, t):
        x_t, b_t, c_t, dt_t = t
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y_t = jnp.einsum("hpn,hn->hp", state, c_t) + w["D"][:, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), R.F32),
                        (x, b, cc, dt))
    y = (y.reshape(s, ds) * jax.nn.silu(z)).reshape(s, groups, ds // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + c["rms_norm_eps"])
    return ((y.reshape(s, ds) * w["norm"]) @ w["out_proj"]) \
        * c["ssm_out_multiplier"]


def attention(h, w, c):
    """Step 3 on one sequence h [S, D]: a [S, D]."""
    s = h.shape[0]
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    h = h * c["attention_in_multiplier"]
    theta = float(c["rope_theta"])      # 1e11: wider than an int32
    q = R.rotary((h @ w["wq"]).reshape(s, nh, hd), theta)
    k = R.rotary(((h @ w["wk"]) * c["key_multiplier"]).reshape(s, nkv, hd),
                 theta)
    v = (h @ w["wv"]).reshape(s, nkv, hd)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    score = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    score = jnp.where(jnp.tril(jnp.ones((s, s), bool)), score, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, -1), v)
    return (out.reshape(s, nh * hd) @ w["wo"]) * c["attention_out_multiplier"]


def feed_forward(g, w, c):
    m_gate, m_down = c["mlp_multipliers"]
    return ((jax.nn.silu((g @ w["gate"]) * m_gate) * (g @ w["up"]))
            @ w["down"]) * m_down


def layer(x, w, c):
    """One block on one sequence [S, D]; weights of any float type,
    computed in float32. Returns (x, 0: no balance loss)."""
    w = jax.tree.map(lambda a: a.astype(R.F32), w)
    x = x.astype(R.F32)
    h = R.rms_norm(x, w["ln1"], c["rms_norm_eps"])
    x = x + mixer(h, w, c) + attention(h, w, c)
    g = R.rms_norm(x, w["ln2"], c["rms_norm_eps"])
    return x + feed_forward(g, w, c), jnp.zeros((), R.F32)


_HEAD_ROWS = 32768


def _hidden(params, ids, c, layer_fn):
    x = params["embed"][ids].astype(R.F32) * c["embedding_multiplier"]
    for i in range(c["num_hidden_layers"]):
        x, _ = layer_fn(x, R.layer_slice(params, i))
    return R.rms_norm(x, params["ln_f"].astype(R.F32), c["rms_norm_eps"])


def logits_at(params, ids, c, positions, layer_fn=None):
    """Float32 logits [len(positions), V] of one sequence of ids."""
    x = _hidden(params, ids, c, layer_fn or (lambda x, w: layer(x, w, c)))
    x, head = x[jnp.asarray(positions)], R.head_of(params, c)
    # the head in blocks of rows: whole, its float32 copy is 5.3 GB at 34B
    return jnp.concatenate(
        [x @ head[i:i + _HEAD_ROWS].astype(R.F32).T
         for i in range(0, head.shape[0], _HEAD_ROWS)], -1) \
        * c["lm_head_multiplier"]


def loss(params, ids, c):
    """Mean next-token cross entropy of one sequence [S + 1]."""
    logits = logits_at(params, ids[:-1], c, jnp.arange(ids.shape[0] - 1))
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                ids[1:, None], -1).mean()


# -- the counts ---------------------------------------------------------

def _conv_dim(c: dict) -> int:
    return c["mamba_d_ssm"] + 2 * c["mamba_n_groups"] * c["mamba_d_state"]


def _state_elements(c: dict) -> int:
    """Numbers in a layer's recurrent state of one sequence."""
    return c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"]


def mixer_params(c: dict) -> int:
    """``in_proj`` and ``out_proj``, the convolution's taps and bias, the
    gated norm's gain, and ``dt_bias``, ``A_log``, ``D`` a head."""
    d, ds, cd = c["hidden_size"], c["mamba_d_ssm"], _conv_dim(c)
    return (d * (ds + cd + c["mamba_n_heads"]) + ds * d
            + cd * c["mamba_d_conv"] + cd + ds + 3 * c["mamba_n_heads"])


def layer_params(c: dict) -> int:
    """The mixer, attention, the three SwiGLU matrices, two norms."""
    return (mixer_params(c) + work.attn_params(c)
            + 3 * c["hidden_size"] * c["intermediate_size"]
            + 2 * c["hidden_size"])


def param_count(c: dict, active: bool = False) -> int:
    return work.decoder_params(c, layer_params(c), active)


def recurrence_flops_per_token(c: dict) -> float:
    """The forward pass of the recurrence, a token: about 6 FLOPs a state
    element a layer (the decay, the outer product and its add, the
    read-out's multiply and add, and ``dt x``)."""
    return 6.0 * c["num_hidden_layers"] * _state_elements(c)


def model_flops_per_token(c: dict, seq_len: int) -> float:
    """As the dense decoder's, plus the recurrence forward and backward
    (three times its forward pass)."""
    return work.train_flops_per_token(c, param_count(c, True), seq_len) \
        + 3.0 * recurrence_flops_per_token(c)


kv_bytes_per_token = work.kv_bytes_per_token


def state_bytes_per_slot(c: dict) -> int:
    """What a sequence keeps beside its keys and values: a float32 state
    a layer, and the convolution's last ``d_conv - 1`` inputs in the
    model's two bytes."""
    return c["num_hidden_layers"] * (
        4 * _state_elements(c) + 2 * (c["mamba_d_conv"] - 1) * _conv_dim(c))


# -- the serve check's three calls into the program -------------------------
# (the cache is the program's own pytree: two page pools and the state
# leaves; a sequence of the check keeps its state in the row of its number)

def make_cache(cfg, num_pages: int, page_size: int, sequences: int):
    from paddle_tpu.inference.paged import init_pool
    from paddle_tpu.models import falcon_h1

    return init_pool(cfg, num_pages, page_size,
                     state_shapes=falcon_h1.state_shapes(cfg),
                     state_rows=sequences)


def prefill(family, params, ids, cfg, cache, page_rows, slen):
    from paddle_tpu.inference.paged import cache_prefill

    return cache_prefill(family, params, ids, cfg, cache, page_rows, slen,
                         jnp.arange(ids.shape[0]))


def decode_step(family, params, cache, block_tables, lengths, tokens, cfg):
    from paddle_tpu.inference.paged import cache_decode_step

    return cache_decode_step(family, params, cache, block_tables, lengths,
                             tokens, cfg, jnp.arange(tokens.shape[0]))


# -- kernel and program work, found by ``roofline.work`` ---------------------

def decode_step_flops(params, ctx, trace):
    """The decode program's share of the peak: as ``trace_ops``' own (2
    FLOPs a multiplied parameter a decoded token, attention's two products
    over the live tokens read) plus the recurrence a decoded token."""
    c, n = ctx["config"], ctx["counters"]
    flops = ((2.0 * param_count(c, active=True)
              + recurrence_flops_per_token(c)) * n["traced_tokens_decoded"]
             + work.decode_attn_flops(n["kv_token_steps"], c))
    secs = trace.matching(params["line"], params["pattern"])[0]
    return flops / ctx["peaks"]["flops"], secs


def ssm_state_bytes(params, ctx, trace):
    """Bytes the in-place state update has to move: every layer's float32
    state of a sequence read once and written once for every token the
    slots decoded while tracing, whatever implements the update; against
    every call's time. Bandwidth bound."""
    c = ctx["config"]
    byts = (ctx["counters"]["traced_tokens_decoded"] * 2.0 * 4.0
            * c["num_hidden_layers"] * _state_elements(c))
    secs = trace.matching(params["line"], params["pattern"])[0]
    return byts / ctx["peaks"]["hbm_bytes"], secs
