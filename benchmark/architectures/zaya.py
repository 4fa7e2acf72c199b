"""ZAYA1 (Zyphra, ``model_type`` ``zaya``): attention inside a compressed
latent with two causal convolutions over queries and keys (CCA; the
listing in arXiv:2510.04476), then sixteen experts of which a token takes
one, through an MLP router that carries its input from layer to layer and
drops nothing (arXiv:2511.17127). Keys and values a token AND the tails
of the convolutions a sequence.

Written from the two papers as known here (nothing could be fetched). One
layer, on a sequence ``x`` [S, D]; sizes at 8B: D 2048; 8 query and 2
key heads of d = 128 inside the latent (G = 4 queries a key head); 16
experts of 2048; router width 256:

1. ``h = RMSNorm(x; ln1)``; ``q~ = h wq`` (1,024), ``k~ = h wk`` (256);
   ``u = [q~ ; k~]``, 10 heads of 128.
2. Two causal convolutions along the sequence, zeros before position 0:
   ``a_t = conv1_w[0] * u_{t-1} + conv1_w[1] * u_t + conv1_b`` (a weight a
   channel a tap); ``c_t[g] = a_{t-1}[g] conv2_w[0, g] + a_t[g] conv2_w[1,
   g] + conv2_b[g]`` (a 128 x 128 matrix a head a tap). ``q^``, ``k^`` are
   the heads of ``c``.
3. ``q_i = q^_i + (q~_i + k~_{i // G}) / 2``; ``k_j = k^_j + (mean of
   q~_i over the group j + k~_j) / 2``.
4. ``q_i <- q_i / rms(q_i)``, ``k_j <- tau_j k_j / rms(k_j)`` (``rms`` over
   the head with the model's epsilon: each to length sqrt(d), the keys
   times a learned scalar a head); rotary (rotate-half) on the first 64
   of each head's 128, theta 5,000,000.
5. Values: key head 0's is ``h_t wv1``, key head 1's is ``h_{t-1} wv2``
   (zero at position 0).
6. ``o = softmax(q k^T / sqrt(d)) v``, causal, grouped-query; ``y = o wo``;
   ``x <- a_alpha * x + a_beta * y``.
7. ``g = RMSNorm(x; ln2)``; ``r_l = g wd + gamma * r_{l-1}`` (``r_{-1}`` =
   0); ``s = softmax(gelu(gelu(RMSNorm(r_l; rnorm) w1) w2) w3)`` over the
   experts (exact GeLU); ``e = argmax(s + rbias)``; ``y = s_e *
   ((silu(g gate_e^T) * (g up_e^T)) down_e)``; ``x <- m_alpha * x + m_beta
   * y``. No shared expert, no capacity, no renormalising.
8. Model: ``x_0 = embed[ids]``; after the last layer ``RMSNorm(x; ln_f)``;
   ``logits = x embed^T`` (the table is tied).

**The pick, where it is a near-tie.** With one expert a token a flipped
pick replaces a layer's whole update for that token, and the bf16 program
decides a near-tie the other way on a few picks in a hundred; no band
that lets that through would fail a wrong weight. So ``prefill`` and
``decode_step`` below, which call the engine's own paged programs, KEEP
what those programs picked: the programs hand the picks back
(``routes=True``), and an ordered ``jax.debug.callback`` puts them into
``_RECORD``, a dict this module owns, under the prompt's ids (a prefill
opens a sequence's entry, each decode step appends to the entries of the
rows it ran, in the row order of the prefill before it). ``logits_at``
finds the entry whose prompt the ids begin with and, layer by layer,
TAKES THE PROGRAM'S PICK WHERE ITS OWN FLOAT32 SCORES ``s + rbias`` PUT
THAT PICK WITHIN ``route_margin`` OF THEIR BEST, AND ITS OWN PICK
ELSEWHERE, and weighs with its own ``s_e``. A wrong router, bias or
tie-break then shows as logits far off under the check's bands; a
near-tie decided the other way does not. It prints the share of picks
that differed and the share it followed. With no entry (a sequence the
programs never ran) it follows nothing.

Departures, none in the mathematics: weights are read by the program's
names (``gate`` and ``up`` lie ``[expert, outputs, inputs]``, ``down``
``[expert, inputs, outputs]``, under ``params["experts"]`` with the layer
first); every expert runs over every row and the pick selects; the head
is taken in blocks of rows. What the config does not settle is listed
under ``assumed`` in the configuration's file. No code of
``paddle_tpu/models`` is used; everything is float32, and every caller
sets ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..harness import reference as R
from ..harness import work
from ..harness.session import say

# -- the plain reference ------------------------------------------------


def _before(t):
    """``t_{s-1}`` along the sequence, zeros before position 0."""
    return jnp.concatenate([jnp.zeros_like(t[:1]), t[:-1]])


def _rotary_part(x, theta, n):
    """Rotary on the first ``n`` of a head's numbers, the rest as is."""
    return jnp.concatenate([R.rotary(x[..., :n], theta), x[..., n:]], -1)


def attention(h, w, c):
    """Steps 1-6 on one sequence h [S, D]: y [S, D]."""
    s = h.shape[0]
    nh, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    g, eps = nh // nkv, c["rms_norm_eps"]
    qt, kt = h @ w["wq"], h @ w["wk"]
    u = jnp.concatenate([qt, kt], -1)
    a = w["conv1_w"][0] * _before(u) + w["conv1_w"][1] * u + w["conv1_b"]
    ah, ap = (t.reshape(s, nh + nkv, d) for t in (a, _before(a)))
    cc = (jnp.einsum("sgi,gio->sgo", ap, w["conv2_w"][0])
          + jnp.einsum("sgi,gio->sgo", ah, w["conv2_w"][1])
          + w["conv2_b"].reshape(nh + nkv, d))
    qt, kt = qt.reshape(s, nh, d), kt.reshape(s, nkv, d)
    q = cc[:, :nh] + (qt + jnp.repeat(kt, g, axis=1)) / 2
    k = cc[:, nh:] + (qt.reshape(s, nkv, g, d).mean(2) + kt) / 2

    def unit(t):
        return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + eps)

    theta = float(c["rope_parameters"]["hybrid"]["rope_theta"])
    n = int(d * c["partial_rotary_factor"])
    q = _rotary_part(unit(q), theta, n)
    k = _rotary_part(unit(k) * w["tau"][:, None], theta, n)
    v = jnp.stack([h @ w["wv1"], _before(h @ w["wv2"])], 1)     # [S, 2, d]
    k, v = (jnp.repeat(t, g, axis=1) for t in (k, v))
    score = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
    score = jnp.where(jnp.tril(jnp.ones((s, s), bool)), score, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, -1), v)
    return out.reshape(s, nh * d) @ w["wo"]


def router(g, r_prev, w, c):
    """Step 7's router on g [S, D]: (r_l, s [S, E], s + rbias)."""
    r = g @ w["wd"] + w["gamma"] * r_prev
    z = R.rms_norm(r, w["rnorm"], c["rms_norm_eps"])
    z = jax.nn.gelu(z @ w["w1"], approximate=False)
    z = jax.nn.gelu(z @ w["w2"], approximate=False)
    s = jax.nn.softmax(z @ w["w3"], -1)
    return r, s, s + w["rbias"]


def experts(g, e, w):
    """Each row of g [S, D] through expert ``e[s]``: every expert over
    every row, the pick selecting."""
    out = jnp.zeros_like(g)
    for i in range(w["gate"].shape[0]):
        gate, up, down = (w[k][i].astype(R.F32)
                          for k in ("gate", "up", "down"))
        y = (jax.nn.silu(g @ gate.T) * (g @ up.T)) @ down
        out = out + jnp.where((e == i)[:, None], y, 0.0)
    return out


def layer(x, w, c, r_prev=None, follow=None, margin=0.0):
    """One layer on one sequence [S, D]; weights of any float type (the
    experts' under ``w["experts"]``), computed in float32. ``r_prev`` is
    the router's input of the layer before (None: zeros); ``follow`` [S]
    the program's picks, taken where this layer's own scores put them
    within ``margin`` of their best. Returns (x, r_l, (the picks taken,
    this layer's own, the gap of ``follow`` under the best))."""
    ex = w["experts"]
    w = jax.tree.map(lambda a: a.astype(R.F32),
                     {k: v for k, v in w.items() if k != "experts"})
    x = x.astype(R.F32)
    eps = c["rms_norm_eps"]
    x = w["a_alpha"] * x + w["a_beta"] * attention(
        R.rms_norm(x, w["ln1"], eps), w, c)
    g = R.rms_norm(x, w["ln2"], eps)
    if r_prev is None:
        r_prev = jnp.zeros((x.shape[0], w["wd"].shape[1]), R.F32)
    r, s, score = router(g, r_prev, w, c)
    own = jnp.argmax(score, -1)
    if follow is None:
        follow = own
    gap = jnp.max(score, -1) - jnp.take_along_axis(
        score, follow[:, None], -1)[:, 0]
    e = jnp.where(gap <= margin, follow, own)
    y = jnp.take_along_axis(s, e[:, None], -1) * experts(g, e, ex)
    return w["m_alpha"] * x + w["m_beta"] * y, r, (e, own, gap)


def _layer_weights(params, i):
    return {**R.layer_slice(params, i),
            "experts": jax.tree.map(lambda a: a[i], params["experts"])}


_HEAD_ROWS = 32768
_RECORD = {}        # a prompt's ids (bytes) -> [picks [L, n] of each call]
_ROWS = []          # the keys of the rows of the prefill before
FOLLOWED = []       # of each ``logits_at`` that found its picks: what it said


def _keep_prefill(ids, slen, picks):
    _ROWS.clear()
    for g in range(ids.shape[0]):
        key = np.asarray(ids[g, :slen[g]], np.int32).tobytes()
        _ROWS.append(key)
        _RECORD[key] = [np.asarray(picks[:, g, :slen[g]])]


def _keep_step(picks):
    for g, key in enumerate(_ROWS):
        _RECORD[key].append(np.asarray(picks[:, g]))


def _picked(ids):
    """The program's picks [L, n] for the first n of ``ids``, from the
    entry whose prompt they begin with (the longest, the newest), or
    None."""
    ids = np.asarray(ids, np.int32)
    best = None
    for key, parts in _RECORD.items():
        n = len(key) // 4
        if n <= len(ids) and ids[:n].tobytes() == key \
                and (best is None or n >= best[0]):
            best = (n, parts)
    if best is None:
        return None
    return np.concatenate(best[1], axis=1)[:, :len(ids)]


def logits_at(params, ids, c, positions, layer_fn=None):
    """Float32 logits [len(positions), V] of one sequence of ids, the
    program's picks followed where they are near-ties (the module's
    docstring). ``layer_fn`` (the check's jitted ``layer``) is not used:
    a layer here takes the router's carried input and the picks too."""
    margin = float(c.get("serve", {}).get("check", {}).get(
        "route_margin", 0.0))
    picked = _picked(ids)
    n = len(ids)
    if picked is not None and picked.shape[1] < n:
        raise ValueError(f"zaya reference: the programs' picks cover "
                         f"{picked.shape[1]} of this sequence's {n} tokens")
    run = jax.jit(lambda x, w, r, f: layer(x, w, c, r, f, margin))
    x = params["embed"][jnp.asarray(ids)].astype(R.F32)
    r, differed, followed, worst = None, 0, 0, 0.0
    for i in range(c["num_hidden_layers"]):
        f = None if picked is None else jnp.asarray(picked[i], jnp.int32)
        x, r, (e, own, gap) = run(x, _layer_weights(params, i), r, f)
        if f is not None:
            e, own, gap = (np.asarray(t) for t in (e, own, gap))
            diff = picked[i] != own
            differed += int(diff.sum())
            followed += int((diff & (e == picked[i])).sum())
            worst = max(worst, float(gap[diff].max(initial=0.0)))
    covered = n * c["num_hidden_layers"]
    if picked is not None:
        FOLLOWED.append({"picks": covered, "differed": differed,
                         "followed": followed, "largest_gap": worst})
        say(f"zaya reference: of {covered} picks (token x layer) the "
            f"program's differed from the reference's own on {differed} "
            f"({100.0 * differed / max(covered, 1):.3f}%), followed "
            f"{followed} of them (route_margin {margin}); the largest gap "
            f"of a differing pick under the reference's best {worst:.5f}")
    x = R.rms_norm(x, params["ln_f"].astype(R.F32), c["rms_norm_eps"])
    x, head = x[jnp.asarray(positions)], params["embed"]
    # the table in blocks of rows: whole, its float32 copy is 2.1 GB
    return jnp.concatenate(
        [x @ head[i:i + _HEAD_ROWS].astype(R.F32).T
         for i in range(0, head.shape[0], _HEAD_ROWS)], -1)


def loss(params, ids, c):
    """Mean next-token cross entropy of one sequence [S + 1]."""
    logits = logits_at(params, ids[:-1], c, jnp.arange(ids.shape[0] - 1))
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                ids[1:, None], -1).mean()


# -- the counts ---------------------------------------------------------

def _latent(c: dict) -> int:
    return (c["num_attention_heads"] + c["num_key_value_heads"]) \
        * c["head_dim"]


def attn_params(c: dict) -> int:
    """``wq``, ``wk``, the two value halves, ``wo``; the depthwise and the
    grouped convolution with their biases; a temperature a key head."""
    d, hd, lat = c["hidden_size"], c["head_dim"], _latent(c)
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    return (d * lat + 2 * d * hd + nh * hd * d
            + c["cca_time0"] * lat + lat
            + c["cca_time1"] * (nh + nkv) * hd * hd + lat + nkv)


def router_params(c: dict) -> int:
    """``wd``, two hidden matrices, the last, the selection bias; the
    carried input's ``gamma`` and the router's norm."""
    d, r, e = c["hidden_size"], c["router_hidden_size"], c["num_experts"]
    return d * r + 2 * r * r + r * e + e + 2 * r


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_params(c: dict, active: bool = False) -> int:
    """Attention, the router, the experts (one where ``active``), two
    norms and the four residual vectors."""
    n = 1 if active else c["num_experts"]
    return (attn_params(c) + router_params(c) + n * expert_params(c)
            + 6 * c["hidden_size"])


def param_count(c: dict, active: bool = False) -> int:
    return work.decoder_params(c, layer_params(c, active), active)


def model_flops_per_token(c: dict, seq_len: int) -> float:
    return work.train_flops_per_token(c, param_count(c, True), seq_len)


kv_bytes_per_token = work.kv_bytes_per_token


def state_bytes_per_slot(c: dict) -> int:
    """What a sequence keeps beside its keys and values: ``u_{t-1}``,
    ``a_{t-1}`` and ``W_V2 h_{t-1}`` a layer, in the model's two bytes."""
    return c["num_hidden_layers"] * 2 * (2 * _latent(c) + c["head_dim"])


# -- the serve check's three calls into the program -------------------------
# (the cache is the program's own pytree: two page pools and the rows; a
# sequence of the check keeps its row at its number; the picks go to
# ``_RECORD`` on their way out, in order)

def make_cache(cfg, num_pages: int, page_size: int, sequences: int):
    from paddle_tpu.inference.paged import init_pool
    from paddle_tpu.models import zaya

    return init_pool(cfg, num_pages, page_size,
                     state_shapes=zaya.state_shapes(cfg),
                     state_rows=sequences)


def prefill(family, params, ids, cfg, cache, page_rows, slen):
    from paddle_tpu.inference.paged import cache_prefill

    cache, logits, picks = cache_prefill(
        family, params, ids, cfg, cache, page_rows, slen,
        jnp.arange(ids.shape[0]), routes=True)
    jax.debug.callback(_keep_prefill, ids, slen, picks, ordered=True)
    return cache, logits


def decode_step(family, params, cache, block_tables, lengths, tokens, cfg):
    from paddle_tpu.inference.paged import cache_decode_step

    cache, logits, picks = cache_decode_step(
        family, params, cache, block_tables, lengths, tokens, cfg,
        jnp.arange(tokens.shape[0]), routes=True)
    jax.debug.callback(_keep_step, picks, ordered=True)
    return cache, logits


# -- kernel work, found by ``roofline.work`` --------------------------------

def moe_expert_bytes(params, ctx, trace):
    """Bytes the decode steps' expert kernel has to read: the three
    matrices of every expert that at least one slot picked, a layer a
    step (``engine.expert_reads`` over ``engine.decode_steps`` of the
    window, times the steps decoded while tracing: every slot is full
    throughout, so the traced slice's steps read as the window's do),
    whatever implements it; never 16 a layer regardless. Against every
    call's time. Bandwidth bound."""
    c, n = ctx["config"], ctx["counters"]
    if not n.get("engine.decode_steps"):
        return 0.0, 0.0
    reads = n["engine.expert_reads"] / n["engine.decode_steps"] \
        * n["traced_decode_steps"]
    secs = trace.matching(params["line"], params["pattern"])[0]
    return reads * expert_params(c) * 2.0 / ctx["peaks"]["hbm_bytes"], secs
