"""Command A+ (Cohere, ``model_type`` ``cohere2_moe``): a parallel block
with window attention beside full attention, and a sigmoid router over
128 experts beside four shared ones. One chip's share of an
expert-parallel deployment: the router runs over all 128, the chip
computes the picks among the experts it holds.

Written from the source's config as known here (nothing could be
fetched) and Cohere's published description of the family (parallel
attention and feed-forward blocks; window layers with rotary positions,
a full layer without any: "global NoPE"). One layer on a sequence ``x``
[S, D]; widths at Command A+: D 4,096, 128 query heads over 8 key/value
heads of 128, experts of 4,096:

1. ``h = LayerNorm(x; ln)``: ``(x - mean(x)) / sqrt(var(x) + eps) * ln``,
   no bias (``layer_norm_eps`` 1e-5; ``rms_norm_eps`` is null).
2. ``q = h Wq``, ``k = h Wk``, ``v = h Wv``. A window layer
   (``layer_types`` ``sliding_attention``) rotates q and k by GPT-J's
   interleaved rope (pairs ``(x_2i, x_2i+1)`` turn at ``theta^(-2i/128)``
   times the position, theta 50,000, the whole head: ``rotary_pct`` 1),
   and position t attends to ``t - 4,095 .. t``; a full layer
   (``full_attention``) rotates nothing and attends to ``0 .. t``. Query
   head i reads key/value head ``i // 16``; scale ``128^-0.5``;
   ``a = concat(o) Wo``.
3. ``s = sigmoid(h W_r)`` over 128 outputs, float32; the 8 largest picked
   (no bias, no groups); ``w_i = s_i / sum of the 8`` (``norm_topk_prob``);
   ``routed = sum over the picks this chip holds of w_i SwiGLU_i(h)``
   (experts of 4,096; the picks held elsewhere are other chips' terms of
   the same sum).
4. ``shared = SwiGLU_shared(h)``: the four shared experts of 4,096 side by
   side, one SwiGLU of 16,384 whose output is their sum.
5. ``x <- x + a + (routed + shared) / 2`` (``use_parallel_block``;
   ``shared_expert_combination_strategy`` ``"average"``, read as the mean
   of the routed part and the shared part: every reading is ``a routed +
   b shared`` with constants, on random weights the others with a down
   matrix rescaled).
6. Model: ``x_0 = embed[ids]``; after the last layer ``LayerNorm(x;
   ln_f)``; ``logits = x embed^T * logit_scale`` (tied, ``logit_scale``
   1).

**The picks, where they are near-ties.** A sigmoid top-8 of 128 has
near-ties between the eighth and the ninth score that bf16 decides the
other way; a flipped pick replaces one of a token's eight terms. So, as
``axk1.py`` does, ``prefill`` and ``decode_step`` below keep the picks the
engine's own programs made (``routes=True``, an ordered callback into
``_RECORD`` under the prompt's ids), and ``logits_at`` TAKES THE PROGRAM'S
EIGHT WHERE ITS OWN FLOAT32 SCORES PUT THEM WITHIN ``route_margin`` OF ITS
RULE (``picks_gap``: no unpicked expert more than the margin over a
pick); its own eight elsewhere. The weights are always the reference's
own scores of the eight taken.

Departures, none in the mathematics: weights are read by the program's
names (``[inputs, outputs]``; a layer's weights under ``params["window"]``
or ``params["full"]`` by its kind, in the stack's order; the held
experts' ``gate`` and ``up`` lie ``[expert, outputs, inputs]``, ``down``
``[expert, inputs, outputs]``, under ``params["experts"]`` by the layer's
place in the stack); every held expert runs over every row and the picks
select; attention is taken a few heads at a time and the head a block of
rows at a time (the float32 scores of 128 heads at 4,408 positions would
be 9.9 GB). No code of ``paddle_tpu/models`` is used; everything is
float32, and every caller sets ``jax.default_matmul_precision
("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..harness import reference as R
from ..harness import work
from ..harness.session import say

WINDOW = "sliding_attention"
_HEADS_A_BLOCK = 4

# -- the plain reference ------------------------------------------------


def layer_norm(x, w, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w


def rotary(x, theta):
    """x [S, heads, d] at positions 0..S-1, interleaved pairs."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=R.F32) / d)
    ang = jnp.arange(s, dtype=R.F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape)


def attention(h, w, c, window: bool):
    """Step 2 on normed rows h [S, D]: the update [S, D]."""
    s = h.shape[0]
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    q = (h @ w["wq"]).reshape(s, nh, hd)
    k = (h @ w["wk"]).reshape(s, nkv, hd)
    v = (h @ w["wv"]).reshape(s, nkv, hd)
    if window:
        q, k = rotary(q, c["rope_theta"]), rotary(k, c["rope_theta"])
    age = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = (age >= 0) & (age < c["sliding_window"]) if window else age >= 0
    per = min(_HEADS_A_BLOCK, nh // nkv)
    group = nh // nkv

    def block(b):
        # heads b * per .. b * per + per - 1, all of one key/value head
        qb = jax.lax.dynamic_slice_in_dim(q, b * per, per, 1)
        kv = b * per // group
        score = jnp.einsum("qhd,kd->hqk", qb, k[:, kv]) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), -1)
        return jnp.einsum("hqk,kd->qhd", p, v[:, kv])

    out = jax.lax.map(block, jnp.arange(nh // per))      # [blocks, S, per, d]
    return jnp.moveaxis(out, 0, 1).reshape(s, nh * hd) @ w["wo"]


def swiglu(g, gate, up, down):
    return (jax.nn.silu(g @ gate) * (g @ up)) @ down


def router(h, w, c):
    """Step 3's scores [S, E] and the reference's own picks [S, k]."""
    s = jax.nn.sigmoid(h @ w["router"])
    return s, jnp.argsort(-s, -1)[:, :c["num_experts_per_tok"]]


def picks_gap(s, picks):
    """How far a set of picks [S, k] lies from the reference's own rule, a
    token (0 for the reference's own picks): the most by which an expert
    that was not picked stands over a pick."""
    rows = jnp.arange(s.shape[0])[:, None]
    chosen = jnp.take_along_axis(s, picks, -1)
    others = s.at[rows, picks].set(-jnp.inf)
    return (jnp.max(others, -1) - jnp.min(chosen, -1)).clip(0.0)


def experts(h, picks, weight, ex, first):
    """The held picks' weighted sum: every held expert over every row (an
    expert's float32 weights at a time), the picks selecting."""
    def one(out, xs):
        i, gate, up, down = xs
        y = swiglu(h, gate.astype(R.F32).T, up.astype(R.F32).T,
                   down.astype(R.F32))
        wi = jnp.sum(jnp.where(picks == first + i, weight, 0.0), -1)
        return out + wi[:, None] * y, None

    n = ex["gate"].shape[0]
    return jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(n), ex["gate"], ex["up"], ex["down"]))[0]


def layer(x, w, c, window: bool, follow=None, margin=0.0):
    """One layer on one sequence [S, D]; weights of any float type (its held
    experts under ``w["experts"]``), computed in float32. ``follow`` [S, k]:
    the program's picks, taken where they lie within ``margin`` of this
    layer's own rule. Returns (x, (the picks taken, this layer's own, their
    gap))."""
    ex = w["experts"]
    w = jax.tree.map(lambda a: a.astype(R.F32),
                     {k: v for k, v in w.items() if k != "experts"})
    x = x.astype(R.F32)
    h = layer_norm(x, w["ln"], c["layer_norm_eps"])
    a = attention(h, w, c, window)
    s, own = router(h, w, c)
    if follow is None:
        follow = own
    gap = picks_gap(s, follow)
    picks = jnp.where((gap <= margin)[:, None], follow, own)
    top = jnp.take_along_axis(s, picks, -1)
    weight = top / top.sum(-1, keepdims=True)
    routed = experts(h, picks, weight, ex, c["first_held_expert"])
    shared = swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
    return x + a + (routed + shared) / 2, (picks, own, gap)


def layer_weights(params, i, c):
    """Layer ``i`` of the stack: its kind's weights (the kind's layers in
    the stack's order) and its held experts; whether it is a window
    layer."""
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    window = kinds[i] == WINDOW
    j = sum((k == WINDOW) == window for k in kinds[:i])
    w = jax.tree.map(lambda a: a[j], params["window" if window else "full"])
    return {**w, "experts": jax.tree.map(lambda a: a[i],
                                         params["experts"])}, window


_HEAD_ROWS = 32768
_RECORD = {}        # a prompt's ids (bytes) -> [picks [L, n, k] a call]
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
    """The program's picks [L, n, k] for the first n of ``ids``, from the
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
    a layer here takes its kind and the picks too."""
    margin = float(c.get("serve", {}).get("check", {}).get(
        "route_margin", 0.0))
    picked = _picked(ids)
    n = len(ids)
    if picked is not None and picked.shape[1] < n:
        raise ValueError(f"command_a reference: the programs' picks cover "
                         f"{picked.shape[1]} of this sequence's {n} tokens")
    runs = {win: jax.jit(lambda x, w, f, win=win: layer(x, w, c, win, f,
                                                        margin))
            for win in (True, False)}
    x = params["embed"][jnp.asarray(ids)].astype(R.F32)
    differed = followed = 0
    worst = 0.0
    for i in range(c["num_hidden_layers"]):
        f = None if picked is None else jnp.asarray(picked[i], jnp.int32)
        w, window = layer_weights(params, i, c)
        x, out = runs[window](x, w, f)
        if f is not None:
            e, own, gap = (np.asarray(t) for t in out)
            diff = np.any(np.sort(picked[i], -1) != np.sort(own, -1), -1)
            differed += int(diff.sum())
            followed += int((diff & np.all(e == picked[i], -1)).sum())
            worst = max(worst, float(gap[diff].max(initial=0.0)))
    covered = n * c["num_hidden_layers"]
    if picked is not None:
        FOLLOWED.append({"picks": covered, "differed": differed,
                         "followed": followed, "largest_gap": worst})
        say(f"command_a reference: of {covered} pick sets (token x layer) "
            f"the program's differed from the reference's own on "
            f"{differed} ({100.0 * differed / max(covered, 1):.3f}%), "
            f"followed {followed} of them (route_margin {margin}); the "
            f"largest gap of a differing set {worst:.5f}")
    x = layer_norm(x, params["ln_f"].astype(R.F32), c["layer_norm_eps"])
    x, head = x[jnp.asarray(positions)], params["embed"]
    return c["logit_scale"] * jnp.concatenate(
        [x @ head[i:i + _HEAD_ROWS].astype(R.F32).T
         for i in range(0, head.shape[0], _HEAD_ROWS)], -1)


def loss(params, ids, c):
    """Mean next-token cross entropy of one sequence [S + 1]."""
    logits = logits_at(params, ids[:-1], c, jnp.arange(ids.shape[0] - 1))
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                ids[1:, None], -1).mean()


# -- the counts ---------------------------------------------------------

def layer_counts(c: dict) -> dict:
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    window = sum(k == WINDOW for k in kinds)
    return {"window": window, "full": len(kinds) - window}


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def held_picks_a_token(c: dict) -> float:
    """Picks a token takes among the held experts, on average: k x held /
    routed (1 at 8 x 16 / 128)."""
    return c["num_experts_per_tok"] * c["num_experts"] \
        / c["n_router_outputs"]


def layer_params(c: dict, active: bool = False) -> int:
    """Attention, the shared experts, the router over every routed expert,
    the norm, and the held experts (where ``active``: the average held
    picks a token's)."""
    d = c["hidden_size"]
    held = expert_params(c) * c["num_experts_per_tok"] * c["num_experts"] \
        // c["n_router_outputs"] if active \
        else c["num_experts"] * expert_params(c)
    return (work.attn_params(c) + c["num_shared_experts"] * expert_params(c)
            + d * c["n_router_outputs"] + d + held)


def param_count(c: dict, active: bool = False) -> int:
    """The layers, the tied table (the head, where ``active``), the last
    norm."""
    return (c["num_hidden_layers"] * layer_params(c, active)
            + c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def attn_flops_per_key(c: dict) -> float:
    """FLOPs of one layer's attention a key read by one query position:
    every query head's score and value products, two a multiply-add."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"]


def model_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward FLOPs a token of a sequence of ``seq_len``
    would take (6 a multiplied parameter; attention over half the sequence
    in the full layers and over at most the window in the others, three
    times its forward pass). The family has no train step; the number is
    the interface's."""
    n = layer_counts(c)
    keys = n["full"] * seq_len / 2 \
        + n["window"] * min(seq_len / 2, c["sliding_window"])
    return 6.0 * param_count(c, True) + 3.0 * attn_flops_per_key(c) * keys


def kv_token_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """A token's keys and values in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per_value


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    """What a cached token holds in the pool: the full layers' keys and
    values."""
    return layer_counts(c)["full"] * kv_token_bytes(c, bytes_per_value)


def ring_tokens(c: dict) -> int:
    """Tokens a window layer's ring holds: the window and one page."""
    return c["sliding_window"] + c.get("ring_page", 16)


def state_bytes_per_slot(c: dict) -> int:
    """What a sequence keeps beside its pages, whatever its length: a ring
    of keys and values a window layer."""
    return layer_counts(c)["window"] * ring_tokens(c) * kv_token_bytes(c)


# -- the serve check's three calls into the program -------------------------
# (the cache is the program's own pytree: the full layers' pool and a ring a
# window layer a sequence; the picks go to ``_RECORD`` on their way out, in
# order)

def make_cache(cfg, num_pages: int, page_size: int, sequences: int):
    from paddle_tpu.inference.paged import init_pool
    from paddle_tpu.models import command_a

    return init_pool(cfg, num_pages, page_size,
                     state_shapes=command_a.state_shapes(cfg),
                     state_rows=sequences,
                     pool_layout=command_a.pool_layout(cfg))


def prefill(family, params, ids, cfg, cache, page_rows, slen):
    from paddle_tpu.inference.paged import cache_prefill

    cache, logits, picks = cache_prefill(family, params, ids, cfg, cache,
                                         page_rows, slen,
                                         jnp.arange(ids.shape[0]),
                                         routes=True)
    jax.debug.callback(_keep_prefill, ids, slen, picks, ordered=True)
    return cache, logits


def decode_step(family, params, cache, block_tables, lengths, tokens, cfg):
    from paddle_tpu.inference.paged import cache_decode_step

    cache, logits, picks = cache_decode_step(
        family, params, cache, block_tables, lengths, tokens, cfg,
        jnp.arange(tokens.shape[0]), routes=True)
    jax.debug.callback(_keep_step, picks, ordered=True)
    return cache, logits


# -- kernel and program work, found by ``roofline.work`` ---------------------

def _window_keys(ctx) -> float:
    """Keys a window layer's kernel had to read while tracing: every
    slot's live tokens up to the window. The harness counts no minimum a
    slot, so this is min(all live tokens read, window x tokens decoded):
    it reads high by the slots younger than the window (a tenth of a
    window's keys or less a decoded token in this mix)."""
    n, c = ctx["counters"], ctx["config"]
    return min(n["kv_token_steps"],
               float(c["sliding_window"]) * n["traced_tokens_decoded"])


def window_attn_bytes(params, ctx, trace):
    """Bytes ``paged_decode_attn_window`` had to read: the keys and values
    inside the window, once a window layer a decoded token."""
    c = ctx["config"]
    byts = layer_counts(c)["window"] * kv_token_bytes(c) * _window_keys(ctx)
    secs = trace.matching(params["line"], params["pattern"])[0]
    return byts / ctx["peaks"]["hbm_bytes"], secs


def paged_attn_bytes(params, ctx, trace):
    """Bytes the paged decode kernels had to read, both names (the pattern
    ``^%paged_decode_attn`` finds the window's too): the full layers' live
    keys and values (exact), plus the window layers'."""
    c, n = ctx["config"], layer_counts(ctx["config"])
    full = n["full"] * kv_token_bytes(c) * ctx["counters"]["kv_token_steps"]
    window = n["window"] * kv_token_bytes(c) * _window_keys(ctx)
    say(f"paged_attn_bytes: full layers {full:.6g} B, window layers "
        f"{window:.6g} B")
    secs = trace.matching(params["line"], params["pattern"])[0]
    return (full + window) / ctx["peaks"]["hbm_bytes"], secs


def moe_expert_bytes(params, ctx, trace):
    """Bytes the decode steps' expert kernel has to read: the three
    matrices of every HELD expert that at least one pick named, a layer a
    step (``engine.expert_reads`` over ``engine.decode_steps`` of the
    window, times the steps decoded while tracing), never the 16 held
    regardless and never an expert held elsewhere. Against every call's
    time. Bandwidth bound."""
    c, n = ctx["config"], ctx["counters"]
    if not n.get("engine.decode_steps"):
        return 0.0, 0.0
    reads = n["engine.expert_reads"] / n["engine.decode_steps"] \
        * n["traced_decode_steps"]
    secs = trace.matching(params["line"], params["pattern"])[0]
    return reads * expert_params(c) * 2.0 / ctx["peaks"]["hbm_bytes"], secs


def decode_step_flops(params, ctx, trace):
    """The decode program's share of the peak: 2 FLOPs a multiplied
    parameter a decoded token (the held experts at their average picks,
    the head), and attention's two products over the live tokens the full
    layers read and the keys the window layers read."""
    c, n, cnt = ctx["config"], ctx["counters"], layer_counts(ctx["config"])
    dense = 2.0 * param_count(c, True) * n["traced_tokens_decoded"]
    full = attn_flops_per_key(c) * cnt["full"] * n["kv_token_steps"]
    window = attn_flops_per_key(c) * cnt["window"] * _window_keys(ctx)
    say(f"decode_step_flops: parameters {dense:.6g}, full attention "
        f"{full:.6g}, window attention {window:.6g}")
    secs = trace.matching(params["line"], params["pattern"])[0]
    return (dense + full + window) / ctx["peaks"]["flops"], secs
