"""A.X K1 (SK Telecom, ``model_type`` ``axk1``; the configuration is
DeepSeek-V3's, key for key): multi-head latent attention (MLA, DeepSeek-V2,
arXiv:2405.04434) and a sigmoid, group-limited router over 192 experts
beside a shared expert (DeepSeek-V3, arXiv:2412.19437). One chip's share
of an expert-parallel deployment: the router runs over all 192, the chip
computes the picks among the experts it holds.

Written from the two papers and the source's config as known here
(nothing could be fetched). One layer on a sequence ``x`` [S, D]; widths
at A.X K1: D 7168, H 64 heads, nope 128, rope 64, v 128, r_q 1536, r_kv
512:

1. ``h = RMSNorm(x; ln1)``; ``cq = RMSNorm(h W_DQ; q_norm)``; ``q = cq
   W_UQ`` as [S, H, 192], ``q_nope`` its first 128, ``q_pe`` the rest.
2. ``[c~ ; kpe] = h W_DKV``; ``c = RMSNorm(c~; kv_norm)``; ``[k_nope ; v]
   = c W_UKV`` as [S, H, 256].
3. RoPE on ``q_pe`` and on ``kpe`` (ONE key of 64 for all heads), YaRN
   frequencies (theta 10,000, factor 32, original 4,096, beta_fast 32,
   beta_slow 1: a pair's frequency is theta^(-2i/64), that over 32 above
   the ramp, mixed linearly between the pairs that turn 32 and 1 times in
   4,096 positions), cos and sin times mscale / mscale_all_dim = 1;
   DeepSeek's interleaved pairs (x_2i, x_2i+1).
4. ``score_h(t, s) = (q_nope_h . k_nope_h + q_pe_h . k_pe) * sigma``,
   ``sigma = 192^-0.5 m^2``, ``m = 0.1 ln 32 + 1``; causal softmax; ``o_h
   = sum p v_h``; ``x <- x + concat(o) W_O``.
5. Layer 0: ``x <- x + SwiGLU(RMSNorm(x; ln2))`` of 18,432. The others:
   ``g = RMSNorm(x; ln2)``; ``s = sigmoid(g W_r)`` over 192, float32; 8
   groups of 24 in order, a group scored by the sum of its two best
   ``s``; the best 4 groups kept; the 8 best ``s`` among their experts
   (no selection bias: ``topk_method`` is ``"none"``); ``w = s_top8 /
   sum(s_top8) * 2.5``; ``x <- x + SwiGLU_shared(g) + sum over the picks
   this chip holds of w_i SwiGLU_i(g)`` (experts of 2,048; the picks
   held elsewhere are other chips' terms of the same sum).
6. Model: ``x_0 = embed[ids]``; after the last layer ``RMSNorm(x;
   ln_f)``; ``logits = x lm_head^T`` (untied).

**The picks, where they are near-ties.** A flipped pick replaces one of
a token's eight terms, and the group rule turns a near-tie between two
groups into up to eight other picks; bf16 decides some of both the other
way. So, as ``zaya.py`` does (PR 33), ``prefill`` and ``decode_step``
below keep the picks the engine's own programs made (``routes=True``, an
ordered callback into ``_RECORD`` under the prompt's ids), and
``logits_at`` TAKES THE PROGRAM'S EIGHT WHERE ITS OWN FLOAT32 SCORES PUT
THEM WITHIN ``route_margin`` OF ITS RULE (``picks_gap``: no picked group
more than the margin under the fourth-best group score, no unpicked
expert of the picked groups more than the margin over a pick, no group
left out that stood clearly over both a pick and the fifth-best group);
its own eight elsewhere. The weights are always the
reference's own scores of the eight taken. A wrong router, group rule or
scaling then shows as logits far off; a near-tie decided the other way
does not.

Departures, none in the mathematics: weights are read by the program's
names (``[inputs, outputs]``; the held experts' ``gate`` and ``up`` lie
``[expert, outputs, inputs]``, ``down`` ``[expert, inputs, outputs]``,
under ``params["experts"]``, the expert layers first); every held expert
runs over every row and the picks select; the head is taken in blocks of
rows. Attention is DECOMPRESSED here (keys and values a head), the
program's decode absorbed. No code of ``paddle_tpu/models`` is used;
everything is float32, and every caller sets
``jax.default_matmul_precision("highest")``.
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


def yarn_frequencies(c: dict):
    """Step 3's frequencies [rope / 2]."""
    d, rs = c["qk_rope_head_dim"], c["rope_scaling"]
    base = float(c["rope_theta"])
    plain = base ** (-np.arange(0, d, 2) / d)

    def pair(turns):                    # the pair that turns so often
        return d * math.log(rs["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(pair(rs["beta_fast"])), 0)
    hi = min(math.ceil(pair(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return jnp.asarray(plain / rs["factor"] * ramp + plain * (1 - ramp),
                       R.F32)


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(c: dict) -> float:
    rs = c["rope_scaling"]
    m = mscale(rs["factor"], rs["mscale_all_dim"])
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def rotary(x, c):
    """x [S, .., rope] at positions 0..S-1, interleaved pairs."""
    rs = c["rope_scaling"]
    ang = jnp.arange(x.shape[0], dtype=R.F32)[:, None] * yarn_frequencies(c)
    gain = mscale(rs["factor"], rs["mscale"]) / mscale(rs["factor"],
                                                       rs["mscale_all_dim"])
    cos, sin = (jnp.expand_dims(t * gain, tuple(range(1, x.ndim - 1)))
                for t in (jnp.cos(ang), jnp.sin(ang)))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape)


def attention(h, w, c):
    """Steps 1-4 on one sequence h [S, D] (normed): the update [S, D]."""
    s = h.shape[0]
    H, nope, rope = (c["num_attention_heads"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"])
    r, eps = c["kv_lora_rank"], c["rms_norm_eps"]
    q = (R.rms_norm(h @ w["wq_a"], w["q_norm"], eps) @ w["wq_b"]).reshape(
        s, H, nope + rope)
    ckv = h @ w["wkv_a"]
    lat = R.rms_norm(ckv[:, :r], w["kv_norm"], eps)
    kv = (lat @ w["wkv_b"]).reshape(s, H, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe, k_pe = rotary(q[..., nope:], c), rotary(ckv[:, r:], c)
    score = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_nope)
             + jnp.einsum("qhd,kd->hqk", q_pe, k_pe)) * softmax_scale(c)
    score = jnp.where(jnp.tril(jnp.ones((s, s), bool)), score, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, -1), v)
    return out.reshape(s, -1) @ w["wo"]


def swiglu(g, w):
    return (jax.nn.silu(g @ w["w_gate"]) * (g @ w["w_up"])) @ w["w_down"]


def router(g, w, c):
    """Step 5's router on g [S, D]: (s [S, E], group scores [S, groups],
    the reference's own picks [S, k])."""
    s = jax.nn.sigmoid(g @ w["router"])
    n, k = c["n_group"], c["num_experts_per_tok"]
    grouped = s.reshape(s.shape[0], n, -1)
    gs = jnp.sort(grouped, -1)[..., -2:].sum(-1)
    kept = jnp.argsort(-gs, -1)[:, :c["topk_group"]]
    keep = jnp.zeros_like(gs, bool).at[
        jnp.arange(s.shape[0])[:, None], kept].set(True)
    choice = jnp.where(jnp.repeat(keep, grouped.shape[-1], -1), s, -jnp.inf)
    return s, gs, jnp.argsort(-choice, -1)[:, :k]


def picks_gap(s, gs, picks, c):
    """How far a set of picks [S, k] lies from the reference's own rule, a
    token (0 for the reference's own picks): the most by which (1) a
    picked group falls under the fourth-best group score, (2) an expert of
    the picked groups that was not picked stands over a pick, or (3) a
    group left out stands over both the weakest pick (by its best expert)
    and the fifth-best group score (it had to be kept). Read so, a
    near-tie between two groups costs only the groups' gap."""
    S, E = s.shape
    n, kg = c["n_group"], c["topk_group"]
    per = E // n
    rows = jnp.arange(S)[:, None]
    ranked = jnp.sort(gs, -1)
    fourth = ranked[:, -kg]
    fifth = ranked[:, -kg - 1] if kg < n else jnp.full((S,), -jnp.inf)
    picked = jnp.zeros((S, n), bool).at[rows, picks // per].set(True)
    chosen = jnp.take_along_axis(s, picks, -1)
    g_gap = jnp.max(jnp.where(picked, fourth[:, None] - gs, 0.0), -1)
    inside = jnp.where(jnp.repeat(picked, per, -1), s, -jnp.inf)
    others = inside.at[rows, picks].set(-jnp.inf)
    e_gap = jnp.max(others, -1) - jnp.min(chosen, -1)
    best = jnp.max(s.reshape(S, n, per), -1)
    x_gap = jnp.max(jnp.where(picked, 0.0, jnp.minimum(
        best - jnp.min(chosen, -1)[:, None], gs - fifth[:, None])), -1)
    return jnp.maximum(jnp.maximum(g_gap, e_gap), x_gap).clip(0.0)


def experts(g, picks, weight, ex, first):
    """The held picks' weighted sum: every held expert over every row,
    the picks selecting."""
    out = jnp.zeros_like(g)
    for i in range(ex["gate"].shape[0]):
        gate, up, down = (ex[k][i].astype(R.F32) for k in ("gate", "up",
                                                          "down"))
        y = (jax.nn.silu(g @ gate.T) * (g @ up.T)) @ down
        wi = jnp.sum(jnp.where(picks == first + i, weight, 0.0), -1)
        out = out + wi[:, None] * y
    return out


def layer(x, w, c, follow=None, margin=0.0):
    """One layer on one sequence [S, D]; weights of any float type (an
    expert layer's held experts under ``w["experts"]``), computed in
    float32. ``follow`` [S, k]: the program's picks, taken where they lie
    within ``margin`` of this layer's own rule. Returns (x, (the picks
    taken, this layer's own, their gap)) — (x, None) for a dense layer."""
    ex = w.get("experts")
    w = jax.tree.map(lambda a: a.astype(R.F32),
                     {k: v for k, v in w.items() if k != "experts"})
    x = x.astype(R.F32)
    eps = c["rms_norm_eps"]
    x = x + attention(R.rms_norm(x, w["ln1"], eps), w, c)
    g = R.rms_norm(x, w["ln2"], eps)
    if ex is None:
        return x + swiglu(g, w), None
    s, gs, own = router(g, w, c)
    if follow is None:
        follow = own
    gap = picks_gap(s, gs, follow, c)
    picks = jnp.where((gap <= margin)[:, None], follow, own)
    top = jnp.take_along_axis(s, picks, -1)
    weight = top / top.sum(-1, keepdims=True) * c["routed_scaling_factor"]
    y = swiglu(g, w) + experts(g, picks, weight, ex,
                               c.get("first_held_expert", 0))
    return x + y, (picks, own, gap)


def _layer_weights(params, i, c):
    k = c["first_k_dense_replace"]
    if i < k:
        return jax.tree.map(lambda a: a[i], params["dense"])
    return {**jax.tree.map(lambda a: a[i - k], params["moe"]),
            "experts": jax.tree.map(lambda a: a[i - k], params["experts"])}


_HEAD_ROWS = 32768
_RECORD = {}        # a prompt's ids (bytes) -> [picks [L_moe, n, k] a call]
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
    """The program's picks [L_moe, n, k] for the first n of ``ids``, from
    the entry whose prompt they begin with (the longest, the newest), or
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
    a layer here takes the picks too."""
    margin = float(c.get("serve", {}).get("check", {}).get(
        "route_margin", 0.0))
    picked = _picked(ids)
    n = len(ids)
    if picked is not None and picked.shape[1] < n:
        raise ValueError(f"axk1 reference: the programs' picks cover "
                         f"{picked.shape[1]} of this sequence's {n} tokens")
    run = jax.jit(lambda x, w, f: layer(x, w, c, f, margin))
    x = params["embed"][jnp.asarray(ids)].astype(R.F32)
    k0 = c["first_k_dense_replace"]
    differed = followed = 0
    worst = 0.0
    for i in range(c["num_hidden_layers"]):
        f = None if picked is None or i < k0 \
            else jnp.asarray(picked[i - k0], jnp.int32)
        x, out = run(x, _layer_weights(params, i, c), f)
        if f is not None:
            e, own, gap = (np.asarray(t) for t in out)
            diff = np.any(np.sort(picked[i - k0], -1) != np.sort(own, -1),
                          -1)
            differed += int(diff.sum())
            followed += int((diff & np.all(e == picked[i - k0], -1)).sum())
            worst = max(worst, float(gap[diff].max(initial=0.0)))
    covered = n * (c["num_hidden_layers"] - k0)
    if picked is not None:
        FOLLOWED.append({"picks": covered, "differed": differed,
                         "followed": followed, "largest_gap": worst})
        say(f"axk1 reference: of {covered} pick sets (token x expert "
            f"layer) the program's differed from the reference's own on "
            f"{differed} ({100.0 * differed / max(covered, 1):.3f}%), "
            f"followed {followed} of them (route_margin {margin}); the "
            f"largest gap of a differing set {worst:.5f}")
    x = R.rms_norm(x, params["ln_f"].astype(R.F32), c["rms_norm_eps"])
    x, head = x[jnp.asarray(positions)], params["lm_head"]
    return jnp.concatenate(
        [x @ head[i:i + _HEAD_ROWS].astype(R.F32).T
         for i in range(0, head.shape[0], _HEAD_ROWS)], -1)


def loss(params, ids, c):
    """Mean next-token cross entropy of one sequence [S + 1]."""
    logits = logits_at(params, ids[:-1], c, jnp.arange(ids.shape[0] - 1))
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                ids[1:, None], -1).mean()


# -- the counts ---------------------------------------------------------

def attn_params(c: dict) -> int:
    """The five products of MLA, its two latent norms, the layer's two
    norms."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    return (d * rq + rq + rq * H * (nope + rope) + d * (rkv + rope) + rkv
            + rkv * H * (nope + vd) + H * vd * d + 2 * d)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def held_picks_a_token(c: dict) -> float:
    """Picks a token takes among the held experts, on average: k x held /
    routed (0.5 at 8 x 12 / 192)."""
    return c["num_experts_per_tok"] * c["n_routed_experts"] \
        / c["n_router_outputs"]


def layer_params(c: dict, dense: bool, active: bool = False) -> int:
    """A dense layer: MLA and its SwiGLU. An expert layer: MLA, the shared
    expert, the router over every routed expert, and the held experts
    (where ``active``: the average held picks a token's)."""
    d = c["hidden_size"]
    if dense:
        return attn_params(c) + 3 * d * c["intermediate_size"]
    held = expert_params(c) * c["num_experts_per_tok"] \
        * c["n_routed_experts"] // c["n_router_outputs"] if active \
        else c["n_routed_experts"] * expert_params(c)
    return (attn_params(c) + expert_params(c)
            + d * c["n_router_outputs"] + held)


def param_count(c: dict, active: bool = False) -> int:
    """The layers, the untied table and head (the head alone where
    ``active``), the last norm."""
    k = c["first_k_dense_replace"]
    table = c["vocab_size"] * c["hidden_size"]
    return (k * layer_params(c, True, active)
            + (c["num_hidden_layers"] - k) * layer_params(c, False, active)
            + (1 if active else 2) * table + c["hidden_size"])


def model_flops_per_token(c: dict, seq_len: int) -> float:
    """As every configuration counts it (``work.train_flops_per_token``,
    a head's size the file's ``head_dim``): no train step exists here."""
    return work.train_flops_per_token(c, param_count(c, True), seq_len)


def pool_width(c: dict) -> int:
    """A token's row a layer, ``[c ; k_pe]`` (576), padded to whole lane
    tiles (640): how HBM lays it out either way."""
    return -(-(c["kv_lora_rank"] + c["qk_rope_head_dim"]) // 128) * 128


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    """What a cached token holds in the pool: one padded row a layer."""
    return c["num_hidden_layers"] * pool_width(c) * bytes_per_value


def state_bytes_per_slot(c: dict) -> int:
    return 0


# -- the serve check's three calls into the program -------------------------
# (the cache is the program's own pytree: one latent pool; the picks go to
# ``_RECORD`` on their way out, in order)

def make_cache(cfg, num_pages: int, page_size: int, sequences: int):
    from paddle_tpu.inference.paged import init_pool
    from paddle_tpu.models import axk1

    return init_pool(cfg, num_pages, page_size,
                     pool_layout=axk1.pool_layout(cfg))


def prefill(family, params, ids, cfg, cache, page_rows, slen):
    from paddle_tpu.inference.paged import cache_prefill

    cache, logits, picks = cache_prefill(family, params, ids, cfg, cache,
                                         page_rows, slen, routes=True)
    jax.debug.callback(_keep_prefill, ids, slen, picks, ordered=True)
    return cache, logits


def decode_step(family, params, cache, block_tables, lengths, tokens, cfg):
    from paddle_tpu.inference.paged import cache_decode_step

    cache, logits, picks = cache_decode_step(
        family, params, cache, block_tables, lengths, tokens, cfg,
        routes=True)
    jax.debug.callback(_keep_step, picks, ordered=True)
    return cache, logits


# -- kernel and program work, found by ``roofline.work`` ---------------------

def _moe_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def mla_attn_work(c: dict, kv_token_steps: float):
    """(FLOPs, bytes) of absorbed latent attention over ``kv_token_steps``
    live cached tokens read, every layer: the score product over the row
    (``[c ; k_pe]``, 576) and the value product over ``c`` (512), two
    FLOPs a multiply-add, every head; the live rows read once for all
    the heads, 576 values of two bytes (not the row's padding)."""
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    row = r + c["qk_rope_head_dim"]
    L = c["num_hidden_layers"]
    return (2.0 * H * (row + r) * kv_token_steps * L,
            2.0 * row * kv_token_steps * L)


def mla_decode_work(params, ctx, trace):
    """The least time of the latent kernel's calls: max(bytes / the HBM's
    rate, FLOPs / the peak). Bytes are the live rows every slot's context
    holds, once a layer a decode step (``kv_token_steps``, tokens and not
    padded pages), plus each slot's query ``[q_lat ; q_pe]`` (64 x 576)
    and output (64 x 512) a layer a step; FLOPs ``mla_attn_work``'s. A
    kernel that reads a padded or a dead page, or a page twice, reads
    lower; none passes 100%. Against every call's time."""
    c, n = ctx["config"], ctx["counters"]
    flops, byts = mla_attn_work(c, n["kv_token_steps"])
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    qo = (n["traced_decode_steps"] * c["num_hidden_layers"]
          * c["serve"]["num_slots"] * H * (2 * r + c["qk_rope_head_dim"])
          * 2.0)
    least = max((byts + qo) / ctx["peaks"]["hbm_bytes"],
                flops / ctx["peaks"]["flops"])
    by_bytes = (byts + qo) / ctx["peaks"]["hbm_bytes"]
    say(f"mla_decode_work: rows {byts:.6g} B, queries and outputs "
        f"{qo:.6g} B, {flops:.6g} FLOPs; bound by "
        f"{'bytes' if by_bytes >= least * (1 - 1e-12) else 'FLOPs'}")
    secs = trace.matching(params["line"], params["pattern"])[0]
    return least, secs


def moe_expert_bytes(params, ctx, trace):
    """Bytes the decode steps' expert kernel has to read: the three
    matrices of every HELD expert that at least one pick named, a layer a
    step (``engine.expert_reads`` over ``engine.decode_steps`` of the
    window, times the steps decoded while tracing), never the 12 held
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
    the absorption's products through ``W_UKV``'s count, the head), and
    the latent attention's two products over the live tokens read."""
    c, n = ctx["config"], ctx["counters"]
    dense = 2.0 * param_count(c, True) * n["traced_tokens_decoded"]
    attn = mla_attn_work(c, n["kv_token_steps"])[0]
    say(f"decode_step_flops: parameters {dense:.6g}, latent attention "
        f"{attn:.6g}")
    secs = trace.matching(params["line"], params["pattern"])[0]
    return (dense + attn) / ctx["peaks"]["flops"], secs
