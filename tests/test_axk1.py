"""A.X K1 at a tiny size (one dense layer and two expert layers, 4 heads,
ranks 32 and 16, rope 8, 16 routed experts in 4 groups of which this
"chip" holds 4 from the fifth on): the stack through the paged programs
and the engine against ``forward``, the one latent leaf of the pool, the
absorbed decode against decompressed attention, the latent kernel in
interpret mode against its reference, the router's group rule by hand,
what the generalised router and expert kernel leave as they were
(deepseek-moe-16b's routing, ZAYA's one pick a row), YaRN, the engine's
counters of held picks, and the refusals."""
import importlib.util
import math
import subprocess

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.core import enforce as E
from paddle_tpu.inference import Request, ServingEngine
from paddle_tpu.inference.paged import (cache_decode_step, cache_prefill,
                                        cache_prefill_shared,
                                        cache_verify_window, init_pool)
from paddle_tpu.kernels import mla_decode as MLA
from paddle_tpu.kernels import moe_experts as M
from paddle_tpu.models import axk1 as A
from paddle_tpu.models import moe
from paddle_tpu.nn.functional.attention import yarn_inv_freq, yarn_mscale


@pytest.fixture(scope="module")
def model():
    c = A.axk1_tiny()
    return c, A.init_params(c, jax.random.PRNGKey(0))


def make_cache(c, pages, ps):
    return init_pool(c, pages, ps, pool_layout=A.pool_layout(c))


# -- the stack and the pool --------------------------------------------------

def test_the_stack_is_declared_and_the_pool_is_one_latent_leaf(model):
    c, params = model
    assert [(s.kind, s.count, s.keeps) for s in A.segments(c)] == [
        ("dense", 1, ("pages",)), ("moe", 2, ("pages",))]
    assert A.pool_layout(c) == (3, 1, 128, ("latent",))
    cache = make_cache(c, 6, 4)
    assert set(cache) == {"latent"} and cache["latent"].shape == (
        3, 6, 1, 4, 128)
    assert params["experts"]["gate"].shape == (2, 4, 32, 64)
    assert params["moe"]["router"].shape == (2, 64, 16)
    # at the published widths the row is 576 numbers, five lane tiles
    assert A.pool_layout(A.AxK1Config(num_hidden_layers=5))[1:3] == (1, 640)


@pytest.mark.parametrize("ps", [4, 8])
def test_prefill_then_decode_through_the_latent_cache_is_forward(model, ps):
    """A prefill of prompts that end inside a page, then decode steps that
    cross into the next, read only the latent pool and give forward's
    logits; the programs hand back every expert layer's picks."""
    c, params = model
    n, plen, steps = 2, 10, 4
    ids = np.random.default_rng(1).integers(0, c.vocab_size, (n, plen + steps))
    want, routes = A.forward(params, jnp.asarray(ids), c, with_routes=True)
    per = -(-(plen + steps) // ps)
    rows = np.arange(n * per, dtype=np.int32).reshape(n, per)
    s_pad = -(-plen // ps) * ps
    padded = np.zeros((n, s_pad), np.int32)
    padded[:, :plen] = ids[:, :plen]
    cache, logits, picks = cache_prefill(
        A, params, jnp.asarray(padded), c, make_cache(c, n * per, ps),
        jnp.asarray(rows[:, :s_pad // ps]), jnp.full((n,), plen, jnp.int32),
        routes=True)
    np.testing.assert_allclose(logits, want[:, plen - 1], atol=2e-6)
    assert picks.shape == (2, n, s_pad, 4)
    np.testing.assert_array_equal(picks[:, :, :plen], routes[:, :, :plen])
    for t in range(steps):
        cache, logits, picks = cache_decode_step(
            A, params, cache, jnp.asarray(rows),
            jnp.full((n,), plen + t + 1, jnp.int32),
            jnp.asarray(ids[:, plen + t], jnp.int32), c, routes=True)
        np.testing.assert_allclose(logits, want[:, plen + t], atol=2e-6)
        np.testing.assert_array_equal(picks[:, :, 0], routes[:, :, plen + t])
    assert set(cache) == {"latent"}


def test_the_absorbed_decode_is_decompressed_attention():
    """``q_lat = W_UK^T q_nope`` against the rows ``[c ; k_pe]`` and ``o =
    W_UV o_lat`` give what keys and values decompressed a head give."""
    H, nope, rope, vd, r, S = 4, 16, 8, 16, 32, 11
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    lat = jax.random.normal(ks[0], (S, r))
    k_pe = jax.random.normal(ks[1], (S, rope))
    up = jax.random.normal(ks[2], (r, H, nope + vd)) * r ** -0.5
    q_nope = jax.random.normal(ks[3], (H, nope))
    q_pe = jax.random.normal(ks[4], (H, rope))
    scale = 0.3
    k = jnp.concatenate([jnp.einsum("sr,rhd->shd", lat, up[..., :nope]),
                         jnp.broadcast_to(k_pe[:, None], (S, H, rope))], -1)
    v = jnp.einsum("sr,rhd->shd", lat, up[..., nope:])
    s = jnp.einsum("hd,shd->hs", jnp.concatenate([q_nope, q_pe], -1),
                   k) * scale
    want = jnp.einsum("hs,shd->hd", jax.nn.softmax(s, -1), v)
    width = 128
    row = jnp.pad(jnp.concatenate([lat, k_pe], -1),
                  ((0, 5), (0, width - r - rope)))           # two pages of 8
    pool = row.reshape(2, 1, 8, width)
    q = jnp.pad(jnp.concatenate([jnp.einsum("hd,rhd->hr", q_nope,
                                            up[..., :nope]), q_pe], -1),
                ((0, 0), (0, width - r - rope)))[None]
    o_lat = MLA.mla_decode_ref(q, pool, jnp.array([[0, 1]]),
                               jnp.array([S]), rank=r, scale=scale)
    got = jnp.einsum("hr,rhd->hd", o_lat[0], up[..., nope:])
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("dtype,lengths", [
    (jnp.float32, [0, 37, 96, 5]), (jnp.bfloat16, [64, 1, 0, 90])] + [
    (dtype, lengths) for dtype in (jnp.float32, jnp.bfloat16)
    for lengths in ([0, 513, 1536, 1, 1100], [1024, 511, 0, 512, 1536],
                    [1100, 1, 1024, 0], [0, 0, 0])])
def test_the_latent_kernel_is_its_reference(dtype, lengths):
    """Pages of 16 rows of 640 scattered over a pool of two layers, the
    second read; an empty slot is a zero row. A block is 32 pages (512
    tokens): the longer cases walk the skewed loop over up to three blocks
    a slot, the copies streaming ahead across slots and past empty ones,
    a one-block slot between two longer ones."""
    B, H, W, ps = len(lengths), 16, 640, 16
    maxp = max(6, -(-max(lengths) // ps))
    P = B * maxp + 6
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, W)).astype(dtype)
    pool = jax.random.normal(ks[1], (2, P, 1, ps, W)).astype(dtype)
    bt = jax.random.permutation(ks[2], P)[:B * maxp].reshape(B, maxp)
    n = jnp.asarray(lengths, jnp.int32)
    got = MLA.mla_decode(q, pool, bt, n, rank=512, scale=0.05,
                         layer=jnp.int32(1), interpret=True)
    want = MLA.mla_decode_ref(q, pool, bt, n, rank=512, scale=0.05,
                              layer=jnp.int32(1))
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    for empty in np.flatnonzero(np.asarray(lengths) == 0):
        assert not np.any(np.asarray(got[empty], np.float32))
    assert MLA.supported(q, pool, 512)
    assert not MLA.supported(q, pool[:, :, :, :12], 512)      # a page of 12
    assert not MLA.supported(q, pool, 576)     # a value prefix off the lanes


def test_the_dispatcher_counts_the_latent_kernel_and_its_fallback():
    q = jnp.zeros((2, 8, 128), jnp.float32)
    pool = jnp.zeros((3, 4, 1, 8, 128), jnp.float32)
    bt, n = jnp.zeros((2, 2), jnp.int32), jnp.array([3, 0], jnp.int32)
    before = kernels.dispatch_stats()
    out = kernels.dispatched_mla_decode(q, pool, bt, n, rank=128, scale=1.0,
                                        layer=jnp.int32(2))
    after = kernels.dispatch_stats()
    assert out.shape == (2, 8, 128)
    assert after["mla_fallback"] == before["mla_fallback"] + 1
    assert after["mla"] == before["mla"]


# -- the router ---------------------------------------------------------------

def test_the_group_rule_the_norm_and_the_scale_by_hand():
    """8 experts in 4 groups of 2, the best 2 groups kept, 2 picks. The
    scores (sigmoid of the logits): group 0 (0.9, 0.1) sums to 1.0, group 1
    (0.6, 0.6) to 1.2, group 2 (0.95, 0.01) to 0.96, group 3 (0.5, 0.45)
    to 0.95. Groups 1 and 0 are kept; expert 4 (0.95) is the best score
    but its group is not, so the picks are 0 (0.9) and one of 2, 3 (0.6):
    weights 0.9 / 1.5 and 0.6 / 1.5, times 2.5."""
    s = np.array([0.9, 0.1, 0.6, 0.6 - 1e-4, 0.95, 0.01, 0.5, 0.45])
    logits = np.log(s / (1 - s))
    c = A.axk1_tiny(n_router_outputs=8, n_routed_experts=4,
                    first_held_expert=0, n_group=4, topk_group=2,
                    num_experts_per_tok=2)
    w, e, _ = moe._route(jnp.eye(8)[:1].astype(jnp.float32),
                         {"router": jnp.asarray(logits[None], jnp.float32)
                          * jnp.eye(8)[:1].T}, c)
    assert e.tolist() == [[0, 2]]
    np.testing.assert_allclose(w, [[0.9 / 1.5 * 2.5, 0.6 / 1.5 * 2.5]],
                               rtol=1e-5)


def _old_route(x, lp, c):
    """models/moe.py::_route before PR 37, word for word."""
    logits = (x.astype(jnp.float32) @ lp["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, c.num_experts_per_tok)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    return topv, topi


def test_the_default_routing_is_deepseek_moes_bit_for_bit():
    c = moe.moe_tiny(num_experts=64, num_experts_per_tok=6, hidden_size=128)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    x = jax.random.normal(ks[0], (300, 128), jnp.bfloat16)
    lp = {"router": jax.random.normal(ks[1], (128, 64))}
    w, e, _ = jax.jit(lambda x, lp: moe._route(x, lp, c))(x, lp)
    w0, e0 = jax.jit(lambda x, lp: _old_route(x, lp, c))(x, lp)
    np.testing.assert_array_equal(e, e0)
    np.testing.assert_array_equal(np.asarray(w).view(np.uint32),
                                  np.asarray(w0).view(np.uint32))


# -- the expert kernel: k picks, a held range, and ZAYA's one pick -------------

def _parent_experts_module():
    try:
        text = subprocess.run(
            ["git", "show", "ca25dec:paddle_tpu/kernels/moe_experts.py"],
            cwd=__file__.rsplit("/tests/", 1)[0], check=True,
            capture_output=True, text=True).stdout
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here to read the parent's kernel from")
    spec = importlib.util.spec_from_loader("_parent_moe_experts", loader=None)
    module = importlib.util.module_from_spec(spec)
    exec(compile(text, "_parent_moe_experts", "exec"), module.__dict__)
    return module


@pytest.mark.parametrize("T,Ex", [(64, 16), (256, 16)])
def test_one_pick_a_row_with_every_expert_held_is_the_parents_call(T, Ex):
    """ZAYA's call (one expert a row, all held, no weight) traces to the
    parent's program, equation for equation, and gives its bits."""
    old = _parent_experts_module()
    ks = jax.random.split(jax.random.PRNGKey(T), 4)
    x = jax.random.normal(ks[0], (T, 128), jnp.float32)
    e = jax.random.randint(ks[1], (T,), 0, Ex)
    g = jax.random.normal(ks[2], (2, Ex, 128, 128)) * 0.1
    d = jax.random.normal(ks[3], (2, Ex, 128, 128)) * 0.1

    def new(*a):
        return M.expert_mlp(*a, name="moe_expert_mlp_decode", interpret=True)

    def parent(*a):
        return old.expert_mlp(*a, name="moe_expert_mlp_decode",
                              interpret=True)

    args = (x, e, g, g, d, 1)
    assert str(jax.make_jaxpr(new)(*args)) == str(jax.make_jaxpr(parent)(
        *args))
    np.testing.assert_array_equal(np.asarray(new(*args)).view(np.uint32),
                                  np.asarray(parent(*args)).view(np.uint32))


@pytest.mark.parametrize("first,routed", [(0, 4), (4, 16), (12, 16)])
def test_k_picks_over_a_held_range_is_the_reference(first, routed):
    """Rows of 3 picks among ``routed`` experts, the weights holding 4 of
    them from ``first``: a pick elsewhere adds nothing, the held ones are
    weighed and summed (the kernel in interpret mode, the reference, and
    a sum written out)."""
    T, D, F, Ex, k = 24, 128, 128, 4, 3
    ks = jax.random.split(jax.random.PRNGKey(first + routed), 6)
    x = jax.random.normal(ks[0], (T, D))
    e = jnp.stack([jax.random.permutation(kk, routed)[:k]
                   for kk in jax.random.split(ks[1], T)])
    w = jax.random.uniform(ks[2], (T, k))
    g, u, d = (jax.random.normal(kk, (1, Ex, F, D)) * 0.1 for kk in ks[3:])
    got = M.expert_mlp(x, e, g, u, d, 0, weight=w, first=first,
                       routed=routed, interpret=True)
    ref = M.expert_mlp_ref(x, e, g, u, d, 0, weight=w, first=first,
                           routed=routed)
    one = [M.expert_mlp_ref(x, jnp.full((T,), i), g, u, d, 0)
           for i in range(Ex)]
    want = sum(jnp.sum(jnp.where(e == first + i, w, 0.0), -1)[:, None]
               * one[i] for i in range(Ex))
    np.testing.assert_allclose(got, want, atol=1e-4)
    np.testing.assert_allclose(ref, want, atol=1e-4)
    held = (e >= first) & (e < first + Ex)
    if not bool(held.any()):
        assert float(jnp.max(jnp.abs(got))) == 0.0


def test_a_call_whose_picks_are_all_another_chips_adds_nothing():
    """A chunk of a prompt's padding routes every row alike, and all its
    picks may lie outside the held range: no tile is in use, the kernel
    computes nothing and the rows' sum is zero (on the chip the row block
    is clamped to the first, PR 37: the block before it is off the rows,
    and the device halts on its bounds check)."""
    T, D, F = 32, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    x = jax.random.normal(ks[0], (T, D))
    e = jnp.tile(jnp.arange(8, 11), (T, 1))            # held: 0..3
    g, u, d = (jax.random.normal(kk, (1, 4, F, D)) * 0.1 for kk in ks[1:])
    got = M.expert_mlp(x, e, g, u, d, 0, weight=jnp.ones((T, 3)), first=0,
                       routed=16, interpret=True)
    assert got.shape == (T, D) and float(jnp.max(jnp.abs(got))) == 0.0


# -- YaRN -----------------------------------------------------------------------

def test_yarn_frequencies_and_scale_are_deepseek_v3s():
    """At A.X K1's numbers (rope 64, theta 10,000, factor 32, original
    4,096, beta 32 and 1) the ramp runs over pairs 10 to 23: below it the
    plain frequencies, above it a 32nd of them; the softmax scale is
    192^-0.5 (0.1 ln 32 + 1)^2 and cos and sin keep their length."""
    inv = np.asarray(yarn_inv_freq(64, theta=1e4, factor=32,
                                   original_max_position_embeddings=4096,
                                   beta_fast=32, beta_slow=1))
    plain = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 32, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(inv[11:23], plain[11:23] / 32 * ramp
                               + plain[11:23] * (1 - ramp), rtol=1e-5)
    c = A.AxK1Config(num_hidden_layers=5, rope_scaling={
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"})
    m = 0.1 * math.log(32) + 1
    assert yarn_mscale(32, 1) == pytest.approx(m) and m == pytest.approx(
        1.3466, abs=1e-4)
    assert c.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    assert c.rope_gain == 1.0
    np.testing.assert_allclose(c.inv_freq(), inv)


# -- the engine -------------------------------------------------------------------

def test_the_engine_serves_it_and_counts_held_picks(model):
    c, params = model
    eng = ServingEngine(A, params, c, num_slots=4, max_len=64, page_size=8,
                        num_pages=40)
    assert set(eng.cache.pool) == {"latent"}
    for i in range(6):
        eng.submit(Request(rid=i, prompt=(np.arange(5 + i) * 7 + i).astype(
            np.int32) % c.vocab_size, max_new_tokens=7))
    while eng.step():
        pass
    st = eng.stats
    assert st.completed == 6 and st.preempted == 0
    # every slot of the grid routes k picks a layer a step; those among
    # experts 4..7 are computed here, the others are other chips'
    assert st.expert_rows == st.decode_steps * 2 * 4 * 4
    assert st.expert_picks_held + st.expert_picks_elsewhere == st.expert_rows
    assert 0 < st.expert_picks_held < st.expert_rows
    assert st.expert_reads <= st.decode_steps * 2 * 4
    for i in range(6):               # greedy: forward's argmax, token by token
        prompt = (np.arange(5 + i) * 7 + i) % c.vocab_size
        toks = eng.outputs[i].tokens
        logits = A.forward(params, jnp.asarray(np.concatenate(
            [prompt, toks[:-1]])[None]), c)
        assert list(np.argmax(logits[0, 4 + i:], -1)) == list(toks)


# -- refusals ---------------------------------------------------------------------

@pytest.mark.parametrize("flag,missing", [
    ("kv_quant", "int8 form of the latent row"),
    ("spec_decode", "verify window over latent pages"),
    ("prefix_cache", "prefix cache over latent pages")])
def test_what_the_latent_pool_cannot_do_is_refused(model, flag, missing):
    c, params = model
    with pytest.raises(E.UnimplementedError, match=missing):
        ServingEngine(A, params, c, num_slots=2, max_len=32, page_size=8,
                      **{flag: True})


def test_the_data_plane_refuses_a_latent_pool_by_name(model):
    c, params = model
    with pytest.raises(E.UnimplementedError, match="int8 pages of a latent"):
        init_pool(c, 4, 8, kv_quant=True, pool_layout=A.pool_layout(c))
    cache = make_cache(c, 4, 8)
    with pytest.raises(E.UnimplementedError, match="latent pool"):
        cache_verify_window(A, params, jnp.zeros((1, 2), jnp.int32), c,
                            cache, jnp.zeros((1, 2), jnp.int32),
                            jnp.zeros((1,), jnp.int32),
                            jnp.ones((1,), bool))
    with pytest.raises(E.UnimplementedError, match="latent pool"):
        cache_prefill_shared(A, params, jnp.zeros((1, 8), jnp.int32), c,
                             cache, jnp.zeros((1, 1), jnp.int32),
                             jnp.ones((1,), jnp.int32),
                             jnp.zeros((1, 1), jnp.int32))


def test_no_train_step_and_no_experts_across_chips():
    with pytest.raises(E.UnimplementedError, match="no train step"):
        A.loss_fn(None, None, None)
    with pytest.raises(E.UnimplementedError, match="experts across chips"):
        A.axk1_tiny(ep_size=2)
    with pytest.raises(E.PreconditionNotMetError, match="held experts"):
        A.axk1_tiny(first_held_expert=14)
