"""Phi-4-mini-flash at a tiny size (8 layers: 2 x [Mamba, window], the
Mamba layer that hands on its memory, the full layer, 1 x [GMU, cross];
window 8 in a ring of 12, heads of 8): the declared stack through the paged
programs and the engine against ``forward`` (every layer at every position,
no cache), the cache the declaration sizes, and the kernels the family
brought, in interpret mode against their references."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import enforce as E
from paddle_tpu.inference import Request, ServingEngine
from paddle_tpu.inference.paged import (PagedKVCache, cache_decode_step,
                                        cache_prefill, init_pool)
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import ssm
from paddle_tpu.models import phi4flash as P
from paddle_tpu.nn.functional.attention import sdpa_reference

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
PS = 4


@pytest.fixture(scope="module")
def model():
    c = P.phi4flash_tiny()
    return c, P.init_params(c, jax.random.PRNGKey(0))


def make_cache(c, pages, rows):
    return init_pool(c, pages, PS, state_shapes=P.state_shapes(c),
                     state_rows=rows, pool_layout=P.pool_layout(c))


# -- the declaration, and the cache it sizes ---------------------------------

def test_the_declaration_is_the_stack_and_the_params_follow_it(model):
    c, params = model
    segs = P.segments(c)
    assert [(s.kind, s.count) for s in segs] == [
        ("mamba_window", 2), ("mamba_mem", 1), ("full", 1), ("gmu_cross", 1)]
    assert [s.keeps for s in segs] == [("state", "ring"), ("state",),
                                       ("pages",), ()]
    assert [s.last_only for s in segs] == [False, False, False, True]
    for s in segs:
        for leaf in jax.tree.leaves(params[s.kind]):
            assert leaf.shape[0] == s.count
    big = P.Phi4FlashConfig()
    assert [(s.kind, s.count) for s in P.segments(big)] == [
        ("mamba_window", 8), ("mamba_mem", 1), ("full", 1), ("gmu_cross", 7)]
    assert P.pool_layout(big) == (1, 10, 128) and big.head_dim == 64
    assert big.mamba_dt_rank == 160 and big.ring_tokens == 528


@pytest.mark.parametrize("max_len", [64, 4096])
def test_one_pool_layer_and_rows_that_do_not_grow_with_the_context(max_len):
    """The cache of an engine at two context limits: one layer of pages, and
    a row of rings and states a slot whose bytes are the same."""
    c = P.phi4flash_tiny()
    cache = PagedKVCache(c, 2 * max_len // PS, PS, max_len // PS,
                         state_shapes=P.state_shapes(c), state_rows=3,
                         pool_layout=P.pool_layout(c))
    pool = jax.eval_shape(lambda: cache.pool)
    assert pool["k"].shape == (1, 2 * max_len // PS, 2, PS, 16)
    st = pool["state"]
    assert st["ring_k"].shape == st["ring_v"].shape == (2, 4, 3, 2, 4, 16)
    assert st["ssm"].shape == (3, 4, 8, 128) and st["ssm"].dtype == jnp.float32
    assert st["conv"].shape == (3, 4, 3, 128)


def test_what_shares_or_rewinds_pages_is_refused(model):
    c, params = model
    for flag in ("prefix_cache", "spec_decode", "kv_quant"):
        with pytest.raises(E.UnimplementedError, match="recurrent state"):
            ServingEngine(P, params, c, num_slots=2, max_len=32,
                          page_size=PS, **{flag: True})
    eng = ServingEngine(P, params, c, num_slots=2, max_len=32, page_size=PS)
    eng.cache.alloc.alloc(0, 8)
    with pytest.raises(E.UnimplementedError, match="no state snapshot"):
        eng.cache.alloc.fork(0, 1)


# -- the paged programs against the whole model ------------------------------

def test_prefill_and_decode_through_the_cache_are_the_whole_model(model):
    """Prompts of unequal length (so slots of unequal age), one row a
    padding dummy, then 30 decode steps: contexts pass three rings of 12,
    the window's lower bound and several pages; one slot goes inactive half
    way. Prefill's logits come from the cross-decoder run on the last
    position alone; ``forward`` runs it on every position."""
    c, params = model
    G, S, steps = 3, 16, 30
    slens = np.array([15, 9, 3], np.int32)
    ids = np.random.default_rng(1).integers(
        0, c.vocab_size, (G, int(slens.max()) + steps)).astype(np.int32)
    full = np.asarray(P.forward(params, jnp.asarray(ids), c))
    per = -(-(int(slens.max()) + steps) // PS)
    cache = make_cache(c, G * per, G)
    rows = np.arange(G * per, dtype=np.int32).reshape(G, per)
    padded = np.zeros((4, S), np.int32)
    for g in range(G):
        padded[g, :slens[g]] = ids[g, :slens[g]]
    page_rows = np.full((4, S // PS), G * per, np.int32)     # dummy: sentinel
    page_rows[:G] = rows[:, :S // PS]
    cache, logits = jax.jit(
        lambda p, i, ca, r, sl, sr: cache_prefill(P, p, i, c, ca, r, sl, sr)
    )(params, jnp.asarray(padded), cache, jnp.asarray(page_rows),
      jnp.asarray(np.append(slens, 1)), jnp.asarray([0, 1, 2, 3]))
    for g in range(G):
        np.testing.assert_allclose(np.asarray(logits[g]),
                                   full[g, slens[g] - 1], atol=2e-6)
    dec = jax.jit(lambda p, ca, bt, ln, tok, sr: cache_decode_step(
        P, p, ca, bt, ln, tok, c, sr))
    lens, live = slens.copy(), np.ones(G, bool)
    for t in range(steps):
        if t == steps // 2:
            live[1] = False                       # slot 1 coasts from here
            frozen = jax.tree.map(lambda a: np.asarray(a[:, 1]),
                                  cache["state"])
        tok = np.array([ids[g, lens[g]] for g in range(G)], np.int32)
        lens = lens + live
        cache, logits = dec(params, cache, jnp.asarray(rows),
                            jnp.asarray(np.where(live, lens, 0)),
                            jnp.asarray(tok), jnp.arange(G))
        for g in np.flatnonzero(live):
            np.testing.assert_allclose(np.asarray(logits[g]),
                                       full[g, lens[g] - 1], atol=2e-6)
    for k, was in frozen.items():                 # its row was not touched
        np.testing.assert_array_equal(np.asarray(cache["state"][k][:, 1]),
                                      was)


def greedy(params, c, prompt, n):
    """Greedy tokens by ``forward`` over one buffer of the final length
    (causal: what lies past a position does not move its logits)."""
    p = len(prompt)
    ids = np.zeros((1, p + n), np.int32)
    ids[0, :p] = prompt
    fwd = jax.jit(lambda i: jnp.argmax(P.forward(params, i, c), -1))
    for t in range(p, p + n):
        ids[0, t] = int(fwd(jnp.asarray(ids))[0, t - 1])
    return ids[0, p:].tolist()


@pytest.mark.parametrize("pages", [64, 14])
def test_the_engine_serves_it_and_rebuilds_rings_and_states_on_preemption(
        model, pages):
    """Five requests through ``submit`` / ``step`` on three slots, against
    a greedy loop over ``forward``. With 14 pages the pool runs out: a
    sequence is preempted and resumed, its rings and states rebuilt by
    prefill, its row handed out again."""
    c, params = model
    rng = np.random.default_rng(2)
    reqs = [Request(rid=i, max_new_tokens=n, prompt=rng.integers(
        0, c.vocab_size, p).astype(np.int32))
        for i, (p, n) in enumerate([(5, 20), (11, 14), (3, 9), (14, 12),
                                    (7, 16)])]
    eng = ServingEngine(P, params, c, num_slots=3, max_len=48, page_size=PS,
                        num_pages=pages, decode_chunk=2)
    outs = eng.run(reqs)
    for r in reqs:
        assert list(outs[r.rid].tokens) == greedy(params, c, r.prompt,
                                                  r.max_new_tokens), r.rid
    assert (eng.stats.preempted > 0) == (pages == 14)
    assert eng.stats.state_rows_assigned == 5 + eng.stats.preempted
    eng.cache.alloc.check_invariants()
    assert eng.cache.alloc.used_rows == 0


# -- the kernels, in interpret mode -------------------------------------------

def _rand(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def test_window_paged_kernel_reads_the_ring_by_age():
    """Rings of 5 pages of 8, a window of 32: lengths under the window,
    between window and ring, many rings long, 0 (an empty slot), and two
    slots on one row; against the gather reference and a brute-force read
    of the positions the window holds."""
    rng = np.random.default_rng(0)
    Lw, R, pages, kv, ps, hd, window = 2, 5, 5, 2, 8, 128, 32
    ring = pages * ps
    rk, rv = (_rand(rng, Lw, R, pages, kv, ps, hd) for _ in range(2))
    B, nh = 6, 8
    q = _rand(rng, B, nh, hd)
    rows = jnp.asarray([0, 3, 1, 4, 4, 2], jnp.int32)
    lengths = jnp.asarray([5, 40, 0, 33, 200, 32], jnp.int32)
    for layer in (0, 1):
        got = pa.ring_window_attention(q, rk, rv, layer, rows, lengths,
                                       window=window, scale=0.125,
                                       interpret=True)
        ref = pa.ring_window_attention(q, rk, rv, layer, rows, lengths,
                                       window=window, scale=0.125, ref=True)
        np.testing.assert_allclose(got, ref, atol=2e-6)
    want = np.zeros((B, nh, hd))
    for i in range(B):
        n = int(lengths[i])
        slots = [p % ring for p in range(max(0, n - window), n)]
        if not slots:
            continue
        K, V = (np.stack([np.asarray(r[1, rows[i], s // ps, :, s % ps])
                          for s in slots]) for r in (rk, rv))
        for h in range(nh):
            sc = K[:, h // (nh // kv)] @ np.asarray(q[i, h]) * 0.125
            p = np.exp(sc - sc.max())
            want[i, h] = (p / p.sum()) @ V[:, h // (nh // kv)]
    np.testing.assert_allclose(ref, want, atol=2e-6)


def test_paged_kernel_serves_pairs_of_64_as_heads_of_128():
    """Differential attention over heads of 64 computed directly, against
    the paged kernel reading key and value pairs as heads of 128 with the
    queries sent as ``q1|0`` and ``0|q2``."""
    rng = np.random.default_rng(3)
    c = P.Phi4FlashConfig(hidden_size=256, num_attention_heads=4,
                          num_key_value_heads=2, intermediate_size=64,
                          num_hidden_layers=8, vocab_size=64,
                          dtype=jnp.float32)
    B, T, ps, hd = 3, 24, 8, 64
    q, k, v = (_rand(rng, B, 4 * hd), _rand(rng, B, T, 2 * hd),
               _rand(rng, B, T, 2 * hd))
    lengths = jnp.asarray([24, 7, 17], jnp.int32)
    pool_k, pool_v = (jnp.moveaxis(P._pairs(t, c).reshape(
        B, T // ps, ps, 1, 2 * hd), 3, 2).reshape(1, -1, 1, ps, 2 * hd)
        for t in (k, v))
    bt = jnp.arange(B * T // ps, dtype=jnp.int32).reshape(B, -1)
    got = pa.ragged_paged_attention(P._pair_queries(q, c), pool_k, pool_v,
                                    bt, lengths, scale=hd ** -0.5, layer=0,
                                    interpret=True)            # [B, 4, 128]
    kh, vv = k.reshape(B, T, 2, hd), v                # one pair: both heads
    qh = q.reshape(B, 4, hd)
    for b in range(B):
        n = int(lengths[b])
        for h in range(4):
            sc = np.asarray(kh[b, :n, h % 2] @ qh[b, h]) / 8.0
            p = np.exp(sc - sc.max())
            np.testing.assert_allclose(got[b, h], (p / p.sum())
                                       @ np.asarray(vv[b, :n]), atol=2e-6)


@pytest.mark.parametrize("S,window,bq,bk,resident", [
    (512, 128, 128, 128, None), (512, 200, 256, 128, None),
    (1024, 512, 512, 512, None), (256, 64, 128, 64, None),
    (512, 100, 128, 256, None), (1024, 130, 128, 128, 512 * 1024)])
def test_flash_forward_with_a_window(monkeypatch, S, window, bq, bk,
                                     resident):
    """The K loop starts at the window's first sub-block and masks the
    sub-blocks its edge and the diagonal cross; blocks wider and narrower
    than the window; the last case keeps a quarter of the keys resident,
    so whole spans lie behind the window and are never fetched."""
    if resident:
        from paddle_tpu.kernels import tiling

        monkeypatch.setattr(tiling, "FLASH_RESIDENT_BYTES", resident)
        assert tiling.flash_span(S, bk, 128, jnp.float32) == S // 4
    rng = np.random.default_rng(4)
    q, k, v = (_rand(rng, 1, S, 4, 128), _rand(rng, 1, S, 2, 128),
               _rand(rng, 1, S, 2, 128))
    got = fa.flash_attention(q, k, v, causal=True, scale=0.125,
                             window=window, block_q=bq, block_k=bk,
                             interpret=True)
    d = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    want = sdpa_reference(q, k, v, ((d >= 0) & (d < window))[None, None],
                          scale=0.125)
    np.testing.assert_allclose(got, want, atol=5e-6)
    with pytest.raises(ValueError):
        fa.flash_attention(q, k, v, window=window)


def test_s6_update_in_place_against_its_reference():
    rng = np.random.default_rng(5)
    L, R, N, C, B = 2, 5, 16, 256, 4
    state = _rand(rng, L, R, N, C)
    rows = jnp.asarray([1, 4, 4, 0], jnp.int32)     # two slots on nobody's
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, (B, C)), jnp.float32)
    x, b, c = _rand(rng, B, C), _rand(rng, B, N), _rand(rng, B, N)
    a = -jnp.asarray(rng.uniform(1, 16, (N, C)), jnp.float32)
    new, y = ssm.ssm_state_update_s6(state, 1, rows, dt, dt * x, a, b, c,
                                     interpret=True)
    ref, yr = ssm.ssm_state_update_s6_ref(state, 1, rows, dt, dt * x, a, b, c)
    own = jnp.asarray([0, 3])                        # rows with one writer
    np.testing.assert_allclose(y[own], yr[own], atol=2e-6)
    np.testing.assert_allclose(new[1, rows[own]], ref[1, rows[own]],
                               atol=1e-6)
    np.testing.assert_array_equal(new[0], state[0])  # the other layer
    np.testing.assert_array_equal(new[1, 2:4], state[1, 2:4])
    assert ssm.s6_supported(state, dt)
    assert not ssm.s6_supported(state.astype(jnp.bfloat16), dt)


def test_s6_scan_is_the_update_token_by_token_and_padding_changes_nothing():
    rng = np.random.default_rng(6)
    G, S, N, C = 2, 11, 8, 128
    x, b, c = _rand(rng, G, S, C), _rand(rng, G, S, N), _rand(rng, G, S, N)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, (G, S, C)), jnp.float32)
    slen = jnp.asarray([11, 6])
    dt = jnp.where((jnp.arange(S) < slen[:, None])[..., None], dt, 0.0)
    a = -jnp.asarray(rng.uniform(1, 16, (N, C)), jnp.float32)
    y, last = ssm.s6_scan_ref(x, dt, a, b, c)
    state = jnp.zeros((1, G, N, C))
    for t in range(S):
        rows = jnp.arange(G)
        state, yt = ssm.ssm_state_update_s6_ref(
            state, 0, rows, dt[:, t], dt[:, t] * x[:, t], a, b[:, t], c[:, t])
        np.testing.assert_allclose(y[:, t], yt, atol=1e-5)
        if t == 5:
            at6 = state[0, 1]
    np.testing.assert_allclose(last, state[0], atol=1e-5)
    np.testing.assert_allclose(last[1], at6, atol=1e-5)   # padded: unmoved


@pytest.mark.parametrize("G,S,N,C", [(2, 48, 16, 1024), (1, 256, 8, 2048),
                                     (2, 512, 16, 1024)])
def test_pallas_s6_scan_against_the_plain_scan(G, S, N, C):
    """Chunks of 16 and of 256 tokens, one chunk and several, one channel
    block and two; a row padded from a third of its length on."""
    rng = np.random.default_rng(7)
    x, b, c = _rand(rng, G, S, C), _rand(rng, G, S, N), _rand(rng, G, S, N)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, (G, S, C)), jnp.float32)
    slen = jnp.asarray([S, S // 3][:G])
    dt = jnp.where((jnp.arange(S) < slen[:, None])[..., None], dt, 0.0)
    a = -jnp.asarray(rng.uniform(1, 16, (N, C)), jnp.float32)
    assert ssm.s6_scan_supported(x, a)
    y, last = ssm.s6_scan(x, dt, a, b, c, interpret=True)
    yr, lr = ssm.s6_scan_ref(x, dt, a, b, c)
    np.testing.assert_allclose(y, yr, atol=2e-6)
    np.testing.assert_allclose(last, lr, atol=2e-6)
    assert not ssm.s6_scan_supported(x[:, :, :128], a[:, :128])


def test_the_dispatchers_count_their_fallbacks_off_the_chip(model):
    from paddle_tpu import kernels

    c, params = model
    # a dispatcher counts when it is TRACED, so the count is of what this
    # process has not traced yet: every cache a trace can be taken from is
    # emptied (the inner jits of ``paged``, a scan body's jaxpr, what an
    # earlier test of this worker jitted at these shapes), and the
    # dispatchers are the off-chip ones, not an interpret-mode
    # registration an earlier file left behind
    jax.clear_caches()
    kernels.register()
    kernels.reset_dispatch_stats()
    P.forward(params, jnp.zeros((1, 8), jnp.int32), c)  # its three scans
    assert kernels.dispatch_stats()["ssm_fallback"] == 3
    assert sum(kernels.dispatch_stats().values()) == 3
    jax.clear_caches()
    kernels.reset_dispatch_stats()
    cache = make_cache(c, 8, 1)
    rows = jnp.arange(8, dtype=jnp.int32)[None]
    cache, _ = cache_prefill(P, params, jnp.zeros((1, 8), jnp.int32), c,
                             cache, rows[:, :2], jnp.asarray([8]),
                             jnp.asarray([0]))
    cache_decode_step(P, params, cache, rows, jnp.asarray([9]),
                      jnp.asarray([1]), c, jnp.asarray([0]))
    got = kernels.dispatch_stats()
    # traced once a segment: a window layer and the full layer in prefill
    # and the two Mamba bodies' scans (the cross layer reads the prompt's
    # keys in hand: no kernel); in decode the same two bodies' updates, the
    # ring's kernel and the pool's (one trace for the full and the cross
    # layer: the same shapes behind one inner jit)
    assert got["flash_fallback"] == 2 and got["ssm_fallback"] == 4
    assert got["paged_fallback"] == 2 and got["flash"] == got["paged"] == 0
