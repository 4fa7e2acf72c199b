"""Falcon-H1 on the serving path at a small size on the CPU: the mixer's
two forms against each other and against a token-by-token recurrence, the
in-place state-update kernel in interpret mode, a recurrent state a
sequence beside the paged cache (rows owned from admission to retire or
preemption, never copied, untouched by padding, dummies and idle slots),
the engine's tokens against an unbatched greedy pass, and the
combinations that are refused because a state has no snapshot or
rollback yet."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.core import enforce as E
from paddle_tpu.inference import Request, ServingEngine
from paddle_tpu.inference import paged
from paddle_tpu.kernels import ssm
from paddle_tpu.models import falcon_h1 as F

CFG = F.falcon_h1_tiny()


@pytest.fixture(scope="module")
def params():
    return F.init_params(CFG, jax.random.PRNGKey(0))


def sequential(x, dt, a, b, c):
    """The recurrence a token at a time in float64, one sequence:
    x [S, H, P], dt [S, H], a [H], b and c [S, R, N]."""
    S, H, P = x.shape
    R, N = b.shape[1:]
    state, ys = np.zeros((H, N, P)), []
    for t in range(S):
        bh, ch = (np.repeat(v[t], H // R, 0) for v in (b, c))
        state = np.exp(dt[t] * a)[:, None, None] * state \
            + bh[:, :, None] * (dt[t][:, None] * x[t])[:, None, :]
        ys.append(np.einsum("hnp,hn->hp", state, ch))
    return np.stack(ys), state


# -- the scan and the kernel ------------------------------------------------

@pytest.mark.parametrize("chunk", [8, 12, 64], ids=lambda c: f"chunk{c}")
def test_chunked_scan_equals_the_sequential_recurrence(chunk):
    """37 tokens: no multiple of 8 or 12, and shorter than 64."""
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    G, S, H, P, R, N = 2, 37, 4, 8, 2, 6
    x = jax.random.normal(k[0], (G, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (G, S, H))) * 0.3
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    b = jax.random.normal(k[3], (G, S, R, N))
    c = jax.random.normal(k[4], (G, S, R, N))
    y, last = ssm.ssd_chunked_scan(x, dt, a, b, c, chunk)
    for g in range(G):
        ys, state = sequential(*(np.asarray(t[g], np.float64)
                                 for t in (x, dt)), np.asarray(a, np.float64),
                               *(np.asarray(t[g], np.float64)
                                 for t in (b, c)))
        np.testing.assert_allclose(y[g], ys, atol=2e-5)
        np.testing.assert_allclose(last[g], state, atol=2e-5)


def test_a_zero_step_neither_decays_nor_adds():
    """Padding is dt = 0: the state after 20 real tokens and 12 padded
    ones is the state after the 20."""
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(k[0], (1, 32, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, 32, 4)))
    a = -jnp.exp(jax.random.normal(k[2], (4,)))
    b, c = (jax.random.normal(kk, (1, 32, 2, 6)) for kk in k[3:])
    _, short = ssm.ssd_chunked_scan(x[:, :20], dt[:, :20], a, b[:, :20],
                                    c[:, :20], 8)
    _, padded = ssm.ssd_chunked_scan(x, dt.at[:, 20:].set(0.0), a, b, c, 8)
    np.testing.assert_allclose(padded, short, atol=1e-6)


def update_inputs(B=3, L=2, R=5, H=4, N=16, P=128, G=2):
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    return (jax.random.normal(k[0], (L, R, H, N, P)),
            jax.random.uniform(k[1], (B, H)), jax.random.normal(k[2], (B, H, P)),
            jax.random.normal(k[3], (B, G, N)), jax.random.normal(k[4], (B, G, N)))


def test_state_update_kernel_matches_the_plain_update_and_touches_its_rows_only():
    state, decay, dtx, b, c = update_inputs()
    rows = jnp.array([2, 4, 0], jnp.int32)
    want_s, want_y = ssm.ssm_state_update_ref(state, 1, rows, decay, dtx, b, c)
    got_s, got_y = jax.jit(lambda *a: ssm.ssm_state_update(
        *a, interpret=True))(state, 1, rows, decay, dtx, b, c)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)
    np.testing.assert_allclose(got_y, want_y, atol=1e-5)
    # the other layer, and the rows no slot names, are as they were
    np.testing.assert_array_equal(got_s[0], state[0])
    np.testing.assert_array_equal(got_s[1, [1, 3]], state[1, [1, 3]])
    assert not np.allclose(got_s[1, 2], state[1, 2])


def test_state_update_heads_split_into_blocks_inside_a_group(monkeypatch):
    """A block smaller than a group's heads: the grid's third axis."""
    state, decay, dtx, b, c = update_inputs(H=8, G=2)
    monkeypatch.setattr(ssm, "_BLOCK_BYTES", 2 * 16 * 128 * 4)   # 2 heads
    assert ssm._heads_per_block(4, 16, 128) == 2
    rows = jnp.array([1, 0, 3], jnp.int32)
    want_s, want_y = ssm.ssm_state_update_ref(state, 0, rows, decay, dtx, b, c)
    got_s, got_y = ssm.ssm_state_update(state, 0, rows, decay, dtx, b, c,
                                        interpret=True)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)
    np.testing.assert_allclose(got_y, want_y, atol=1e-5)


def test_state_update_is_dispatched_and_counted():
    state, decay, dtx, b, c = update_inputs()
    assert ssm.supported(state, dtx, b)
    assert not ssm.supported(state.astype(jnp.bfloat16), dtx, b)
    assert not ssm.supported(state[..., :64], dtx[..., :64], b)
    kernels.reset_dispatch_stats()
    kernels.dispatched_ssm_update(state, 0, jnp.arange(3), decay, dtx, b, c)
    stats = kernels.dispatch_stats()
    assert stats["ssm_fallback"] == 1 and stats["ssm"] == 0   # off the TPU
    kernels.reset_dispatch_stats()


# -- the mixer's two forms ----------------------------------------------------

def test_prefill_then_one_decode_step_equals_a_longer_prefill(params):
    """``mixer_prefill`` over 21 tokens and ``mixer_decode`` of the 22nd
    against the state it left, against ``mixer_prefill`` over 22."""
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 22, CFG.hidden_size))
    full, want = F.mixer_prefill(h, lp, CFG, jnp.array([22, 22]))
    m, st = F.mixer_prefill(h[:, :21], lp, CFG, jnp.array([21, 21]))
    np.testing.assert_allclose(m, full[:, :21], atol=1e-5)
    # rows 1 and 3 of a 4-row state (the 5th is nobody's), layer 0 of 1
    rows = jnp.array([1, 3])
    state = {k: jnp.zeros((1, 5) + v.shape[1:], v.dtype).at[0, rows].set(v)
             for k, v in st.items()}
    step, state = F.mixer_decode(h[:, 21:], lp, CFG, state, 0, rows)
    np.testing.assert_allclose(step[:, 0], full[:, 21], atol=1e-5)
    for k in want:
        np.testing.assert_allclose(state[k][0, rows], want[k], atol=1e-5)
        assert not np.asarray(state[k][0, [0, 2, 4]]).any()


def test_config_states_its_head_size_and_refuses_another_mixer():
    assert CFG.head_dim == 8 != CFG.hidden_size // CFG.num_attention_heads
    assert CFG.conv_dim == 64 + 2 * 2 * 8
    big = F.FalconH1Config(rope_theta=100000000000)
    assert isinstance(big.rope_theta, float) and big.head_dim == 128
    with pytest.raises(E.UnimplementedError):
        F.falcon_h1_tiny(mamba_norm_before_gate=True)
    with pytest.raises(E.EnforceError):
        F.falcon_h1_tiny(mamba_n_heads=3)


# -- the cache: pages a token, a state a sequence ------------------------------

def fresh_cache(rows=3, pages=12, ps=8):
    return paged.init_pool(CFG, pages, ps,
                           state_shapes=F.state_shapes(CFG), state_rows=rows)


def test_prefill_writes_the_state_at_slen_and_padding_and_dummies_nowhere(
        params):
    """A group of 4 in a bucket of 32: prompts of 13 and 27 tokens, one of
    32, one dummy. Each real row's state is what an unpadded prefill of
    its prompt alone leaves; the row no request was given stays zero."""
    ps, S = 8, 32
    ids = np.random.default_rng(0).integers(0, 256, (4, S)).astype(np.int32)
    slen = np.array([13, 27, 32, 1], np.int32)
    page_rows = np.full((4, S // ps), 12, np.int32)          # sentinel
    for g, n in enumerate((2, 4, 4)):
        page_rows[g, :n] = np.arange(4 * g, 4 * g + n)
    srows = jnp.array([2, 0, 3, 4])          # 4 = nobody's: the dummy
    cache, _ = paged.cache_prefill(F, params, jnp.asarray(ids), CFG,
                                   fresh_cache(rows=4), jnp.asarray(page_rows),
                                   jnp.asarray(slen), srows)
    for g, row in ((0, 2), (1, 0), (2, 3)):
        n = int(slen[g])
        pad = -n % ps
        alone, _ = paged.cache_prefill(
            F, params, jnp.asarray(np.pad(ids[g:g + 1, :n], ((0, 0), (0, pad)))),
            CFG, fresh_cache(rows=1), jnp.arange((n + pad) // ps)[None],
            jnp.array([n]), jnp.array([0]))
        for k in ("ssm", "conv"):
            np.testing.assert_allclose(cache["state"][k][:, row],
                                       alone["state"][k][:, 0], atol=1e-5)
    assert not np.asarray(cache["state"]["ssm"][:, 1]).any()


def test_a_wide_group_goes_through_in_passes(params, monkeypatch):
    """A group wider than the rows a pass is prefilled in equal passes
    inside one program: the same cache and logits as in one."""
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, (4, 16)),
                      jnp.int32)
    args = (jnp.arange(8, dtype=jnp.int32).reshape(4, 2),
            jnp.array([16, 9, 12, 3]), jnp.array([1, 3, 0, 2]))
    whole, logits = paged.cache_prefill(F, params, ids, CFG,
                                        fresh_cache(rows=4), *args)
    monkeypatch.setattr(paged, "_PREFILL_PASS_ROWS", 2)
    traced = jax.make_jaxpr(lambda c: paged.cache_prefill(
        F, params, ids, CFG, c, *args))(fresh_cache(rows=4))
    assert "length=2" in str(traced)            # two passes of two rows
    split, logits2 = paged.cache_prefill(F, params, ids, CFG,
                                         fresh_cache(rows=4), *args)
    np.testing.assert_allclose(logits2, logits, atol=1e-6)
    for a, b in zip(jax.tree.leaves(split), jax.tree.leaves(whole)):
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("group, rows", [
    (1, 1), (2, 2), (4, 4), (8, 8), (16, 8), (96, 8), (112, 8), (128, 8),
    (7, 7), (13, 1), (14, 7)])
def test_rows_a_pass_at_the_default(group, rows):
    """The scheduler's own groups (powers of two up to 8) are one pass; a
    warm-up's group of every slot is passes of 8, whatever the state's
    size (the rule once divided by a row's bytes and gave 1)."""
    assert paged._rows_a_pass(group) == rows


def test_an_idle_slot_leaves_its_row_untouched(params):
    """Decode over 3 slots, the middle one idle (length 0): its row, which
    a coasting sequence still owns, is as it was."""
    cache = fresh_cache()
    k = jax.random.split(jax.random.PRNGKey(5), 2)
    cache["state"] = {
        "ssm": jax.random.normal(k[0], cache["state"]["ssm"].shape),
        "conv": jax.random.normal(k[1], cache["state"]["conv"].shape)}
    before = jax.tree.map(np.asarray, cache["state"])
    bt = jnp.asarray(np.arange(12, dtype=np.int32).reshape(3, 4))
    cache, _ = paged.cache_decode_step(
        F, params, cache, bt, jnp.array([6, 0, 9]), jnp.array([5, 7, 11]),
        CFG, jnp.array([0, 1, 2]))
    for name in ("ssm", "conv"):
        after = np.asarray(cache["state"][name])
        np.testing.assert_array_equal(after[:, 1], before[name][:, 1])
        assert not np.allclose(after[:, 0], before[name][:, 0])
        assert not np.allclose(after[:, 2], before[name][:, 2])


def test_a_sequence_owns_a_row_from_alloc_to_free():
    a = paged.PageAllocator(16, 8, 4, state_rows=2)
    assert a.alloc(10, 8) is not None and a.alloc(11, 8) is not None
    assert {a.state_row(10), a.state_row(11)} == {0, 1} and a.used_rows == 2
    a.check_invariants()
    # no row left: out of memory as for pages, nothing taken
    free = a.free_pages
    assert a.alloc(12, 8) is None and a.free_pages == free
    a.free(10)
    assert a.used_rows == 1 and a.alloc(12, 8) is not None
    assert a.rows_assigned == 3
    a.check_invariants()
    a._free_rows.append(a.state_row(12))          # a row both held and free
    with pytest.raises(AssertionError, match="state rows drift"):
        a.check_invariants()
    # without a state nothing changes
    plain = paged.PageAllocator(16, 8, 4)
    plain.alloc(1, 8)
    assert plain.used_rows == 0 and "row" not in plain._seqs[1]


# -- through the engine ---------------------------------------------------------

def greedy(params, prompt, n):
    """An unbatched greedy pass: the whole sequence again for every token."""
    fw = jax.jit(lambda p, i: F.forward(p, i, CFG))
    ids, out = list(prompt), []
    for _ in range(n):
        tok = int(jnp.argmax(fw(params, jnp.asarray([ids]))[0, -1]))
        out.append(tok)
        ids.append(tok)
    return out


SCENES = {
    # finish at different times: compaction moves slots, no state moves
    "compact": dict(slots=4, pages=40, sizes=[(13, 3), (21, 12), (8, 5),
                                              (30, 9)]),
    # more requests than slots: rows are given back and handed out again
    "retire_readmit": dict(slots=2, pages=24, sizes=[(9, 4), (17, 7), (12, 3),
                                                     (25, 6), (8, 5), (14, 4)]),
    # a pool too small for all to finish: the youngest is preempted, its
    # row released, and prefill rebuilds its state when it is admitted again
    "preempt": dict(slots=3, pages=9, sizes=[(14, 18), (15, 18), (13, 18)]),
}


@pytest.mark.parametrize("scene", list(SCENES))
def test_engine_tokens_equal_an_unbatched_greedy_pass(params, scene):
    sc = SCENES[scene]
    rng = np.random.default_rng(7)
    eng = ServingEngine(F, params, CFG, num_slots=sc["slots"], max_len=64,
                        page_size=8, num_pages=sc["pages"])
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, p, dtype=np.int32),
                    max_new_tokens=n) for i, (p, n) in enumerate(sc["sizes"])]
    for r in reqs:
        eng.submit(r)
    moved = 0
    while True:
        before = [s and s.req.rid for s in eng.slots]
        busy = eng.step()
        moved += any(b is not None and b in [s and s.req.rid
                                             for s in eng.slots]
                     and [s and s.req.rid for s in eng.slots].index(b) != i
                     for i, b in enumerate(before))
        eng.cache.alloc.check_invariants()
        if not busy:
            break
    for r in reqs:
        assert eng.outputs[r.rid].tokens.tolist() \
            == greedy(params, r.prompt, r.max_new_tokens), r.rid
    st = eng.stats
    assert st.state_rows_in_use == 0 or eng.cache.alloc.used_rows == 0
    assert st.peak_state_rows_in_use <= sc["slots"]
    assert st.state_rows_assigned == st.admitted >= len(reqs)
    if scene == "compact":
        assert moved, "no slot ever moved: the scene tests nothing"
    if scene == "preempt":
        assert st.preempted >= 1
        assert st.state_rows_assigned == len(reqs) + st.preempted
    if scene == "retire_readmit":
        assert st.state_rows_assigned == 6 > st.peak_state_rows_in_use == 2


@pytest.mark.parametrize("flag,missing", [
    ("prefix_cache", "snapshot"), ("spec_decode", "rollback"),
    ("kv_quant", "quantized form")])
def test_what_shares_or_rewinds_pages_is_refused_at_construction(
        params, flag, missing):
    with pytest.raises(E.UnimplementedError, match=missing):
        ServingEngine(F, params, CFG, num_slots=2, max_len=64, page_size=8,
                      **{flag: True})


def test_fork_and_the_page_only_programs_refuse_a_state(params):
    a = paged.PageAllocator(16, 8, 4, state_rows=2)
    a.alloc(1, 8)
    with pytest.raises(E.UnimplementedError, match="no state snapshot"):
        a.fork(1, 2)
    with pytest.raises(E.UnimplementedError, match="no state snapshot"):
        a.alloc_prefix(3, [0], 16)
    cache, i32 = fresh_cache(), jnp.int32
    with pytest.raises(E.UnimplementedError, match="snapshot"):
        paged.cache_prefill_shared(
            F, params, jnp.zeros((1, 8), i32), CFG, cache,
            jnp.zeros((1, 1), i32), jnp.ones((1,), i32),
            jnp.zeros((1, 1), i32))
    with pytest.raises(E.UnimplementedError, match="rollback"):
        paged.cache_verify_window(
            F, params, jnp.zeros((1, 4), i32), CFG, cache,
            jnp.zeros((1, 4), i32), jnp.ones((1,), i32),
            jnp.ones((1,), bool))


def test_the_other_families_keep_no_state_and_their_cache_two_leaves():
    from paddle_tpu.models import llama as L

    cfg = L.llama_tiny()
    eng = ServingEngine(L, L.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                        num_slots=2, max_len=32, page_size=8)
    assert set(eng.cache.pool) == {"k", "v"} and not eng._recurrent
    assert eng.cache.alloc.state_rows == 0
    eng.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=3))
    while eng.step():
        pass
    assert set(eng.cache.pool) == {"k", "v"} and "rows" not in eng._dev
    assert eng.stats.state_rows_assigned == 0


# -- what the state's type costs -------------------------------------------------

def loud(params, by=8.0):
    """The matrices scaled so that a pre-activation at hidden 64 spreads
    as at hidden 5,120 (64 x (8 x .02)^2 against 5120 x .02^2): at 0.02
    the mixer's inputs are small and its state adds nothing visible."""
    layers = {k: (v * by).astype(v.dtype)
              if v.ndim == 3 and k != "conv_w" else v
              for k, v in params["layers"].items()}
    return dict(params, layers=layers)


def test_bfloat16_state_drifts_from_float32_over_hundreds_of_steps(params):
    """The same 300 decode steps (one sequence, tokens fixed) against a
    state stored in float32 and in bfloat16: rounding the accumulator once
    a token moves the logits thousands of times further than the float32
    program stands from its reference (3e-7), and the rounded state itself
    is off by a few parts in a thousand throughout."""
    ps, steps, weights = 8, 300, loud(params)
    toks = np.random.default_rng(3).integers(0, 256, steps).astype(np.int32)
    pages = steps // ps + 2
    bt = jnp.arange(pages, dtype=jnp.int32)[None]
    step = jax.jit(lambda c, n, t: paged.cache_decode_step(
        F, weights, c, bt, n, t, CFG, jnp.array([0])))

    def run(dtype):
        cache = paged.init_pool(CFG, pages, ps,
                                state_shapes=F.state_shapes(CFG), state_rows=1)
        cache["state"]["ssm"] = cache["state"]["ssm"].astype(dtype)
        out = []
        for t in range(steps):
            cache, logits = step(cache, jnp.array([t + 1]),
                                 jnp.asarray(toks[t:t + 1]))
            out.append(np.asarray(logits[0]))
        assert cache["state"]["ssm"].dtype == dtype
        return np.stack(out), np.asarray(cache["state"]["ssm"], np.float32)

    (exact, state), (rounded, state16) = run(jnp.float32), run(jnp.bfloat16)

    def rms(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    assert rms(rounded[250:], exact[250:]) > 1e-3
    assert rms(rounded[250:], exact[250:]) > rms(rounded[:5], exact[:5])
    assert 1e-3 < rms(state16[:, 0], state[:, 0]) < 2e-2
