"""Paged KV cache + ragged paged attention + continuous-batching engine
(inference/paged.py, inference/engine.py, kernels/paged_attention.py).

The load-bearing contract: the paged decode path must produce EXACTLY
the ring-buffer path's tokens (greedy and fixed-seed sampling, bf16 and
weight-only int8, llama and MoE) while allocating KV at page
granularity — plus allocator refcount invariants (nothing leaks, OOM is
admission refusal, fork is copy-on-write) and scheduler behavior under
a randomized arrival/length trace.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401  (backend/platform init)
from paddle_tpu.core import enforce as E
from paddle_tpu.inference import PagedKVCache, Request, ServingEngine
from paddle_tpu.inference.paged import PageAllocator
from paddle_tpu.kernels import paged_attention as PA
from paddle_tpu.models import llama as L
from paddle_tpu.models import moe as M

pytestmark = pytest.mark.serving


def _prompts(rng, vocab, lens):
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lens]


def _ring_generate(family, params, cfg, prompt, n, **kw):
    return np.asarray(family.generate(
        params, jnp.asarray(prompt)[None, :], cfg, max_new_tokens=n,
        **kw))[0]


class TestKernel:
    """ragged_paged_attention (interpret mode) vs the jnp gather ref."""

    def _case(self, dtype, B=3, nh=4, kv=2, hd=64, ps=8, P=16, maxp=4,
              lengths=(13, 0, 25), seed=0):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(B, nh, hd)), dtype)
        kp = jnp.asarray(rng.normal(size=(P, kv, ps, hd)), dtype)
        vp = jnp.asarray(rng.normal(size=(P, kv, ps, hd)), dtype)
        bt = jnp.asarray(rng.permutation(P)[:B * maxp].reshape(B, maxp),
                         jnp.int32)
        ln = jnp.asarray(lengths, jnp.int32)
        return q, kp, vp, bt, ln

    def test_kernel_matches_ref_f32(self):
        q, kp, vp, bt, ln = self._case(jnp.float32)
        got = PA.ragged_paged_attention(q, kp, vp, bt, ln, interpret=True)
        want = PA.paged_attention_ref(q, kp, vp, bt, ln)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_kernel_matches_ref_bf16_gqa(self):
        q, kp, vp, bt, ln = self._case(jnp.bfloat16, nh=8, kv=2, ps=16,
                                       lengths=(31, 7, 64))
        got = PA.ragged_paged_attention(q, kp, vp, bt, ln, interpret=True)
        want = PA.paged_attention_ref(q, kp, vp, bt, ln)
        np.testing.assert_allclose(
            np.asarray(got).astype(np.float32),
            np.asarray(want).astype(np.float32), rtol=2e-2, atol=2e-2)

    def test_empty_sequence_yields_zero_row_not_nan(self):
        q, kp, vp, bt, _ = self._case(jnp.float32)
        ln = jnp.zeros((3,), jnp.int32)
        for fn in (lambda: PA.ragged_paged_attention(
                q, kp, vp, bt, ln, interpret=True),
                lambda: PA.paged_attention_ref(q, kp, vp, bt, ln)):
            out = np.asarray(fn())
            assert np.isfinite(out).all()
            np.testing.assert_array_equal(out, np.zeros_like(out))

    def test_ref_matches_ring_attention_math(self):
        """Paged gather attention == the ring _attn_over_cache on the
        same KV laid out contiguously (pages = consecutive chunks)."""
        rng = np.random.default_rng(3)
        B, nh, kv, hd, ps, maxp = 2, 4, 2, 32, 4, 3
        Mlen = maxp * ps
        q = jnp.asarray(rng.normal(size=(B, 1, nh, hd)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(B, Mlen, kv, hd)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(B, Mlen, kv, hd)), jnp.float32)
        pos = 9                                   # ring: 0..pos valid
        ring = L._attn_over_cache(q, kc, vc, jnp.asarray(pos))
        # re-page the same cache: page p of seq b = rows [p*ps, (p+1)*ps)
        kp = jnp.moveaxis(kc.reshape(B * maxp, ps, kv, hd), 2, 1)
        vp = jnp.moveaxis(vc.reshape(B * maxp, ps, kv, hd), 2, 1)
        bt = jnp.arange(B * maxp, dtype=jnp.int32).reshape(B, maxp)
        ln = jnp.full((B,), pos + 1, jnp.int32)
        paged = PA.paged_attention_ref(q[:, 0], kp, vp, bt, ln)
        np.testing.assert_allclose(np.asarray(ring)[:, 0],
                                   np.asarray(paged).reshape(B, -1),
                                   rtol=1e-5, atol=1e-5)

    def test_supported_guard(self):
        q, kp, _, bt, _ = self._case(jnp.float32, hd=128)
        assert PA.supported(q, kp, bt)
        assert not PA.supported(q.astype(jnp.int8), kp, bt)
        assert not PA.supported(q[:, :3], kp, bt)      # nh % kv != 0
        # a page leaves HBM whole: its rows must fill the 128 lanes
        q, kp, _, bt, _ = self._case(jnp.float32, hd=64)
        assert not PA.supported(q, kp, bt)


_BLOCK = 64     # tokens a block in TestKernelBlocks: 4 pages of 16, 2 of 32
_MAXP = {16: 10, 32: 5}    # a table of 160 tokens: two blocks and a half


def _paged_case(arm, g, hd, ps, lengths, seed=0, kv=2):
    """q, pools, table, lengths and the scale keywords of one case; every
    sequence owns exactly the pages its length needs, from a shuffled
    pool with pages to spare, and the table's dead entries name one of
    the spare pages."""
    rng = np.random.default_rng(seed)
    B, maxp = len(lengths), _MAXP[ps]
    P = B * maxp + 7
    q = jnp.asarray(rng.normal(size=(B, g * kv, hd)), jnp.bfloat16)
    kw = {}
    if arm == "int8":
        kp, vp = (jnp.asarray(rng.integers(-127, 128, (P, kv, ps, hd)),
                              jnp.int8) for _ in range(2))
        kw = {n: jnp.asarray(rng.uniform(0.004, 0.02, (P, kv)), jnp.float32)
              for n in ("k_scales", "v_scales")}
    else:
        kp, vp = (jnp.asarray(rng.normal(size=(P, kv, ps, hd)),
                              jnp.bfloat16) for _ in range(2))
    perm, at = rng.permutation(P), 0
    bt = np.full((B, maxp), perm[-1], np.int32)
    for b, n in enumerate(lengths):
        need = -(-n // ps)
        bt[b, :need] = perm[at:at + need]
        at += need
    owned = np.zeros(P, bool)
    owned[perm[:at]] = True
    return (q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths, jnp.int32),
            kw, owned)


def _f32(x):
    return np.asarray(x).astype(np.float32)


class TestKernelBlocks:
    """The kernel (interpret mode) against the gather reference where its
    loop turns: no trip, one token, a page, a block, a block and a token,
    the whole table; and that nothing past a sequence's live pages is
    ever read."""

    # 0 | 1 | one page | one block | one block + 1 | the full table
    LENGTHS = {16: (0, 1, 16, _BLOCK, _BLOCK + 1, 160),
               32: (0, 1, 32, _BLOCK, _BLOCK + 1, 160)}

    @pytest.fixture(autouse=True)
    def _small_blocks(self, monkeypatch):
        monkeypatch.setattr(PA, "_BLOCK_TOKENS", _BLOCK)

    def _check(self, case):
        q, kp, vp, bt, ln, kw, _ = case
        got = PA.ragged_paged_attention(q, kp, vp, bt, ln, interpret=True,
                                        **kw)
        want = PA.paged_attention_ref(q, kp, vp, bt, ln, **kw)
        np.testing.assert_allclose(_f32(got), _f32(want), rtol=2e-2,
                                   atol=2e-2)
        empty = np.asarray(ln) == 0
        np.testing.assert_array_equal(_f32(got)[empty], 0.0)
        return got

    @pytest.mark.parametrize("arm,ps", [("bf16", 16), ("bf16", 32),
                                        ("int8", 32)])
    @pytest.mark.parametrize("hd", [64, 128])
    @pytest.mark.parametrize("g", [1, 4, 8])
    def test_every_turn_of_the_loop(self, arm, ps, hd, g):
        """All six lengths in one call, so a sequence's last block hands
        over to the next sequence's first in every way it can."""
        self._check(_paged_case(arm, g, hd, ps, self.LENGTHS[ps]))

    @pytest.mark.parametrize("arm,ps", [("bf16", 16), ("int8", 32)])
    @pytest.mark.parametrize("which", range(6), ids=[
        "empty", "one-token", "one-page", "one-block", "one-block-plus-1",
        "full-table"])
    def test_one_length_between_empty_slots(self, arm, ps, which):
        n = self.LENGTHS[ps][which]
        self._check(_paged_case(arm, 4, 128, ps, (0, n, 0), seed=which))

    @pytest.mark.parametrize("arm,ps", [("bf16", 16), ("int8", 32)])
    def test_at_the_default_block(self, arm, ps, monkeypatch):
        monkeypatch.undo()
        self._check(_paged_case(arm, 4, 128, ps, (160, 0, 33, 1)))

    @pytest.mark.parametrize("arm,ps", [("bf16", 16), ("int8", 32)])
    def test_dead_table_entries_are_never_indices(self, arm, ps):
        """Entries past a sequence's live pages hold -1 and P + 5: the
        result is the clean table's, bit for bit."""
        case = _paged_case(arm, 4, 128, ps, self.LENGTHS[ps], seed=5)
        q, kp, vp, bt, ln, kw, _ = case
        clean = self._check(case)
        live = (np.arange(bt.shape[1])[None, :] * ps
                < np.asarray(ln)[:, None])
        junk = np.where(np.arange(bt.size).reshape(bt.shape) % 2,
                        -1, kp.shape[0] + 5)
        dirty = PA.ragged_paged_attention(
            q, kp, vp, jnp.asarray(np.where(live, bt, junk), jnp.int32),
            ln, interpret=True, **kw)
        np.testing.assert_array_equal(_f32(dirty), _f32(clean))

    @pytest.mark.parametrize("arm,ps", [("bf16", 16), ("int8", 32)])
    def test_pages_no_sequence_owns_are_never_read(self, arm, ps):
        """Every page no sequence owns is NaN (for int8 codes: its
        scales), and the dead entries name such pages: the output is
        finite and the clean pool's."""
        case = _paged_case(arm, 4, 128, ps, self.LENGTHS[ps], seed=6)
        q, kp, vp, bt, ln, kw, owned = case
        clean = self._check(case)
        assert not owned[np.asarray(bt)[0, -1]]    # a dead entry's page
        if arm == "int8":
            kw = {n: jnp.where(jnp.asarray(owned)[:, None], x, jnp.nan)
                  for n, x in kw.items()}
        else:
            own = jnp.asarray(owned)[:, None, None, None]
            kp, vp = jnp.where(own, kp, jnp.nan), jnp.where(own, vp, jnp.nan)
        got = PA.ragged_paged_attention(q, kp, vp, bt, ln, interpret=True,
                                        **kw)
        assert np.isfinite(_f32(got)).all()
        np.testing.assert_array_equal(_f32(got), _f32(clean))


_LAYERS = 3
_LAYER_LENGTHS = (0, 33, 160, 1)    # an empty slot; a token past a page's end


@functools.lru_cache(maxsize=None)
def _layered_case(arm, ps):
    """``_paged_case`` with pools (and scale planes) of ``_LAYERS`` layers,
    every layer its own random pages under ONE table."""
    cases = [_paged_case(arm, 4, 128, ps, _LAYER_LENGTHS, seed=s)
             for s in range(_LAYERS)]
    q, _, _, bt, ln, _, _ = cases[0]
    k5, v5 = (jnp.stack([c[i] for c in cases]) for i in (1, 2))
    kw5 = {n: jnp.stack([c[5][n] for c in cases]) for n in cases[0][5]}
    return q, k5, v5, bt, ln, kw5


class TestKernelLayers:
    """The pool with its layer axis and ``layer`` a traced scalar, against
    the same call on that layer cut out: the kernel (interpret mode) and the
    gather reference, bf16 and int8 pages."""

    IMPLS = {"kernel": functools.partial(PA.ragged_paged_attention,
                                         interpret=True),
             "ref": PA.paged_attention_ref}

    @pytest.mark.parametrize("layer", range(_LAYERS))
    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("arm,ps", [("bf16", 16), ("int8", 32)])
    def test_a_layer_of_the_whole_pool_is_that_layer_alone(self, arm, ps,
                                                           impl, layer):
        fn = self.IMPLS[impl]
        q, k5, v5, bt, ln, kw5 = _layered_case(arm, ps)
        whole = jax.jit(lambda l: fn(q, k5, v5, bt, ln, layer=l, **kw5))(
            jnp.int32(layer))
        cut = jax.jit(lambda k, v, kw: fn(q, k, v, bt, ln, **kw))(
            k5[layer], v5[layer], {n: x[layer] for n, x in kw5.items()})
        np.testing.assert_array_equal(_f32(whole), _f32(cut))
        np.testing.assert_array_equal(_f32(whole)[0], 0.0)   # the empty slot
        # and it is not another layer's answer
        other = jax.jit(lambda l: fn(q, k5, v5, bt, ln, layer=l, **kw5))(
            jnp.int32((layer + 1) % _LAYERS))
        assert not np.allclose(_f32(whole)[1:], _f32(other)[1:], atol=1e-3)

    def test_supported_judges_the_last_four_dimensions(self):
        q, k5, _, bt, _, _ = _layered_case("bf16", 16)
        assert PA.supported(q, k5, bt) and PA.supported(q, k5[0], bt)
        assert not PA.supported(q, k5[None], bt)
        q8, c5, _, bt8, _, _ = _layered_case("int8", 32)
        assert PA.supported(q8, c5, bt8, quant=True)
        assert not PA.supported(q8, c5, bt8)         # codes without scales


@functools.lru_cache(maxsize=None)
def _one_decode_step(kv_quant):
    """A pool of random pages before and after ONE decode step over three
    slots, the middle one idle: (before, after, pages written, offsets)."""
    from paddle_tpu.inference.paged import cache_decode_step, init_pool
    cfg = L.llama_tiny(num_hidden_layers=3)
    params = L.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(4)
    pool = init_pool(cfg, 12, 8, kv_quant=kv_quant)

    def fill(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        if a.ndim == 3:                                # a scale plane
            return jnp.asarray(rng.uniform(0.004, 0.02, a.shape), a.dtype)
        return jnp.asarray(rng.normal(size=a.shape), a.dtype)

    pool = jax.tree.map(fill, pool)
    before = jax.tree.map(np.asarray, pool)
    bt = jnp.asarray(rng.permutation(12).reshape(3, 4), jnp.int32)
    lengths = np.array([6, 0, 19])
    after, _ = jax.jit(lambda c: cache_decode_step(
        L, params, c, bt, jnp.asarray(lengths), jnp.array([5, 7, 11]),
        cfg))(pool)
    pos = lengths[[0, 2]] - 1
    pages = np.asarray(bt)[[0, 2], pos // 8]
    return before, jax.tree.map(np.asarray, after), pages, pos % 8


class TestDecodeStepWrite:
    """One decode step appends layer l's token to layer l's page, and every
    other element of the pool (the carry the layer scan updates in place)
    is bit-equal to what it was."""

    @pytest.mark.parametrize("layer", range(3))
    @pytest.mark.parametrize("half", ["k", "v"])
    def test_bf16_pool_changes_at_the_token_alone(self, half, layer):
        before, after, pages, off = _one_decode_step(False)
        b, a = before[half], after[half]
        touched = np.zeros(b.shape, bool)
        touched[layer, pages, :, off] = True
        diff = (a != b)[layer]
        np.testing.assert_array_equal(diff & ~touched[layer], False)
        assert diff[pages, :, off].any(axis=-1).all()   # each row landed
        # not the row another layer wrote
        nxt = (layer + 1) % 3
        assert not np.array_equal(a[layer][pages, :, off],
                                  a[nxt][pages, :, off])

    @pytest.mark.parametrize("layer", range(3))
    @pytest.mark.parametrize("half", ["k", "v"])
    def test_int8_pool_changes_at_the_pages_alone(self, half, layer):
        """The int8 arm rewrites a touched page whole under its fresh
        scale: codes and scale row of (layer, page), nothing else."""
        before, after, pages, off = _one_decode_step(True)
        for leaf in ("q", "s"):
            b, a = before[half][leaf], after[half][leaf]
            touched = np.zeros(b.shape[1], bool)
            touched[pages] = True
            diff = (a != b)[layer].reshape(b.shape[1], -1).any(axis=1)
            np.testing.assert_array_equal(diff & ~touched, False)
            # (a page's absmax, so its scale, may come out as it was)
            assert leaf == "s" or diff[pages].all()


class TestAllocator:
    def test_alloc_advance_free_roundtrip(self):
        a = PageAllocator(num_pages=8, page_size=4, max_pages_per_seq=4)
        pages = a.alloc(0, 10)                         # 3 pages
        assert len(pages) == 3 and a.used_pages == 3
        a.advance(0, 10)
        a.check_invariants()
        a.free(0)
        assert a.used_pages == 0 and a.free_pages == 8
        a.check_invariants()

    def test_oom_returns_none_state_unchanged(self):
        a = PageAllocator(num_pages=2, page_size=4, max_pages_per_seq=4)
        assert a.alloc(0, 8) is not None
        assert a.alloc(1, 4) is None                   # OOM: no pages
        assert 1 not in a._seqs and a.used_pages == 2
        a.advance(0, 8)
        assert a.ensure(0, 12) is None                 # grow OOM
        assert len(a.seq_pages(0)) == 2                # unchanged
        a.check_invariants()

    def test_ensure_grows_only_when_needed(self):
        a = PageAllocator(num_pages=8, page_size=4, max_pages_per_seq=8)
        a.alloc(0, 4)
        a.advance(0, 4)
        new, cow = a.ensure(0, 4)
        assert new == [] and cow == []
        new, cow = a.ensure(0, 5)
        assert len(new) == 1 and cow == []
        a.check_invariants()

    def test_fork_shares_then_copies_on_write(self):
        a = PageAllocator(num_pages=8, page_size=4, max_pages_per_seq=4)
        pages = a.alloc(0, 6)
        a.advance(0, 6)
        assert a.fork(0, 1) == pages
        assert a.used_pages == 2                       # shared, no copies
        a.check_invariants()
        new, cow = a.ensure(1, 7)     # writes into the shared tail page
        assert new == [] and len(cow) == 1
        assert cow[0][0] == pages[1]
        assert a.seq_pages(1)[1] != pages[1]
        assert a.seq_pages(0) == pages                 # src untouched
        a.check_invariants()
        a.free(0)
        a.free(1)
        assert a.used_pages == 0
        a.check_invariants()

    def test_double_alloc_and_overadvance_raise(self):
        a = PageAllocator(num_pages=4, page_size=4, max_pages_per_seq=4)
        a.alloc(0, 4)
        with pytest.raises(E.EnforceError):
            a.alloc(0, 4)
        with pytest.raises(E.EnforceError):
            a.advance(0, 5)                            # past capacity

    def test_pool_cow_copies_device_pages(self):
        cfg = L.llama_tiny()
        c = PagedKVCache(cfg, num_pages=6, page_size=4,
                         max_pages_per_seq=3, dtype=jnp.float32)
        pages = c.alloc.alloc(0, 6)
        c.pool["k"] = c.pool["k"].at[:, pages[1]].set(7.0)
        c.alloc.advance(0, 6)
        c.alloc.fork(0, 1)
        _, cow = c.alloc.ensure(1, 7)
        c.apply_cow(cow)
        dst = c.alloc.seq_pages(1)[1]
        np.testing.assert_array_equal(
            np.asarray(c.pool["k"][:, dst]),
            np.full_like(np.asarray(c.pool["k"][:, dst]), 7.0))


class TestPagedDecodeParity:
    """Identical tokens vs the ring-buffer path (the acceptance bar)."""

    def _run(self, family, cfg, params, lens, new, **req_kw):
        rng = np.random.default_rng(7)
        prompts = _prompts(rng, cfg.vocab_size, lens)
        want = [_ring_generate(family, params, cfg, p, new,
                               **{k: v for k, v in req_kw.items()
                                  if k in ("temperature", "key")})
                for p in prompts]
        eng = ServingEngine(family, params, cfg, num_slots=2,
                            max_len=32, page_size=4, decode_chunk=3)
        outs = eng.run([Request(rid=i, prompt=p, max_new_tokens=new,
                                **req_kw)
                        for i, p in enumerate(prompts)])
        for i, w in enumerate(want):
            np.testing.assert_array_equal(outs[i].tokens, w)
        eng.cache.alloc.check_invariants()
        assert eng.cache.alloc.used_pages == 0         # all retired
        return eng

    @pytest.mark.slow
    def test_llama_greedy_f32(self):
        # tier-1 budget (ISSUE 8): duplicate-dtype parity (~6s) — the
        # bf16 case below keeps the llama engine parity seam in the
        # fast lane at the dtype the engine actually serves
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        self._run(L, cfg, params, (5, 8, 11), 6)

    def test_llama_greedy_bf16(self):
        cfg = L.llama_tiny(dtype=jnp.bfloat16)
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        self._run(L, cfg, params, (5, 9), 5)

    def test_llama_temperature_fixed_seed(self):
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(1))
        self._run(L, cfg, params, (8,), 6, temperature=0.8,
                  key=jax.random.PRNGKey(42))

    @pytest.mark.slow  # tier-1 budget (ISSUE 14 rebalance): int8 paged
    # parity duplicates the bf16 paged pin above + the weight-only
    # generate/beam pins in test_models (TestWeightOnlyDecode)
    def test_llama_int8(self):
        cfg = L.llama_tiny()
        qp = L.quantize_weights(L.init_params(cfg, jax.random.PRNGKey(2)))
        self._run(L, cfg, qp, (6, 10), 5)

    @pytest.mark.slow  # tier-1 budget (ISSUE 5): heavy; llama parity
    def test_moe_greedy(self):  # cases keep the engine seam in tier-1
        cfg = M.moe_tiny()
        params = M.init_params(cfg, jax.random.PRNGKey(3))
        self._run(M, cfg, params, (4, 9), 5)

    @pytest.mark.slow  # tier-1 budget (ISSUE 5): heavy; run in slow lane
    def test_moe_int8(self):
        cfg = M.moe_tiny()
        qp = M.quantize_weights(M.init_params(cfg, jax.random.PRNGKey(4)))
        self._run(M, cfg, qp, (7,), 4)

    def test_eos_stops_and_frees(self):
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(5))
        rng = np.random.default_rng(9)
        prompt = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
        full = _ring_generate(L, params, cfg, prompt, 8)
        eos = int(full[3])                  # force a stop mid-stream
        eng = ServingEngine(L, params, cfg, num_slots=1, max_len=32,
                            page_size=4, decode_chunk=3)
        outs = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=8,
                                eos_token_id=eos)])
        got = outs[0].tokens
        assert got[-1] == eos and len(got) <= 8
        np.testing.assert_array_equal(got, full[:len(got)])
        assert eng.cache.alloc.used_pages == 0

    def test_decode_through_interpret_kernel_matches_ref(self):
        """The pallas kernel (interpret) slotted into the decode seam
        produces the same tokens as the jnp fallback."""
        from paddle_tpu import kernels as K
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(6))
        rng = np.random.default_rng(11)
        prompt = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        want = _ring_generate(L, params, cfg, prompt, 4)
        orig = K.dispatched_paged_attention
        import paddle_tpu.inference.paged as paged_mod  # noqa: F401

        def interp(q, kp, vp, bt, ln, *, scale=None, layer=None):
            return PA.ragged_paged_attention(q, kp, vp, bt, ln, scale=scale,
                                             layer=layer, interpret=True)

        K.dispatched_paged_attention = interp
        try:
            eng = ServingEngine(L, params, cfg, num_slots=1, max_len=16,
                                page_size=8, decode_chunk=2)
            outs = eng.run([Request(rid=0, prompt=prompt,
                                    max_new_tokens=4)])
        finally:
            K.dispatched_paged_attention = orig
        np.testing.assert_array_equal(outs[0].tokens, want)


# family, tiny config with heads that fill the lanes (float32: a tile is 8
# rows, so pages of 8), the engine's sizes
_KV_WRITE_FAMILIES = {
    "llama": lambda: (L, L.llama_tiny(hidden_size=256, num_attention_heads=2,
                                      num_key_value_heads=1),
                      dict(num_slots=3, max_len=48, num_pages=24)),
    "falcon_h1": lambda: (_family("falcon_h1"),
                          _family("falcon_h1").falcon_h1_tiny(head_dim=128),
                          dict(num_slots=3, max_len=48, num_pages=24)),
    "phi4flash": lambda: (_family("phi4flash"),
                          _family("phi4flash").phi4flash_tiny(
                              hidden_size=256, num_attention_heads=4,
                              num_key_value_heads=2, ring_page=8),
                          dict(num_slots=3, max_len=48, num_pages=24)),
    "zaya": lambda: (_family("zaya"), _family("zaya").zaya_tiny(head_dim=128),
                     dict(num_slots=3, max_len=48, num_pages=24)),
}


def _family(name):
    import importlib

    return importlib.import_module(f"paddle_tpu.models.{name}")


@pytest.mark.parametrize("name", _KV_WRITE_FAMILIES)
def test_engine_tokens_through_the_kv_write_kernel_and_its_fallback(name):
    """The same prompts through ``ServingEngine`` with the kernels
    registered in interpret mode (a decode step's token goes through
    ``kv_token_write``: the pool's pages, and Phi's rings) and with the
    fallback's two scatters: identical greedy tokens, more slots than
    requests at the end (inactive slots write nothing), and the
    dispatcher counts ``kv_write`` on one side and ``kv_write_fallback``
    on the other."""
    from paddle_tpu import kernels as K
    from paddle_tpu.inference import paged as paged_mod

    family, cfg, sizes = _KV_WRITE_FAMILIES[name]()
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(17)
    prompts = _prompts(rng, cfg.vocab_size, (5, 11, 3, 9))

    def serve():
        paged_mod._kv_token_write.clear_cache()   # the inner jit's trace
        before = K.dispatch_stats()
        eng = ServingEngine(family, params, cfg, page_size=8,
                            decode_chunk=3, **sizes)
        outs = eng.run([Request(rid=i, prompt=p, max_new_tokens=7 + i)
                        for i, p in enumerate(prompts)])
        after = K.dispatch_stats()
        return ([outs[i].tokens for i in range(len(prompts))],
                {k: after[k] - before[k]
                 for k in ("kv_write", "kv_write_fallback")})

    want, counted = serve()
    assert counted["kv_write"] == 0 and counted["kv_write_fallback"] >= 1
    try:
        K.register(interpret=True)
        got, counted = serve()
    finally:
        K.register()
        paged_mod._kv_token_write.clear_cache()
    assert counted["kv_write"] >= 1 and counted["kv_write_fallback"] == 0
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


class TestEngineScheduling:
    def test_randomized_arrival_length_trace(self):
        """Poisson-ish arrivals x random prompt/gen lengths through a
        small slot grid: every request completes with exactly its token
        budget, no page leaks, occupancy accounted."""
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(8))
        rng = np.random.default_rng(123)
        eng = ServingEngine(L, params, cfg, num_slots=3, max_len=48,
                            page_size=4, decode_chunk=2)
        reqs = [Request(rid=i,
                        prompt=rng.integers(
                            0, cfg.vocab_size,
                            (int(rng.integers(1, 14)),)).astype(np.int32),
                        max_new_tokens=int(rng.integers(1, 9)))
                for i in range(9)]
        pending = list(reqs)
        # staggered arrivals: a couple of requests join per scheduler step
        eng.submit(pending.pop(0))
        busy = True
        while busy or pending:
            for _ in range(int(rng.integers(0, 3))):
                if pending:
                    eng.submit(pending.pop(0))
            busy = eng.step()
        outs = eng.outputs
        assert sorted(outs) == [r.rid for r in reqs]
        for r in reqs:
            assert len(outs[r.rid].tokens) == r.max_new_tokens
            # spot-check correctness on a couple of requests
        for r in reqs[:2]:
            want = _ring_generate(L, params, cfg, r.prompt,
                                  r.max_new_tokens)
            np.testing.assert_array_equal(outs[r.rid].tokens, want)
        eng.cache.alloc.check_invariants()
        assert eng.cache.alloc.used_pages == 0
        s = eng.stats
        assert s.completed == len(reqs)
        assert s.tokens_generated == sum(r.max_new_tokens for r in reqs)
        assert 0.0 < s.occupancy() <= 1.0

    @pytest.mark.slow  # tier-1 budget (ISSUE 19 rebalance): preemption duplicated by the randomized
    # arrival trace above + test_engine's eviction-policy suite
    def test_preemption_under_tiny_pool(self):
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(9))
        rng = np.random.default_rng(5)
        eng = ServingEngine(L, params, cfg, num_slots=2, max_len=16,
                            page_size=4, num_pages=5, decode_chunk=2)
        reqs = [Request(rid=i, prompt=rng.integers(
                    0, cfg.vocab_size, (4,)).astype(np.int32),
                        max_new_tokens=8) for i in range(3)]
        outs = eng.run(reqs)
        assert eng.stats.preempted >= 1            # pool forces eviction
        for r in reqs:                             # recompute = exact
            want = _ring_generate(L, params, cfg, r.prompt, 8)
            np.testing.assert_array_equal(outs[r.rid].tokens, want)
        assert eng.cache.alloc.used_pages == 0

    def test_admission_refused_on_oom_idle_engine(self):
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(10))
        eng = ServingEngine(L, params, cfg, num_slots=1, max_len=16,
                            page_size=4, num_pages=4)
        # pool holds 4 pages; a 17-token request exceeds max_len
        with pytest.raises(E.EnforceError):
            eng.submit(Request(rid=0,
                               prompt=np.zeros(12, np.int32),
                               max_new_tokens=8))

    def test_watermark_defers_admission(self):
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(11))
        rng = np.random.default_rng(6)
        eng = ServingEngine(L, params, cfg, num_slots=2, max_len=16,
                            page_size=4, num_pages=8, watermark=0.5,
                            decode_chunk=2)
        reqs = [Request(rid=i, prompt=rng.integers(
                    0, cfg.vocab_size, (12,)).astype(np.int32),
                        max_new_tokens=4) for i in range(2)]
        for r in reqs:
            eng.submit(r)
        eng.step()
        # each prompt buckets to 4 pages; admitting the second would
        # leave 0 < 4 (= watermark) free pages: deferred
        assert eng.stats.admitted == 1 and len(eng.queue) == 1
        outs = eng.run()
        assert sorted(outs) == [0, 1]
        assert eng.cache.alloc.used_pages == 0

    def test_max_len_auto_page_size(self):
        """page_size=None resolves through the autotune knob (defaults
        off-TPU) and the engine still round-trips."""
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(12))
        eng = ServingEngine(L, params, cfg, num_slots=1, max_len=32)
        assert eng.page_size >= 1
        outs = eng.run([Request(rid=0,
                                prompt=np.arange(4, dtype=np.int32),
                                max_new_tokens=3)])
        assert len(outs[0].tokens) == 3


class TestPagedAutotune:
    def test_page_size_sweep_with_injected_measure(self):
        from paddle_tpu.kernels import autotune as at
        cache = at.AutotuneCache(path="/dev/null/never")  # memory-only
        calls = []

        def measure(ps):
            calls.append(ps)
            return {8: 5.0, 16: 1.0, 32: 2.0, 64: 3.0}[ps]

        got = at.paged_page_size(4, 8, 2, 64, 128, jnp.float32,
                                 measure=measure, cache=cache)
        assert got == 16 and len(calls) >= 2
        # second call is a cache hit: no remeasure
        calls.clear()
        got = at.paged_page_size(4, 8, 2, 64, 128, jnp.float32,
                                 measure=measure, cache=cache)
        assert got == 16 and calls == []

    def test_bf16_candidates_respect_sublane(self):
        from paddle_tpu.kernels import autotune as at
        assert all(ps >= 16 for ps in at.paged_candidates(jnp.bfloat16,
                                                          128))
        assert 8 in at.paged_candidates(jnp.float32, 128)
