"""Quantized memory plane (docs/quantization.md): packed int4
weight-only trees (models/llama.py quant_packed/unpack_int4, moe.py)
and int8 KV-cache pages behind FLAGS_serving_kv_quant
(inference/paged.py scale planes, kernels/paged_attention.py quant
arm, inference/engine.py wiring).

The load-bearing contracts: flags-off is byte-identical (plain-array
pools, int8-only default quantize_weights); kv-quant greedy decode
emits the full-precision pools' exact tokens (llama and MoE, jnp
fallback AND interpret kernel); int4 trees clear a pinned SQNR floor;
allocator fork/CoW/free move codes and scale planes in lockstep; the
autotune knob keys quantized and full-precision tunings apart and
warm-starts cold shapes from the nearest tuned neighbor.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import enforce as E
from paddle_tpu.core import flags as FL
from paddle_tpu.inference import PagedKVCache, Request, ServingEngine
from paddle_tpu.kernels import paged_attention as PA
from paddle_tpu.models import llama as L
from paddle_tpu.models import moe as M
from paddle_tpu.monitor import numerics as NU

pytestmark = pytest.mark.serving

# int4 keeps ~4 bits of mantissa: gaussian weights measure ~18-19 dB
# SQNR at tiny shapes; 12 dB is the refuse-to-serve floor
INT4_SQNR_FLOOR_DB = 12.0


def _prompts(rng, vocab, lens):
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lens]


def _serve(family, cfg, params, lens, new=6, seed=7, **kw):
    rng = np.random.default_rng(seed)
    prompts = _prompts(rng, cfg.vocab_size, lens)
    eng = ServingEngine(family, params, cfg, num_slots=2, max_len=32,
                        page_size=4, decode_chunk=3, **kw)
    outs = eng.run([Request(rid=i, prompt=p, max_new_tokens=new)
                    for i, p in enumerate(prompts)])
    eng.cache.alloc.check_invariants()
    assert eng.cache.alloc.used_pages == 0
    return {i: np.asarray(o.tokens) for i, o in outs.items()}, eng


# ---------------------------------------------------------------------------
# int4 nibble packing
# ---------------------------------------------------------------------------

class TestInt4Packing:
    def test_pack_unpack_roundtrip_matches_codes(self):
        """unpack(pack(codes)) == codes for the full [-8, 7] range on
        both parities of the interleave."""
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(16, 12)), jnp.float32)
        leaf = L.quant_packed(w, in_axis=0, weight_dtype="int4")
        assert set(leaf) == {"q4", "s"}
        assert leaf["q4"].dtype == jnp.int8
        assert leaf["q4"].shape == (8, 12)          # in_axis halved
        assert leaf["s"].shape == (12,)
        codes = np.asarray(L.unpack_int4(leaf["q4"], 0))
        # reference codes straight from the one-scheme contract
        wf = np.asarray(w, np.float64)
        s = np.abs(wf).max(axis=0) / 7.0
        want = np.clip(np.round(wf / np.maximum(s, 1e-10)), -8, 7)
        np.testing.assert_array_equal(codes, want.astype(np.int8))
        assert codes.min() >= -8 and codes.max() <= 7

    def test_dequant_is_f32_multiply_one_cast(self):
        """Dequantized int4 weights reproduce the quantizer's own
        rounding exactly (no intermediate-dtype double rounding)."""
        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.normal(size=(6, 10)), jnp.float32)
        leaf = L.quant_packed(w, in_axis=0, weight_dtype="int4")
        deq = (L.unpack_int4(leaf["q4"], 0).astype(jnp.float32)
               * leaf["s"][None, :])
        err = np.abs(np.asarray(deq) - np.asarray(w)).max()
        step = float(np.asarray(leaf["s"]).max())
        assert err <= 0.5 * step + 1e-7      # round-to-nearest bound

    def test_odd_contraction_dim_refused(self):
        w = jnp.zeros((7, 4), jnp.float32)
        with pytest.raises(E.EnforceError):
            L.quant_packed(w, in_axis=0, weight_dtype="int4")

    def test_unknown_width_refused(self):
        with pytest.raises(E.UnimplementedError):
            L.quant_packed(jnp.zeros((4, 4)), in_axis=0,
                           weight_dtype="int2")

    def test_int8_arm_is_quant_int8(self):
        w = jnp.asarray(np.random.default_rng(2).normal(size=(8, 8)),
                        jnp.float32)
        a = L.quant_packed(w, in_axis=0)
        b = L.quant_int8(w, in_axis=0)
        np.testing.assert_array_equal(np.asarray(a["q"]),
                                      np.asarray(b["q"]))

    def test_numpy_dequant_ref_matches_jax_unpack(self):
        """monitor/numerics dequant_ref(int4_packed=True) mirrors the
        jax unpack bit-for-bit (both sign-extension tricks agree)."""
        rng = np.random.default_rng(3)
        w = jnp.asarray(rng.normal(size=(3, 8, 6)), jnp.float32)
        leaf = L.quant_packed(w, in_axis=1, weight_dtype="int4")
        want = (L.unpack_int4(leaf["q4"], 1).astype(jnp.float32)
                * leaf["s"][:, None, :])
        got = NU.dequant_ref(np.asarray(leaf["q4"]),
                             np.asarray(leaf["s"]), int4_packed=True)
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-6,
                                   atol=1e-7)


# ---------------------------------------------------------------------------
# int4 trees: audit floors + serving parity
# ---------------------------------------------------------------------------

class TestInt4Trees:
    def test_llama_audit_clears_sqnr_floor(self):
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(2))
        q4 = L.quantize_weights(params, weight_dtype="int4")
        rep = NU.audit_quantized_tree(params, q4)
        assert np.isfinite(rep["int4_min_sqnr_db"])
        assert rep["int4_min_sqnr_db"] >= INT4_SQNR_FLOOR_DB
        assert rep["min_sqnr_db"] >= INT4_SQNR_FLOOR_DB
        assert all(e["bits"] == 4 for e in rep["tensors"].values())

    def test_moe_audit_clears_sqnr_floor(self):
        cfg = M.moe_tiny()
        params = M.init_params(cfg, jax.random.PRNGKey(3))
        q4 = M.quantize_weights(params, weight_dtype="int4")
        rep = NU.audit_quantized_tree(params, q4)
        assert np.isfinite(rep["int4_min_sqnr_db"])
        assert rep["int4_min_sqnr_db"] >= INT4_SQNR_FLOOR_DB

    def test_default_weight_dtype_unchanged_int8(self):
        """Flags-off pin: quantize_weights() still emits {"q","s"}
        int8 leaves — int4 is opt-in by argument only."""
        cfg = L.llama_tiny()
        qp = L.quantize_weights(L.init_params(cfg, jax.random.PRNGKey(0)))
        assert set(qp["layers"]["wq"]) == {"q", "s"}
        assert qp["layers"]["wq"]["q"].dtype == jnp.int8

    def test_llama_int4_ring_vs_paged_parity(self):
        """The int4 tree serves through the SAME engine seam as int8:
        paged tokens == ring-buffer generate tokens."""
        cfg = L.llama_tiny()
        q4 = L.quantize_weights(L.init_params(cfg, jax.random.PRNGKey(2)),
                                weight_dtype="int4")
        rng = np.random.default_rng(7)
        prompts = _prompts(rng, cfg.vocab_size, (6, 10))
        want = [np.asarray(L.generate(q4, jnp.asarray(p)[None, :], cfg,
                                      max_new_tokens=5))[0]
                for p in prompts]
        got, _ = _serve(L, cfg, q4, (6, 10), new=5)
        for i, w in enumerate(want):
            np.testing.assert_array_equal(got[i], w)

    @pytest.mark.slow  # tier-1 budget: llama int4 parity above keeps
    # the int4 engine seam in the fast lane; MoE adds expert matmuls
    def test_moe_int4_ring_vs_paged_parity(self):
        cfg = M.moe_tiny()
        q4 = M.quantize_weights(M.init_params(cfg, jax.random.PRNGKey(3)),
                                weight_dtype="int4")
        rng = np.random.default_rng(7)
        prompts = _prompts(rng, cfg.vocab_size, (5, 8))
        want = [np.asarray(M.generate(q4, jnp.asarray(p)[None, :], cfg,
                                      max_new_tokens=4))[0]
                for p in prompts]
        got, _ = _serve(M, cfg, q4, (5, 8), new=4)
        for i, w in enumerate(want):
            np.testing.assert_array_equal(got[i], w)


# ---------------------------------------------------------------------------
# int8 KV pages: kernel arm
# ---------------------------------------------------------------------------

class TestKVQuantKernel:
    def _case(self, seed=0, B=3, nh=4, kv=2, hd=64, ps=32, P=12, maxp=3,
              lengths=(13, 0, 70)):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(B, nh, hd)), jnp.float32)
        kq = jnp.asarray(rng.integers(-127, 128, (P, kv, ps, hd)),
                         jnp.int8)
        vq = jnp.asarray(rng.integers(-127, 128, (P, kv, ps, hd)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(0.004, 0.02, (P, kv)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.004, 0.02, (P, kv)), jnp.float32)
        bt = jnp.asarray(rng.integers(0, P, (B, maxp)), jnp.int32)
        ln = jnp.asarray(lengths, jnp.int32)
        return q, kq, vq, ks, vs, bt, ln

    def test_quant_kernel_matches_quant_ref(self):
        q, kq, vq, ks, vs, bt, ln = self._case()
        got = PA.ragged_paged_attention(q, kq, vq, bt, ln, k_scales=ks,
                                        v_scales=vs, interpret=True)
        want = PA.paged_attention_ref(q, kq, vq, bt, ln, k_scales=ks,
                                      v_scales=vs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_quant_ref_matches_dense_dequant(self):
        """Scale folding is exact: attention over int8 codes + scales
        == attention over the densely dequantized pages."""
        q, kq, vq, ks, vs, bt, ln = self._case(seed=1)
        want = PA.paged_attention_ref(
            q, kq.astype(jnp.float32) * ks[:, :, None, None],
            vq.astype(jnp.float32) * vs[:, :, None, None], bt, ln)
        got = PA.paged_attention_ref(q, kq, vq, bt, ln, k_scales=ks,
                                     v_scales=vs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    def test_supported_quant_guard(self):
        q, kq, vq, ks, vs, bt, ln = self._case(hd=128)
        assert PA.supported(q, kq, bt, quant=True)
        # int8 pages without the scales arm are a contract breach
        assert not PA.supported(q, kq, bt)
        # quant arm needs the int8 sublane tile (32 rows)
        assert not PA.supported(q, kq[:, :, :16], bt, quant=True)
        # quant arm over non-int8 pages is not a thing
        assert not PA.supported(q, kq.astype(jnp.float32), bt,
                                quant=True)


# ---------------------------------------------------------------------------
# int8 KV pages: allocator + pool plumbing
# ---------------------------------------------------------------------------

class TestKVQuantPool:
    def test_quant_pool_layout(self):
        cfg = L.llama_tiny()
        c = PagedKVCache(cfg, num_pages=6, page_size=4,
                         max_pages_per_seq=3, dtype=jnp.float32,
                         kv_quant=True)
        for leaf in (c.pool["k"], c.pool["v"]):
            assert set(leaf) == {"q", "s"}
            assert leaf["q"].dtype == jnp.int8
            assert leaf["s"].dtype == jnp.float32
            assert leaf["s"].shape == leaf["q"].shape[:3]

    def test_flags_off_pool_is_plain_array(self):
        """Byte-identity pin: flag off, the pool leaves are the same
        plain arrays as before the quantized plane existed (no dict
        wrapper, no scale planes, same dtype/shape)."""
        cfg = L.llama_tiny()
        c = PagedKVCache(cfg, num_pages=6, page_size=4,
                         max_pages_per_seq=3, dtype=jnp.float32)
        assert isinstance(c.pool["k"], jnp.ndarray)
        assert c.pool["k"].dtype == jnp.float32
        assert not c.kv_quant

    def test_cow_copies_codes_and_scales_in_lockstep(self):
        """apply_cow moves the scale row WITH its page — the invariant
        that keeps dequantization correct across forks."""
        cfg = L.llama_tiny()
        c = PagedKVCache(cfg, num_pages=6, page_size=4,
                         max_pages_per_seq=3, dtype=jnp.float32,
                         kv_quant=True)
        pages = c.alloc.alloc(0, 6)
        c.pool["k"]["q"] = c.pool["k"]["q"].at[:, pages[1]].set(7)
        c.pool["k"]["s"] = c.pool["k"]["s"].at[:, pages[1]].set(0.25)
        c.alloc.advance(0, 6)
        c.alloc.fork(0, 1)
        _, cow = c.alloc.ensure(1, 7)
        c.apply_cow(cow)
        c.alloc.check_invariants()
        dst = c.alloc.seq_pages(1)[1]
        assert dst != pages[1]
        np.testing.assert_array_equal(
            np.asarray(c.pool["k"]["q"][:, dst]), 7)
        np.testing.assert_array_equal(
            np.asarray(c.pool["k"]["s"][:, dst]), 0.25)
        c.alloc.free(0)
        c.alloc.free(1)
        assert c.alloc.used_pages == 0
        c.alloc.check_invariants()

    def test_engine_flag_routes_construction(self):
        """ServingEngine(kv_quant=None) resolves FLAGS_serving_kv_quant
        (the _opt pattern every serving flag follows)."""
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        try:
            FL.set_flags({"FLAGS_serving_kv_quant": True})
            eng = ServingEngine(L, params, cfg, num_slots=1, max_len=16,
                                page_size=4)
            assert eng._kv_quant and isinstance(eng.cache.pool["k"], dict)
        finally:
            FL.set_flags({"FLAGS_serving_kv_quant": False})
        eng = ServingEngine(L, params, cfg, num_slots=1, max_len=16,
                            page_size=4)
        assert not eng._kv_quant
        assert isinstance(eng.cache.pool["k"], jnp.ndarray)


# ---------------------------------------------------------------------------
# int8 KV pages: greedy decode parity (the acceptance bar)
# ---------------------------------------------------------------------------

class TestKVQuantDecodeParity:
    """Quantized pools must emit the full-precision pools' exact greedy
    tokens at tiny shapes (weights untouched — only the KV cache drops
    to int8, and the one-scheme scales keep argmax stable)."""

    def test_llama_greedy_fallback(self):
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        want, _ = _serve(L, cfg, params, (5, 9, 12))
        got, eng = _serve(L, cfg, params, (5, 9, 12), kv_quant=True)
        for i in want:
            np.testing.assert_array_equal(got[i], want[i])
        assert isinstance(eng.cache.pool["k"], dict)

    @pytest.mark.slow  # tier-1 budget (ISSUE 20 rebalance): kv-quant family re-run; llama_greedy_fallback
    # keeps the dequant-parity seam fast
    def test_moe_greedy_fallback(self):
        cfg = M.moe_tiny()
        params = M.init_params(cfg, jax.random.PRNGKey(3))
        want, _ = _serve(M, cfg, params, (5, 9))
        got, _ = _serve(M, cfg, params, (5, 9), kv_quant=True)
        for i in want:
            np.testing.assert_array_equal(got[i], want[i])

    @pytest.mark.slow  # tier-1 budget (ISSUE 20 rebalance): interpret-kernel arm; llama_greedy_fallback +
    # the TestKVQuantKernel parity units keep the seam fast
    def test_llama_greedy_interpret_kernel(self):
        """The quant KERNEL (interpret) slotted into the decode seam
        produces the fallback's tokens — both decode arms agree."""
        from paddle_tpu import kernels as K
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(6))
        want, _ = _serve(L, cfg, params, (5, 8), new=4)
        orig = K.dispatched_paged_attention

        def interp(q, kp, vp, bt, ln, *, scale=None, k_scales=None,
                   v_scales=None, layer=None):
            return PA.ragged_paged_attention(
                q, kp, vp, bt, ln, scale=scale, k_scales=k_scales,
                v_scales=v_scales, layer=layer, interpret=True)

        K.dispatched_paged_attention = interp
        try:
            got, _ = _serve(L, cfg, params, (5, 8), new=4, kv_quant=True)
        finally:
            K.dispatched_paged_attention = orig
        for i in want:
            np.testing.assert_array_equal(got[i], want[i])

    def test_prefix_cache_composition(self):
        """Radix prefix cache over int8 pools: forked pages carry their
        scale rows, tokens match the flags-off serve, and the cache
        holds drain."""
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        pref = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
        prompts = [np.concatenate(
            [pref, rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32)])
            for _ in range(3)]

        def serve(**kw):
            eng = ServingEngine(L, params, cfg, num_slots=2, max_len=32,
                                page_size=4, decode_chunk=3, **kw)
            outs = eng.run([Request(rid=i, prompt=p, max_new_tokens=5)
                            for i, p in enumerate(prompts)])
            eng.cache.alloc.check_invariants()
            return {i: np.asarray(o.tokens) for i, o in outs.items()}, eng

        want, _ = serve()
        got, eng = serve(kv_quant=True, prefix_cache=True)
        for i in want:
            np.testing.assert_array_equal(got[i], want[i])
        # the radix cache held pages across requests (prefill skipped)
        assert eng.stats.prefix_tokens_saved > 0

    @pytest.mark.slow  # tier-1 budget (ISSUE 20 rebalance): composition sweep; prefix_cache_composition +
    # test_prefix_cache's spec greedy-identity pins keep the seam fast
    def test_spec_decode_composition(self):
        """Speculative verify windows rewrite quantized pages in place
        (paged_verify_window's gather/requant path): tokens match the
        flags-off serve exactly."""
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        want, _ = _serve(L, cfg, params, (6, 9), new=8)
        got, _ = _serve(L, cfg, params, (6, 9), new=8, kv_quant=True,
                        spec_decode=True)
        for i in want:
            np.testing.assert_array_equal(got[i], want[i])


# ---------------------------------------------------------------------------
# numerics feeds
# ---------------------------------------------------------------------------

class TestKVQuantNumerics:
    @pytest.fixture(autouse=True)
    def _clean(self):
        yield
        FL.set_flags({"FLAGS_enable_monitor": False,
                      "FLAGS_serving_kv_quant": False})
        NU.set_kv_sample_rate(None)
        from paddle_tpu import monitor
        monitor.reset()
        NU.reset()

    def test_record_and_snapshot(self):
        from paddle_tpu import monitor
        FL.set_flags({"FLAGS_enable_monitor": True})
        monitor.reset()
        NU.reset()
        NU.record_kv_quant(np.full((2, 3), 0.5, np.float32), 0.01)
        snap = NU.kv_quant_snapshot()
        assert snap["samples"] == 1
        assert snap["scale_p99"] == pytest.approx(0.5)
        assert snap["clip_fraction"] == pytest.approx(0.01)
        g = monitor.snapshot()["gauges"]
        assert g["numerics.kv_quant.scale_p99"] == pytest.approx(0.5)
        assert g["numerics.kv_quant.clip_fraction"] == pytest.approx(0.01)
        NU.reset()
        assert NU.kv_quant_snapshot()["samples"] == 0

    def test_engine_sampling_feeds_kv_quant(self):
        """The engine's 1-in-N absmax seam records scale/clip health
        for quantized pools (live pages only, finite, positive)."""
        from paddle_tpu import monitor
        FL.set_flags({"FLAGS_enable_monitor": True})
        monitor.reset()
        NU.reset()
        NU.set_kv_sample_rate(1)
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        _serve(L, cfg, params, (5, 9), kv_quant=True)
        snap = NU.kv_quant_snapshot()
        assert snap["samples"] > 0
        assert snap["scale_p99"] is not None and snap["scale_p99"] > 0
        assert 0.0 <= snap["clip_fraction"] <= 1.0
        # the absmax plane keeps feeding alongside (absmax = |q|*s)
        assert NU.kv_snapshot()["samples"] > 0


# ---------------------------------------------------------------------------
# autotune key space + warm start
# ---------------------------------------------------------------------------

class TestPagedAutotuneKVQuant:
    def test_kv_quant_candidates_floor_32(self):
        from paddle_tpu.kernels import autotune as AT
        assert all(ps % 32 == 0
                   for ps in AT.paged_candidates(jnp.bfloat16, 256,
                                                 kv_quant=True))
        assert 16 in AT.paged_candidates(jnp.bfloat16, 256)

    def test_key_space_no_collision(self, tmp_path):
        """kv_quant entries ride a ':kvq' suffix — a quantized tuning
        never shadows the full-precision pool's entry for the same
        shape."""
        from paddle_tpu.kernels import autotune as AT
        cache = AT.AutotuneCache(str(tmp_path / "at.json"))
        ps_fp = AT.paged_page_size(4, 8, 2, 64, 256, jnp.bfloat16,
                                   measure=lambda ps: float(ps),
                                   cache=cache)
        ps_q = AT.paged_page_size(4, 8, 2, 64, 256, jnp.bfloat16,
                                  measure=lambda ps: 1.0 / ps,
                                  cache=cache, kv_quant=True)
        keys = sorted(cache._mem)
        assert len(keys) == 2 and keys[1].endswith(":kvq")
        assert ps_fp == 16         # cheapest by injected timing (8 < bf16 sublane)
        assert ps_q == 64
        assert ps_q % 32 == 0

    def test_nearest_neighbor_warm_start(self, tmp_path):
        """A cold shape that cannot measure (CPU backend) seeds from
        the closest tuned neighbor in its key family instead of the
        hardcoded default."""
        from paddle_tpu.kernels import autotune as AT
        cache = AT.AutotuneCache(str(tmp_path / "at.json"))
        # tune b4 via injected measure; then ask for b6 with no measure
        AT.paged_page_size(4, 8, 2, 64, 256, jnp.bfloat16,
                           measure=lambda ps: 1.0 / ps, cache=cache)
        got = AT.paged_page_size(6, 8, 2, 64, 256, jnp.bfloat16,
                                 cache=cache)
        key = [k for k in AT._USED if "b6h8" in k and "kvq" not in k][0]
        assert AT._USED[key]["source"].startswith("warm-start:")
        assert got == 64

    def test_warm_start_ignores_other_families_and_errors(self, tmp_path):
        from paddle_tpu.kernels import autotune as AT
        cache = AT.AutotuneCache(str(tmp_path / "at.json"))
        # a kv-quant entry and an error entry must NOT warm-start the
        # full-precision key family
        AT.paged_page_size(4, 8, 2, 64, 256, jnp.bfloat16,
                           measure=lambda ps: 1.0 / ps, cache=cache,
                           kv_quant=True)
        bad_key = [k for k in cache._mem][0].replace(":kvq", "") \
            .replace("b4", "b2")
        cache.put(bad_key, {"page_size": 8, "error": "boom"})
        got = AT.paged_page_size(6, 8, 2, 64, 256, jnp.bfloat16,
                                 cache=cache)
        key = [k for k in AT._USED if "b6h8" in k and "kvq" not in k][0]
        assert AT._USED[key]["source"] == "default-not-tpu"
        assert got == AT.PAGED_DEFAULT_PAGE
