"""Pallas kernel library numeric tests (interpret mode on CPU — the
hardware-free kernel test path, mirroring the reference's OpTest numeric
comparisons vs reference implementations, SURVEY.md §4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import importlib

# the package re-exports the callable under the submodule's name, so reach
# the module itself through sys.modules
fa_mod = importlib.import_module("paddle_tpu.kernels.flash_attention")
flash_attention = fa_mod.flash_attention
from paddle_tpu.kernels.rms_norm import rms_norm as fused_rms
from paddle_tpu.nn.functional.attention import sdpa_reference

RNG = np.random.default_rng(7)


def rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,H,KV,D,causal", [
        (2, 128, 4, 4, 64, False),
        (2, 256, 4, 2, 64, True),     # GQA + causal
        (1, 128, 8, 2, 128, True),
    ])
    def test_forward_matches_reference(self, B, S, H, KV, D, causal):
        q, k, v = rand((B, S, H, D)), rand((B, S, KV, D)), rand((B, S, KV, D))
        ref = sdpa_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_backward_matches_reference(self):
        B, S, H, KV, D = 2, 128, 4, 2, 64
        q, k, v = rand((B, S, H, D)), rand((B, S, KV, D)), rand((B, S, KV, D))

        def lf(q, k, v):
            return (flash_attention(q, k, v, causal=True,
                                    interpret=True) ** 2).sum()

        def lr(q, k, v):
            return (sdpa_reference(q, k, v, causal=True) ** 2).sum()

        g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=5e-4)

    def test_unsupported_shapes_detected(self):
        q = rand((1, 100, 4, 64))   # 100 not divisible by block
        k = v = rand((1, 100, 4, 64))
        assert not fa_mod.supported(q, k, v)

    def test_dispatch_seam(self):
        """register() routes F.scaled_dot_product_attention through the
        dispatcher (with XLA fallback for unsupported shapes)."""
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu import kernels
        from paddle_tpu.nn.functional import attention as att
        q = rand((1, 64, 2, 32))
        try:
            kernels.register(interpret=True)
            assert att._FLASH_IMPL is not None
            out = F.scaled_dot_product_attention(
                paddle.to_tensor(np.asarray(q)),
                paddle.to_tensor(np.asarray(q)),
                paddle.to_tensor(np.asarray(q)), is_causal=True)
            ref = sdpa_reference(q, q, q, causal=True)
            np.testing.assert_allclose(out.numpy(), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5)
        finally:
            kernels.unregister()


# B, Sq, Sk, H, KV, D, causal, block_q, block_k, resident bytes (None: the
# sequences whole in VMEM; a number cuts them into that many bytes' spans)
LOOP_FORMS = {
    "blocks-larger-than-the-sequence": (1, 64, 64, 2, 2, 32, True, 128, 128,
                                        None),
    "blocks-equal-to-the-sequence": (1, 64, 64, 2, 2, 32, True, 64, 64, None),
    "blocks-smaller-than-the-sequence": (2, 128, 128, 2, 2, 32, True, 32, 32,
                                         None),
    # sq < sk, offset 40: no block boundary meets the diagonal
    "bottom-right-offset-off-the-boundaries": (1, 64, 104, 2, 2, 32, True,
                                               32, 8, None),
    "offset-of-whole-blocks": (1, 64, 128, 2, 1, 32, True, 32, 64, None),
    "gqa-group-1": (1, 64, 64, 3, 3, 32, True, 32, 32, None),
    "gqa-group-4": (1, 64, 64, 4, 1, 32, True, 32, 32, None),
    "gqa-group-5": (2, 64, 64, 10, 2, 32, True, 32, 32, None),
    # a q block of 64 rows over k blocks of 16: each visits whole
    # sub-blocks first and then four the diagonal crosses, side by side
    "interior-beside-diagonal-wide-q": (1, 128, 128, 2, 2, 32, True, 64, 16,
                                        None),
    "interior-beside-diagonal-wide-k": (1, 128, 128, 2, 2, 32, True, 16, 64,
                                        None),
    "non-causal": (1, 64, 96, 2, 1, 32, False, 32, 48, None),
    "non-causal-one-block": (1, 64, 64, 2, 2, 32, False, 64, 64, None),
    # the sequences in spans of two sub-blocks: the grid's third axis
    # runs, and a hidden span is neither fetched nor walked
    "spans-causal": (1, 128, 128, 2, 1, 32, True, 16, 16, 4 * 32 * 32 * 4),
    "spans-offset": (1, 64, 128, 2, 2, 32, True, 16, 32, 4 * 64 * 32 * 4),
    "spans-non-causal": (1, 64, 128, 2, 2, 32, False, 32, 16,
                         4 * 32 * 32 * 4),
}


def _resident(monkeypatch, nbytes):
    if nbytes is not None:
        from paddle_tpu.kernels import tiling
        monkeypatch.setattr(tiling, "FLASH_RESIDENT_BYTES", nbytes)


class TestFlashLoopForms:
    @pytest.mark.parametrize("case", LOOP_FORMS)
    def test_forward_and_gradients_match_reference(self, monkeypatch, case):
        """The forward and all three gradients against sdpa_reference at
        every form the tile loop takes."""
        B, Sq, Sk, H, KV, D, causal, bq, bk, resident = LOOP_FORMS[case]
        _resident(monkeypatch, resident)
        q, k, v = rand((B, Sq, H, D)), rand((B, Sk, KV, D)), \
            rand((B, Sk, KV, D))
        w = rand((B, Sq, H, D))

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=causal, block_q=bq,
                                   block_k=bk, interpret=True)

        def ref(q, k, v):
            return sdpa_reference(q, k, v, causal=causal)

        np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                                   np.asarray(ref(q, k, v)),
                                   rtol=1e-5, atol=1e-5)
        g1 = jax.grad(lambda *a: (flash(*a) * w).sum(), (0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: (ref(*a) * w).sum(), (0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("Sq,Sk,bq,bk,span_q,span_k", [
        (4096, 4096, 512, 512, 4096, 4096),   # the train cell's call
        (128, 128, 32, 32, 128, 128),
        (64, 104, 32, 8, 64, 104),            # offset off the boundaries
        (128, 128, 64, 16, 128, 32),          # K/V in spans of two
        (128, 128, 16, 64, 32, 128),          # q and dO in spans of two
        (256, 128, 32, 32, 64, 64),           # rows that see nothing
    ])
    def test_a_causal_call_runs_the_visible_pairs_and_no_other(
            self, Sq, Sk, bq, bk, span_q, span_k):
        """Counted from the loop bounds the kernels run (not timed): the
        bodies a causal call executes are the (q block, k block) pairs
        that hold a visible token pair, each once, and the ones that
        build the mask are the ones the diagonal crosses; the dkv kernel's
        turn of the loop visits the same pairs."""
        offset = Sk - Sq
        rows, cols = np.arange(Sq)[:, None], np.arange(Sk)[None, :]
        seen = (cols <= rows + offset).reshape(Sq // bq, bq, Sk // bk, bk)
        visible = seen.any(axis=(1, 3))
        crossed = visible & ~seen.all(axis=(1, 3))

        ran, masked = np.zeros_like(visible, int), np.zeros_like(visible, int)
        per = span_k // bk
        for qi in range(Sq // bq):
            for kj in range(Sk // span_k):
                whole, end = (int(x) for x in fa_mod.k_loop_bounds(
                    qi, kj * span_k, offset=offset, block_q=bq, block_k=bk,
                    count=per))
                assert 0 <= whole <= end <= per
                ran[qi, kj * per:kj * per + end] += 1
                masked[qi, kj * per + whole:kj * per + end] += 1
        np.testing.assert_array_equal(ran, visible)
        np.testing.assert_array_equal(masked, crossed)

        ran[:], masked[:] = 0, 0
        per = span_q // bq
        for ki in range(Sk // bk):
            for qj in range(Sq // span_q):
                start, whole = (int(x) for x in fa_mod.q_loop_bounds(
                    ki, qj * span_q, offset=offset, block_q=bq, block_k=bk,
                    count=per))
                assert 0 <= start <= whole <= per
                ran[qj * per + start:(qj + 1) * per, ki] += 1
                masked[qj * per + start:qj * per + whole, ki] += 1
        np.testing.assert_array_equal(ran, visible)
        np.testing.assert_array_equal(masked, crossed)
        if Sq == Sk == 4096:
            assert visible.sum() == 36 and crossed.sum() == 8


class TestFusedRMSNorm:
    def test_forward_backward_match(self):
        n, d = 256, 128
        x = rand((n, d))
        w = rand((d,)) * 0.1 + 1.0

        def ref(x, w):
            xf = x.astype(jnp.float32)
            r = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
            return xf * r * w

        y = fused_rms(x, w, 1e-6, 256, True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x, w)),
                                   rtol=1e-5, atol=1e-5)

        g1 = jax.grad(lambda x, w: (fused_rms(x, w, 1e-6, 256, True)
                                    ** 2).sum(), argnums=(0, 1))(x, w)
        g2 = jax.grad(lambda x, w: (ref(x, w) ** 2).sum(),
                      argnums=(0, 1))(x, w)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_3d_input(self):
        x = rand((4, 32, 64))
        w = jnp.ones((64,))
        y = fused_rms(x, w, 1e-6, 128, True)
        assert y.shape == x.shape


class TestCausalAlignment:
    def test_causal_cross_length_bottom_right(self):
        """causal with Sq != Sk must use bottom-right alignment like
        sdpa (chunked prefill pattern)."""
        q = rand((1, 64, 2, 32))
        k = rand((1, 128, 2, 32))
        v = rand((1, 128, 2, 32))
        ref = sdpa_reference(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_cross_length_backward(self):
        q = rand((1, 64, 2, 32))
        k = rand((1, 128, 2, 32))
        v = rand((1, 128, 2, 32))
        g1 = jax.grad(lambda q, k, v: (flash_attention(
            q, k, v, causal=True, interpret=True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda q, k, v: (sdpa_reference(
            q, k, v, causal=True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=5e-4)


class TestDispatchGuards:
    def test_rms_broadcastable_weight_falls_back(self):
        """2-D / broadcastable weights must take the XLA path, with the
        same promoted output dtype as the unregistered op."""
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu import kernels
        x = paddle.to_tensor(np.random.randn(8, 128).astype("float32"))
        w2d = paddle.to_tensor(np.ones((1, 128), "float32"))
        ref = F.rms_norm(x, w2d).numpy()
        try:
            kernels.register(interpret=True)
            out = F.rms_norm(x, w2d).numpy()
        finally:
            kernels.unregister()
        np.testing.assert_allclose(out, ref, rtol=1e-6)

    def test_rms_dtype_promotion_matches(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu import kernels
        x = paddle.to_tensor(np.random.randn(8, 128).astype("float32")).astype("bfloat16")
        w = paddle.to_tensor(np.ones((128,), "float32"))
        ref = F.rms_norm(x, w)
        try:
            kernels.register(interpret=True)
            out = F.rms_norm(x, w)
        finally:
            kernels.unregister()
        assert out.dtype == ref.dtype, (out.dtype, ref.dtype)

    def test_lazy_register_no_backend_probe(self):
        """auto_register's dispatchers only probe the backend at call
        time; registering must not initialize anything."""
        from paddle_tpu import kernels
        from paddle_tpu.nn.functional import attention as att
        try:
            kernels.register()
            assert att._FLASH_IMPL is not None
            # off-TPU it must route to the XLA reference path
            q = rand((1, 64, 2, 32))
            out = att._FLASH_IMPL(q, q, q, causal=True)
            ref = sdpa_reference(q, q, q, causal=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref))
        finally:
            kernels.unregister()


# ---------------------------------------------------------------------------
# every pallas_call of the main paths carries its stable name
# ---------------------------------------------------------------------------

def _walk(jaxpr):
    """Every equation of a jaxpr, nested ones (scan bodies, remat,
    custom_vjp, pjit) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub)


def _eqns(jaxpr, name):
    return [e for e in _walk(jaxpr) if e.primitive.name == name]


def _pallas_names(jaxpr):
    """``name=`` of every ``pallas_call`` in a jaxpr."""
    return [e.params["name"] for e in _eqns(jaxpr, "pallas_call")]


def _trace_train(packed):
    from paddle_tpu.models import llama as L
    cfg = L.llama_tiny(dtype=jnp.bfloat16, hidden_size=256,
                       num_attention_heads=2, num_key_value_heads=2,
                       max_position_embeddings=256, remat=True)
    params = jax.eval_shape(lambda k: L.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    batch = (ids, ids, ids, ids) if packed \
        else jax.ShapeDtypeStruct((1, 129), jnp.int32)
    return jax.make_jaxpr(lambda p, b: jax.value_and_grad(
        lambda q: L.loss_fn(q, b, cfg))(p))(params, batch)


def _trace_decode(pages):
    from paddle_tpu.inference import paged
    from paddle_tpu.inference.paged import init_pool, paged_decode_step
    from paddle_tpu.models import llama as L
    # the KV write sits behind a jit of its own, whose cached trace holds
    # whichever arm the dispatcher took when it was made
    paged._kv_token_write.clear_cache()
    cfg = L.llama_tiny(dtype=jnp.bfloat16, hidden_size=256,
                       num_attention_heads=2, num_key_value_heads=2)
    params = jax.eval_shape(lambda k: L.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    pool = jax.eval_shape(lambda: init_pool(cfg, pages or 8, 16))
    bt = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    vec = jax.ShapeDtypeStruct((2,), jnp.int32)
    return jax.make_jaxpr(lambda p, pk, pv, b, n, t: paged_decode_step(
        L, p, pk, pv, b, n, t, cfg))(params, pool["k"], pool["v"], bt,
                                     vec, vec)


@pytest.mark.parametrize("on_tpu", [True, False],
                         ids=["kernel", "gather-reference"])
def test_decode_step_carries_the_pool_and_never_cuts_a_layer_out(
        monkeypatch, on_tpu):
    """Jaxpr level: the decode step's layer scan has both pool halves in
    its carry and no stacked output (a scan's ys is a fresh buffer: a
    second pool and a copy of it every step); the two kernels' operands
    are the pool halves whole, [L, P, kv, ps, hd] read as [L * P, kv, ps,
    hd], and the write's two results are halves whole too; and no
    equation makes one layer [P, kv, ps, hd] of a half, on either arm of
    the dispatchers."""
    from paddle_tpu import kernels
    monkeypatch.setattr(kernels, "_on_tpu", lambda: on_tpu)
    jaxpr = _trace_decode(12).jaxpr    # (the tables name 8 pages: a gather)
    half = (2, 12, 2, 16, 128)               # llama_tiny here, 12 pages of 16
    # the layer scan (the write kernel's loops over slots are scans too)
    scan, = [e for e in _eqns(jaxpr, "scan")
             if half in {v.aval.shape for v in e.outvars}]
    carried = scan.outvars[:scan.params["num_carry"]]
    assert [v.aval.shape for v in carried].count(half) == 2
    assert len(scan.outvars) == scan.params["num_carry"]       # no ys
    if on_tpu:
        calls = {e.params["name"]: e for e in _eqns(jaxpr, "pallas_call")}
        assert set(calls) == {"kv_token_write", "paged_decode_attn"}
        whole = (half[0] * half[1],) + half[2:]
        for call in calls.values():
            assert [v.aval.shape for v in call.invars].count(whole) == 2
        assert [v.aval.shape for v in
                calls["kv_token_write"].outvars] == [whole, whole]

    assert half[1:] not in {v.aval.shape for e in _walk(jaxpr)
                            for v in e.outvars}


def _trace_rms(_):
    from paddle_tpu.nn.functional import norm
    x = jax.ShapeDtypeStruct((16, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128,), jnp.float32)
    return jax.make_jaxpr(lambda a, b: jax.value_and_grad(
        lambda c: norm._FUSED_RMS_IMPL(c, b, 1e-6).sum())(a))(x, w)


@pytest.mark.parametrize("trace,arg,want", [
    (_trace_train, False, {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    (_trace_train, True, {"flash_seg_fwd", "flash_seg_bwd_dq",
                          "flash_seg_bwd_dkv"}),
    (_trace_decode, None, {"paged_decode_attn", "kv_token_write"}),
    (_trace_rms, None, {"rms_norm_fwd", "rms_norm_bwd"}),
], ids=["train_step", "packed_train_step", "decode_step", "rms_norm"])
def test_every_pallas_call_of_the_main_paths_is_named(monkeypatch, trace,
                                                      arg, want):
    """Jaxpr level (nothing compiles): with the dispatchers believing
    they are on a TPU, every ``pallas_call`` the program traces carries
    the kernel's stable name — what the compiled instruction is called
    in a device trace, and what ``benchmark/layer_metrics/kern.*``
    match. A kernel without ``name=`` would show the kernel function's
    own name here and ``closed_call``/``checkpoint`` on the chip."""
    from paddle_tpu import kernels
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "cached")
    kernels.register()
    try:
        names = _pallas_names(trace(arg).jaxpr)
    finally:
        kernels.unregister()
    assert names and set(names) == want, names
