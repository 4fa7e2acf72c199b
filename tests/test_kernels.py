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
