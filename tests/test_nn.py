"""nn layer tests — numeric parity against NumPy/JAX references, mirroring
the reference's OpTest strategy (test/legacy_test/op_test.py)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def t(x, sg=True):
    return paddle.to_tensor(np.asarray(x, dtype=np.float32),
                            stop_gradient=sg)


class TestFunctionalActivations:
    def test_relu(self):
        x = t([[-1.0, 2.0], [3.0, -4.0]])
        np.testing.assert_allclose(F.relu(x).numpy(),
                                   [[0, 2], [3, 0]], rtol=1e-6)

    def test_softmax_rows_sum_to_one(self):
        x = t(np.random.randn(4, 7))
        s = F.softmax(x).numpy()
        np.testing.assert_allclose(s.sum(-1), np.ones(4), rtol=1e-5)

    def test_gelu_matches_scipy_form(self):
        x = np.linspace(-3, 3, 13).astype(np.float32)
        got = F.gelu(t(x)).numpy()
        from math import erf, sqrt
        want = np.array([0.5 * v * (1 + erf(v / sqrt(2))) for v in x],
                        dtype=np.float32)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_silu_swish(self):
        x = t(np.random.randn(5))
        np.testing.assert_allclose(F.silu(x).numpy(), F.swish(x).numpy())

    def test_activation_grad(self):
        x = t(np.random.randn(3, 3), sg=False)
        y = paddle.sum(F.relu(x) * 2.0)
        y.backward()
        want = np.where(x.numpy() > 0, 2.0, 0.0)
        np.testing.assert_allclose(x.grad.numpy(), want)


class TestLinearEmbedding:
    def test_linear_matches_numpy(self):
        l = nn.Linear(6, 3)
        x = t(np.random.randn(4, 6))
        want = x.numpy() @ l.weight.numpy() + l.bias.numpy()
        np.testing.assert_allclose(l(x).numpy(), want, rtol=1e-5)

    def test_linear_no_bias(self):
        l = nn.Linear(6, 3, bias_attr=False)
        assert l.bias is None

    def test_embedding_lookup_and_padding(self):
        e = nn.Embedding(10, 4, padding_idx=0)
        ids = paddle.to_tensor(np.array([[1, 0, 3]]))
        out = e(ids)
        assert out.shape == [1, 3, 4]
        np.testing.assert_allclose(out.numpy()[0, 1], np.zeros(4))

    def test_embedding_grad_scatters(self):
        e = nn.Embedding(5, 3)
        ids = paddle.to_tensor(np.array([1, 1, 2]))
        out = paddle.sum(e(ids))
        out.backward()
        g = e.weight.grad.numpy()
        np.testing.assert_allclose(g[1], 2 * np.ones(3))
        np.testing.assert_allclose(g[2], np.ones(3))
        np.testing.assert_allclose(g[0], np.zeros(3))


class TestNorms:
    def test_layer_norm_stats(self):
        ln = nn.LayerNorm(16)
        x = t(np.random.randn(4, 16) * 5 + 3)
        y = ln(x).numpy()
        np.testing.assert_allclose(y.mean(-1), np.zeros(4), atol=1e-5)
        np.testing.assert_allclose(y.std(-1), np.ones(4), atol=1e-2)

    def test_rms_norm(self):
        rn = nn.RMSNorm(8)
        x = t(np.random.randn(2, 8))
        y = rn(x).numpy()
        xn = x.numpy()
        want = xn / np.sqrt((xn ** 2).mean(-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(y, want, rtol=1e-4)

    def test_batch_norm_train_updates_stats(self):
        bn = nn.BatchNorm1D(4, data_format="NCL")
        x = t(np.random.randn(8, 4, 5) * 2 + 1)
        bn.train()
        y = bn(x)
        # running stats moved toward batch stats
        assert not np.allclose(bn._mean.numpy(), np.zeros(4))
        bn.eval()
        y2 = bn(x)
        assert y2.shape == x.shape

    def test_group_norm(self):
        gn = nn.GroupNorm(2, 4)
        x = t(np.random.randn(2, 4, 3, 3))
        y = gn(x)
        assert y.shape == x.shape


class TestConvPool:
    def test_conv2d_identity_kernel(self):
        conv = nn.Conv2D(1, 1, 3, padding=1, bias_attr=False)
        w = np.zeros((1, 1, 3, 3), np.float32)
        w[0, 0, 1, 1] = 1.0
        conv.weight.set_value(w)
        x = t(np.random.randn(1, 1, 5, 5))
        np.testing.assert_allclose(conv(x).numpy(), x.numpy(), atol=1e-6)

    def test_conv2d_shape_stride(self):
        conv = nn.Conv2D(3, 8, 3, stride=2, padding=1)
        x = t(np.random.randn(2, 3, 8, 8))
        assert conv(x).shape == [2, 8, 4, 4]

    def test_conv2d_groups(self):
        conv = nn.Conv2D(4, 8, 3, groups=2, padding=1)
        x = t(np.random.randn(1, 4, 6, 6))
        assert conv(x).shape == [1, 8, 6, 6]

    def test_conv_transpose_shape(self):
        convt = nn.Conv2DTranspose(3, 6, 4, stride=2, padding=1)
        x = t(np.random.randn(2, 3, 8, 8))
        assert convt(x).shape == [2, 6, 16, 16]

    def test_conv1d(self):
        conv = nn.Conv1D(2, 4, 3, padding=1)
        x = t(np.random.randn(2, 2, 10))
        assert conv(x).shape == [2, 4, 10]

    def test_max_pool(self):
        x = t(np.arange(16).reshape(1, 1, 4, 4))
        y = F.max_pool2d(x, kernel_size=2)
        np.testing.assert_allclose(y.numpy()[0, 0],
                                   [[5, 7], [13, 15]])

    def test_avg_pool(self):
        x = t(np.ones((1, 1, 4, 4)))
        y = F.avg_pool2d(x, kernel_size=2)
        np.testing.assert_allclose(y.numpy(), np.ones((1, 1, 2, 2)))

    def test_avg_pool_inclusive_ceil(self):
        # exclusive=False counts padding cells in the divisor, but never the
        # ceil_mode extension (reference pooling kernel semantics).
        x = t(np.ones((1, 1, 4, 4)))
        y = F.avg_pool2d(x, kernel_size=2, stride=2, padding=1,
                         exclusive=False, ceil_mode=True)
        # corner window: 1 real + 3 pad cells -> 1/4
        assert y.shape == [1, 1, 3, 3]
        np.testing.assert_allclose(y.numpy()[0, 0, 0, 0], 0.25)
        np.testing.assert_allclose(y.numpy()[0, 0, 1, 1], 1.0)

    def test_avg_pool_exclusive_pad(self):
        x = t(np.ones((1, 1, 4, 4)))
        y = F.avg_pool2d(x, kernel_size=2, stride=2, padding=1,
                         exclusive=True, ceil_mode=True)
        np.testing.assert_allclose(y.numpy()[0, 0], np.ones((3, 3)))

    def test_adaptive_avg_pool(self):
        x = t(np.random.randn(2, 3, 8, 8))
        y = F.adaptive_avg_pool2d(x, output_size=1)
        np.testing.assert_allclose(
            y.numpy()[..., 0, 0], x.numpy().mean((-1, -2)), rtol=1e-5)

    def test_conv_grad(self):
        conv = nn.Conv2D(1, 2, 3)
        x = t(np.random.randn(1, 1, 5, 5), sg=False)
        loss = paddle.sum(conv(x) ** 2)
        loss.backward()
        assert conv.weight.grad is not None
        assert x.grad.shape == x.shape


class TestDropout:
    def test_eval_is_identity(self):
        d = nn.Dropout(0.5)
        d.eval()
        x = t(np.random.randn(10, 10))
        np.testing.assert_allclose(d(x).numpy(), x.numpy())

    def test_train_zeroes_and_scales(self):
        paddle.seed(0)
        d = nn.Dropout(0.5)
        x = t(np.ones((100, 100)))
        y = d(x).numpy()
        assert (y == 0).mean() > 0.3
        nz = y[y != 0]
        np.testing.assert_allclose(nz, 2 * np.ones_like(nz))

    def test_dropout2d_channelwise(self):
        paddle.seed(0)
        x = t(np.ones((4, 8, 5, 5)))
        y = F.dropout2d(x, p=0.5, training=True).numpy()
        flat = y.reshape(4, 8, -1)
        for b in range(4):
            for c in range(8):
                ch = flat[b, c]
                assert (ch == 0).all() or (ch == 2).all()


class TestLosses:
    def test_cross_entropy_matches_manual(self):
        logits = np.random.randn(6, 5).astype(np.float32)
        labels = np.array([0, 1, 2, 3, 4, 0])
        got = float(F.cross_entropy(t(logits), paddle.to_tensor(labels)))
        e = np.exp(logits - logits.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        want = -np.log(p[np.arange(6), labels]).mean()
        assert abs(got - want) < 1e-5

    def test_cross_entropy_ignore_index(self):
        logits = np.random.randn(4, 3).astype(np.float32)
        labels = np.array([0, -100, 2, -100])
        got = float(F.cross_entropy(t(logits), paddle.to_tensor(labels)))
        e = np.exp(logits - logits.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        want = -np.log(p[[0, 2], [0, 2]]).mean()
        assert abs(got - want) < 1e-5

    def test_mse(self):
        a, b = np.random.randn(5), np.random.randn(5)
        got = float(F.mse_loss(t(a), t(b)))
        assert abs(got - ((a - b) ** 2).mean()) < 1e-6

    def test_bce_with_logits(self):
        z = np.random.randn(8).astype(np.float32)
        y = (np.random.rand(8) > 0.5).astype(np.float32)
        got = float(F.binary_cross_entropy_with_logits(t(z), t(y)))
        p = 1 / (1 + np.exp(-z))
        want = -(y * np.log(p) + (1 - y) * np.log(1 - p)).mean()
        assert abs(got - want) < 1e-5

    def test_kl_div(self):
        logp = np.log(np.array([[0.2, 0.8]], dtype=np.float32))
        target = np.array([[0.5, 0.5]], dtype=np.float32)
        got = float(F.kl_div(t(logp), t(target), reduction="sum"))
        want = (target * (np.log(target) - logp)).sum()
        assert abs(got - want) < 1e-5

    def test_loss_layers(self):
        ce = nn.CrossEntropyLoss()
        out = ce(t(np.random.randn(3, 4)), paddle.to_tensor([0, 1, 2]))
        assert out.shape == []
        sl = nn.SmoothL1Loss()
        assert sl(t([1.0, 2.0]), t([1.5, 0.0])).shape == []


class TestAttentionTransformer:
    def test_sdpa_matches_manual(self):
        B, S, H, D = 2, 4, 2, 8
        q = np.random.randn(B, S, H, D).astype(np.float32)
        k = np.random.randn(B, S, H, D).astype(np.float32)
        v = np.random.randn(B, S, H, D).astype(np.float32)
        got = F.scaled_dot_product_attention(t(q), t(k), t(v)).numpy()
        # manual
        qt, kt, vt = [a.transpose(0, 2, 1, 3) for a in (q, k, v)]
        logits = qt @ kt.transpose(0, 1, 3, 2) / np.sqrt(D)
        e = np.exp(logits - logits.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        want = (p @ vt).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_causal_masking(self):
        B, S, H, D = 1, 5, 1, 4
        q = np.random.randn(B, S, H, D).astype(np.float32)
        k = np.random.randn(B, S, H, D).astype(np.float32)
        v = np.random.randn(B, S, H, D).astype(np.float32)
        out = F.scaled_dot_product_attention(
            t(q), t(k), t(v), is_causal=True).numpy()
        # first position attends only to itself
        np.testing.assert_allclose(out[0, 0, 0], v[0, 0, 0], rtol=1e-5)

    def test_encoder_layer(self):
        layer = nn.TransformerEncoderLayer(16, 4, 32, dropout=0.0)
        enc = nn.TransformerEncoder(layer, 2)
        x = t(np.random.randn(2, 6, 16))
        assert enc(x).shape == [2, 6, 16]

    def test_full_transformer(self):
        m = nn.Transformer(d_model=16, nhead=2, num_encoder_layers=1,
                           num_decoder_layers=1, dim_feedforward=32,
                           dropout=0.0)
        src = t(np.random.randn(2, 5, 16))
        tgt = t(np.random.randn(2, 3, 16))
        assert m(src, tgt).shape == [2, 3, 16]


class TestRNN:
    def test_lstm_shapes(self):
        lstm = nn.LSTM(4, 8, num_layers=2)
        x = t(np.random.randn(3, 6, 4))
        out, (h, c) = lstm(x)
        assert out.shape == [3, 6, 8]
        assert h.shape == [2, 3, 8]
        assert c.shape == [2, 3, 8]

    def test_gru_bidirect(self):
        gru = nn.GRU(4, 8, direction="bidirect")
        x = t(np.random.randn(3, 6, 4))
        out, h = gru(x)
        assert out.shape == [3, 6, 16]
        assert h.shape == [2, 3, 8]

    def test_lstm_grad(self):
        lstm = nn.LSTM(4, 8)
        x = t(np.random.randn(2, 5, 4), sg=False)
        out, _ = lstm(x)
        paddle.sum(out).backward()
        assert x.grad.shape == x.shape
        assert lstm.weight_ih_l0.grad is not None

    def test_lstm_cell_consistency(self):
        """Fused scan must equal stepwise cell application."""
        paddle.seed(42)
        lstm = nn.LSTM(3, 5)
        cell = nn.LSTMCell(3, 5)
        cell.weight_ih.set_value(lstm.weight_ih_l0.numpy())
        cell.weight_hh.set_value(lstm.weight_hh_l0.numpy())
        cell.bias_ih.set_value(lstm.bias_ih_l0.numpy())
        cell.bias_hh.set_value(lstm.bias_hh_l0.numpy())
        x = t(np.random.randn(2, 4, 3))
        out, _ = lstm(x)
        h = c = paddle.zeros([2, 5])
        ys = []
        state = (h, c)
        for i in range(4):
            y, state = cell(x[:, i], state)
            ys.append(y.numpy())
        np.testing.assert_allclose(out.numpy(),
                                   np.stack(ys, 1), rtol=1e-4, atol=1e-5)


class TestLayerMechanics:
    def test_state_dict_roundtrip(self):
        m1 = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        m2 = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        m2.set_state_dict(m1.state_dict())
        x = t(np.random.randn(3, 4))
        np.testing.assert_allclose(m1(x).numpy(), m2(x).numpy())

    def test_named_parameters(self):
        m = nn.Sequential(nn.Linear(2, 2), nn.Linear(2, 2))
        names = dict(m.named_parameters())
        assert "0.weight" in names and "1.bias" in names

    def test_train_eval_propagates(self):
        m = nn.Sequential(nn.Linear(2, 2), nn.Dropout(0.5))
        m.eval()
        assert not m[1].training
        m.train()
        assert m[1].training

    def test_apply_and_sublayers(self):
        m = nn.Sequential(nn.Linear(2, 2), nn.Sequential(nn.Linear(2, 2)))
        count = []
        m.apply(lambda l: count.append(type(l).__name__))
        assert "Linear" in count and len(count) >= 4

    def test_forward_hooks(self):
        l = nn.Linear(2, 2)
        calls = []
        h = l.register_forward_post_hook(
            lambda layer, inp, out: calls.append(1))
        l(t(np.ones((1, 2))))
        assert calls == [1]
        h.remove()
        l(t(np.ones((1, 2))))
        assert calls == [1]

    def test_layer_to_dtype(self):
        import jax.numpy as jnp
        l = nn.Linear(2, 2)
        l.to(dtype="bfloat16")
        assert l.weight.dtype == jnp.bfloat16

    def test_containers(self):
        ll = nn.LayerList([nn.Linear(2, 2) for _ in range(3)])
        assert len(ll) == 3
        ll.append(nn.Linear(2, 2))
        assert len(ll) == 4
        ld = nn.LayerDict({"a": nn.Linear(2, 2)})
        assert "a" in ld

    def test_buffers_in_state_dict(self):
        bn = nn.BatchNorm1D(3, data_format="NCL")
        sd = bn.state_dict()
        assert "_mean" in sd and "_variance" in sd


class TestParityFixes:
    """Regression tests for Paddle-parity parameters that are easy to drop
    silently (found via review): ceil_mode, padding_mode, output_size,
    sequence_length, dropout downscale mode, gumbel sampling."""

    def test_ceil_mode_shapes(self):
        x = t(np.random.randn(1, 1, 6, 6))
        assert F.max_pool2d(x, kernel_size=3, stride=2,
                            ceil_mode=True).shape == [1, 1, 3, 3]
        assert F.max_pool2d(x, kernel_size=3, stride=2).shape == [1, 1, 2, 2]
        ya = F.avg_pool2d(t(np.ones((1, 1, 6, 6))), kernel_size=3, stride=2,
                          ceil_mode=True)
        np.testing.assert_allclose(ya.numpy(), np.ones((1, 1, 3, 3)))

    def test_conv_transpose_output_size(self):
        x = t(np.random.randn(1, 2, 7, 7))
        convt = nn.Conv2DTranspose(2, 3, 3, stride=2, padding=1)
        assert convt(x, output_size=[14, 14]).shape == [1, 3, 14, 14]
        assert convt(x).shape == [1, 3, 13, 13]

    def test_conv_padding_mode_reflect(self):
        c = nn.Conv2D(1, 1, 3, padding=1, padding_mode="reflect",
                      bias_attr=False)
        xi = t(np.random.randn(1, 1, 5, 5))
        want = F.conv2d(F.pad(xi, [1, 1, 1, 1], mode="reflect"), c.weight,
                        stride=1, padding=0).numpy()
        np.testing.assert_allclose(c(xi).numpy(), want, rtol=1e-5)

    def test_dropout_downscale_in_infer(self):
        x = t(np.ones(10))
        y = F.dropout(x, p=0.5, training=False, mode="downscale_in_infer")
        np.testing.assert_allclose(y.numpy(), 0.5 * np.ones(10))

    def test_gumbel_softmax_samples(self):
        paddle.seed(3)
        logits = t(np.zeros((4, 8)))
        g1 = F.gumbel_softmax(logits, hard=True).numpy()
        g2 = F.gumbel_softmax(logits, hard=True).numpy()
        assert not np.allclose(g1, g2)
        np.testing.assert_allclose(g1.sum(-1), np.ones(4))

    def test_lstm_sequence_length(self):
        paddle.seed(4)
        lstm = nn.LSTM(3, 5)
        xfull = np.random.randn(2, 6, 3).astype(np.float32)
        lens = paddle.to_tensor(np.array([4, 6], np.int32))
        out, (h, c) = lstm(t(xfull), sequence_length=lens)
        out_p, (h_p, c_p) = lstm(t(xfull[:, :4]))
        np.testing.assert_allclose(h.numpy()[0, 0], h_p.numpy()[0, 0],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(out.numpy()[0, 4:], np.zeros((2, 5)),
                                   atol=1e-6)

    def test_gru_bidirect_sequence_length(self):
        paddle.seed(4)
        gru = nn.GRU(3, 4, direction="bidirect")
        xfull = np.random.randn(2, 6, 3).astype(np.float32)
        lens = paddle.to_tensor(np.array([4, 6], np.int32))
        ob, hb = gru(t(xfull), sequence_length=lens)
        ob_p, hb_p = gru(t(xfull[:, :4]))
        np.testing.assert_allclose(ob.numpy()[0, :4], ob_p.numpy()[0],
                                   rtol=1e-5, atol=1e-6)


class TestVarlenAttention:
    def test_unpadded_matches_per_sequence(self):
        """Packed ragged attention == per-sequence dense attention."""
        import paddle_tpu.nn.functional as F
        rng = np.random.default_rng(0)
        lens = [5, 3, 8]
        T, H, D = sum(lens), 2, 16
        q = rng.normal(size=(T, H, D)).astype("float32")
        k = rng.normal(size=(T, H, D)).astype("float32")
        v = rng.normal(size=(T, H, D)).astype("float32")
        cu = np.cumsum([0] + lens).astype("int32")
        out, _ = F.flash_attn_unpadded(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            paddle.to_tensor(cu), paddle.to_tensor(cu), causal=True)
        out = np.asarray(out.numpy())
        import jax.numpy as jnp
        for i, L in enumerate(lens):
            lo, hi = cu[i], cu[i + 1]
            ref = F.sdpa_reference(jnp.asarray(q[None, lo:hi]),
                                   jnp.asarray(k[None, lo:hi]),
                                   jnp.asarray(v[None, lo:hi]), causal=True)
            np.testing.assert_allclose(out[lo:hi], np.asarray(ref)[0],
                                       rtol=1e-4, atol=1e-5)

    def test_padding_tokens_zero(self):
        import paddle_tpu.nn.functional as F
        rng = np.random.default_rng(1)
        T, H, D = 8, 1, 8
        q = rng.normal(size=(T, H, D)).astype("float32")
        cu = np.array([0, 5], "int32")   # tokens 5..7 are padding
        out, _ = F.flash_attn_unpadded(
            paddle.to_tensor(q), paddle.to_tensor(q), paddle.to_tensor(q),
            paddle.to_tensor(cu), paddle.to_tensor(cu))
        np.testing.assert_allclose(np.asarray(out.numpy())[5:], 0.0)

    def test_segment_ids(self):
        import paddle_tpu.nn.functional as F
        import jax.numpy as jnp
        seg = F.segment_ids_from_cu_seqlens(jnp.array([0, 2, 5]), 7)
        np.testing.assert_array_equal(np.asarray(seg),
                                      [0, 0, 1, 1, 1, -1, -1])

    def test_varlen_grad_flows(self):
        import paddle_tpu.nn.functional as F
        rng = np.random.default_rng(2)
        q = paddle.to_tensor(rng.normal(size=(6, 1, 8)).astype("float32"),
                             stop_gradient=False)
        cu = paddle.to_tensor(np.array([0, 3, 6], "int32"))
        out, _ = F.flash_attn_unpadded(q, q, q, cu, cu, causal=True)
        out.sum().backward()
        assert np.isfinite(q.grad.numpy()).all()

    def test_varlen_causal_differing_cu_seqlens(self):
        """Causal masking is SEGMENT-LOCAL: q and k prefix sums differ."""
        import jax.numpy as jnp
        import paddle_tpu.nn.functional as F
        rng = np.random.default_rng(4)
        lens_q, lens_k = [2, 2], [3, 3]
        cq = np.cumsum([0] + lens_q).astype("int32")
        ck = np.cumsum([0] + lens_k).astype("int32")
        H, D = 1, 8
        q = rng.normal(size=(sum(lens_q), H, D)).astype("float32")
        k = rng.normal(size=(sum(lens_k), H, D)).astype("float32")
        v = rng.normal(size=(sum(lens_k), H, D)).astype("float32")
        out, _ = F.flash_attn_unpadded(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            paddle.to_tensor(cq), paddle.to_tensor(ck), causal=True)
        out = np.asarray(out.numpy())
        assert np.abs(out).max() > 0      # no fully-masked rows
        # per-sequence reference with local causal alignment
        for i in range(2):
            qs = q[cq[i]:cq[i+1]]
            ks = k[ck[i]:ck[i+1]]
            vs = v[ck[i]:ck[i+1]]
            s = np.einsum("qhd,khd->hqk", qs, ks) / np.sqrt(D)
            mask = np.arange(len(qs))[:, None] >= np.arange(len(ks))[None, :]
            s = np.where(mask[None], s, -1e30)
            p = np.exp(s - s.max(-1, keepdims=True))
            p = p / p.sum(-1, keepdims=True)
            ref = np.einsum("hqk,khd->qhd", p, vs)
            np.testing.assert_allclose(out[cq[i]:cq[i+1]], ref,
                                       rtol=1e-4, atol=1e-5)

    def test_varlen_unsupported_options_raise(self):
        import paddle_tpu.nn.functional as F
        q = paddle.to_tensor(np.zeros((4, 1, 8), "float32"))
        cu = paddle.to_tensor(np.array([0, 4], "int32"))
        with pytest.raises(NotImplementedError, match="dropout"):
            F.flash_attn_unpadded(q, q, q, cu, cu, dropout=0.1)
        with pytest.raises(NotImplementedError, match="softmax"):
            F.flash_attn_unpadded(q, q, q, cu, cu, return_softmax=True)


class TestSdpKernelRestore:
    """ADVICE-r4: sdp_kernel(enable_flash=False) must restore the exact
    dispatcher installed on entry, not clobber it with a fresh
    default registration."""

    def test_restores_prior_impl_verbatim(self):
        import paddle_tpu.nn.functional as F
        from paddle_tpu.nn.functional import attention as att

        prev = att._FLASH_IMPL
        try:
            sentinel = lambda *a, **k: None
            att.register_flash_impl(sentinel)
            with F.sdp_kernel(enable_flash=False):
                assert att._FLASH_IMPL is None
            assert att._FLASH_IMPL is sentinel
            # deliberately-unregistered state also survives
            att.register_flash_impl(None)
            with F.sdp_kernel(enable_flash=False):
                pass
            assert att._FLASH_IMPL is None
        finally:
            att.register_flash_impl(prev)
