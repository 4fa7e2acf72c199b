"""Tests for the API-surface completion sweep: top-level misc ops,
framework compat surface, unpool/fractional pool, sequence losses
(CTC/RNN-T), hsigmoid, margin losses, beam search decode.

Torch (CPU) is used as the parity oracle where it implements the same
op; otherwise numpy references.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def t(x, **kw):
    return paddle.to_tensor(x, **kw)


class TestTopLevelMisc:
    def test_stacks(self):
        x = np.arange(4, dtype="float32")
        a, b = t(x), t(x + 4)
        assert paddle.hstack([a, b]).shape == [8]
        assert paddle.vstack([a, b]).shape == [2, 4]
        assert paddle.row_stack([a, b]).shape == [2, 4]
        assert paddle.column_stack([a, b]).shape == [4, 2]
        m = t(x.reshape(2, 2))
        assert paddle.dstack([m, m]).shape == [2, 2, 2]

    def test_combinations(self):
        x = t(np.arange(4, dtype="float32"))
        c = paddle.combinations(x)
        assert c.shape == [6, 2]
        want = [[0, 1], [0, 2], [0, 3], [1, 2], [1, 3], [2, 3]]
        np.testing.assert_array_equal(np.asarray(c.numpy()), want)
        cr = paddle.combinations(x, 2, with_replacement=True)
        assert cr.shape == [10, 2]

    def test_pdist(self):
        import scipy.spatial.distance as ssd

        a = np.random.default_rng(0).random((6, 3)).astype("float32")
        np.testing.assert_allclose(np.asarray(paddle.pdist(t(a)).numpy()),
                                   ssd.pdist(a), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(paddle.pdist(t(a), p=1.0).numpy()),
            ssd.pdist(a, "minkowski", p=1), rtol=1e-5)

    def test_random_ops(self):
        x = t(np.zeros((3, 4), "float32"))
        r = paddle.randint_like(x, 5)
        assert r.shape == [3, 4]
        arr = np.asarray(r.numpy())
        assert (arr >= 0).all() and (arr < 5).all()
        b = paddle.binomial(t(np.full(1000, 20.0, "float32")),
                            t(np.full(1000, 0.3, "float32")))
        m = float(np.asarray(b.numpy()).mean())
        assert 5.0 < m < 7.0          # E = 6
        g = paddle.standard_gamma(t(np.full(2000, 3.0, "float32")))
        gm = float(np.asarray(g.numpy()).mean())
        assert 2.5 < gm < 3.5         # E = alpha = 3

    def test_inplace_variants(self):
        x = t([1.0, -2.0])
        x.square_()
        np.testing.assert_allclose(x.numpy(), [1.0, 4.0])
        y = t([0.5])
        paddle.erf_(y)
        np.testing.assert_allclose(np.asarray(y.numpy()),
                                   [0.5204999], rtol=1e-5)
        z = t(np.zeros((3, 2), "float32"))
        z.index_add_(t(np.array([0, 2], "int64")), axis=0,
                     value=t(np.ones((2, 2), "float32")))
        np.testing.assert_allclose(np.asarray(z.numpy()),
                                   [[1, 1], [0, 0], [1, 1]])

    def test_dtype_info_and_places(self):
        assert paddle.finfo(paddle.float32).max > 1e38
        assert paddle.iinfo("int16").max == 32767
        assert paddle.CPUPlace() == paddle.CPUPlace()
        assert paddle.CPUPlace() != paddle.CUDAPlace(0)
        assert paddle.CUDAPlace(0).get_device_id() == 0
        paddle.set_printoptions(precision=4)
        paddle.disable_signal_handler()
        assert paddle.is_grad_enabled()
        assert paddle.bool is paddle.bool_

    def test_batch_reader(self):
        def reader():
            yield from range(7)

        batches = list(paddle.batch(reader, 3)())
        assert batches == [[0, 1, 2], [3, 4, 5], [6]]
        batches = list(paddle.batch(reader, 3, drop_last=True)())
        assert batches == [[0, 1, 2], [3, 4, 5]]

    def test_flops(self):
        net = nn.Linear(8, 4)
        assert paddle.flops(net, (2, 8)) == 2 * 2 * 8 * 4

    def test_check_shape(self):
        paddle.check_shape([2, -1, 3])
        with pytest.raises(ValueError):
            paddle.check_shape([-1, -1])

    def test_lazy_guard(self):
        with paddle.LazyGuard():
            lin = nn.Linear(3, 3)
        assert lin.weight.shape == [3, 3]

    def test_tolist(self):
        assert t([[1.0, 2.0]]).tolist() == [[1.0, 2.0]]


class TestPoolingExtras:
    def test_max_pool_mask_and_unpool_torch_parity(self):
        import torch
        import torch.nn.functional as TF

        x = np.random.default_rng(1).normal(size=(2, 3, 8, 8)) \
            .astype("float32")
        for k, s, p in [(2, 2, 0), (3, 2, 1)]:
            out, mask = F.max_pool2d(t(x), k, s, p, return_mask=True)
            tout, tmask = TF.max_pool2d(torch.tensor(x), k, s, p,
                                        return_indices=True)
            np.testing.assert_allclose(np.asarray(out.numpy()),
                                       tout.numpy(), rtol=1e-6)
            np.testing.assert_array_equal(np.asarray(mask.numpy()),
                                          tmask.numpy())
            osz = (8, 8) if p else None
            up = F.max_unpool2d(out, mask, k, s, p, output_size=osz)
            tup = TF.max_unpool2d(tout, tmask, k, s, p, output_size=osz)
            np.testing.assert_allclose(np.asarray(up.numpy()),
                                       tup.numpy(), rtol=1e-6)

    def test_max_pool1d_3d_mask(self):
        import torch
        import torch.nn.functional as TF

        x1 = np.random.default_rng(2).normal(size=(2, 3, 10)) \
            .astype("float32")
        o1, m1 = F.max_pool1d(t(x1), 2, 2, return_mask=True)
        to1, tm1 = TF.max_pool1d(torch.tensor(x1), 2, 2,
                                 return_indices=True)
        np.testing.assert_array_equal(np.asarray(m1.numpy()), tm1.numpy())
        up = F.max_unpool1d(o1, m1, 2, 2)
        np.testing.assert_allclose(np.asarray(up.numpy()),
                                   TF.max_unpool1d(to1, tm1, 2, 2).numpy())
        x3 = np.random.default_rng(3).normal(size=(1, 2, 6, 6, 6)) \
            .astype("float32")
        o3, m3 = F.max_pool3d(t(x3), 2, 2, return_mask=True)
        to3, tm3 = TF.max_pool3d(torch.tensor(x3), 2, 2,
                                 return_indices=True)
        np.testing.assert_array_equal(np.asarray(m3.numpy()), tm3.numpy())

    def test_unpool_layers(self):
        x = np.random.default_rng(4).normal(size=(1, 2, 6, 6)) \
            .astype("float32")
        out, mask = F.max_pool2d(t(x), 2, 2, return_mask=True)
        up = nn.MaxUnPool2D(2, 2)(out, mask)
        assert up.shape == [1, 2, 6, 6]
        # every pooled max lands back at its argmax position
        total = np.asarray(up.numpy()).sum()
        np.testing.assert_allclose(total, np.asarray(out.numpy()).sum(),
                                   rtol=1e-6)

    def test_fractional_max_pool(self):
        x = np.random.default_rng(5).normal(size=(2, 3, 9, 9)) \
            .astype("float32")
        out = F.fractional_max_pool2d(t(x), 3, random_u=0.3)
        assert out.shape == [2, 3, 3, 3]
        # every output is the max of SOME window, so must appear in input
        assert np.isin(np.asarray(out.numpy()),
                       np.asarray(x)).all()
        out, mask = F.fractional_max_pool2d(t(x), 3, random_u=0.3,
                                            return_mask=True)
        flat = np.asarray(x).reshape(2, 3, -1)
        gathered = np.take_along_axis(
            flat, np.asarray(mask.numpy()).reshape(2, 3, -1), axis=2)
        np.testing.assert_allclose(gathered.reshape(2, 3, 3, 3),
                                   np.asarray(out.numpy()), rtol=1e-6)
        o3 = F.fractional_max_pool3d(t(np.random.default_rng(6).normal(
            size=(1, 2, 8, 8, 8)).astype("float32")), 2, random_u=0.5)
        assert o3.shape == [1, 2, 2, 2, 2]
        # kernel_size-pinned variant + layer classes
        ok = F.fractional_max_pool2d(t(x), 3, kernel_size=2, random_u=0.4)
        assert ok.shape == [2, 3, 3, 3]
        assert nn.FractionalMaxPool2D(3, random_u=0.2)(t(x)).shape == \
            [2, 3, 3, 3]


class TestSequenceLosses:
    def test_ctc_torch_parity(self):
        import torch

        rng = np.random.default_rng(0)
        T, N, C, S = 12, 3, 6, 4
        logits = rng.normal(size=(T, N, C)).astype("float32")
        labels = rng.integers(1, C, (N, S)).astype("int32")
        ilen = np.array([12, 10, 8], "int32")
        llen = np.array([4, 3, 2], "int32")
        ours = F.ctc_loss(t(logits), t(labels), t(ilen), t(llen),
                          blank=0, reduction="none")
        tl = torch.nn.functional.ctc_loss(
            torch.log_softmax(torch.tensor(logits), -1),
            torch.tensor(labels.astype("int64")),
            torch.tensor(ilen.astype("int64")),
            torch.tensor(llen.astype("int64")), blank=0, reduction="none")
        np.testing.assert_allclose(np.asarray(ours.numpy()), tl.numpy(),
                                   rtol=1e-4)

    def test_ctc_grad_and_layer(self):
        rng = np.random.default_rng(1)
        logits = rng.normal(size=(6, 2, 5)).astype("float32")
        labels = rng.integers(1, 5, (2, 3)).astype("int32")
        lt = t(logits, stop_gradient=False)
        loss = nn.CTCLoss()(lt, t(labels), t(np.array([6, 5], "int32")),
                            t(np.array([3, 2], "int32")))
        loss.backward()
        g = np.asarray(lt.grad.numpy())
        assert np.isfinite(g).all() and np.abs(g).sum() > 0

    def test_rnnt_dp_parity(self):
        import scipy.special as sp

        rng = np.random.default_rng(1)
        B, T, U, V = 2, 5, 3, 4
        logits = rng.normal(size=(B, T, U + 1, V)).astype("float32")
        label = rng.integers(1, V, (B, U)).astype("int32")
        ilen = np.array([5, 4], "int32")
        llen = np.array([3, 2], "int32")

        def ref(lp, lab, Tb, Ub, blank=0):
            lp = sp.log_softmax(lp, axis=-1)
            alpha = np.full((Tb, Ub + 1), -np.inf)
            alpha[0, 0] = 0.0
            for ti in range(Tb):
                for u in range(Ub + 1):
                    if ti == 0 and u == 0:
                        continue
                    c = []
                    if ti > 0:
                        c.append(alpha[ti - 1, u] + lp[ti - 1, u, blank])
                    if u > 0:
                        c.append(alpha[ti, u - 1] + lp[ti, u - 1, lab[u - 1]])
                    alpha[ti, u] = sp.logsumexp(c)
            return -(alpha[Tb - 1, Ub] + lp[Tb - 1, Ub, blank])

        want = [ref(logits[b], label[b], ilen[b], llen[b]) for b in range(B)]
        ours = F.rnnt_loss(t(logits), t(label), t(ilen), t(llen),
                           blank=0, reduction="none")
        np.testing.assert_allclose(np.asarray(ours.numpy()), want,
                                   rtol=1e-4)
        lt = t(logits, stop_gradient=False)
        loss = nn.RNNTLoss()(lt, t(label), t(ilen), t(llen))
        loss.backward()
        assert np.isfinite(np.asarray(lt.grad.numpy())).all()


class TestMarginAndTreeLosses:
    def test_multi_margin_torch_parity(self):
        import torch

        rng = np.random.default_rng(2)
        x = rng.normal(size=(5, 7)).astype("float32")
        y = rng.integers(0, 7, (5,)).astype("int64")
        for p, margin in [(1, 1.0), (2, 0.5)]:
            ours = F.multi_margin_loss(t(x), t(y), p=p, margin=margin)
            tl = torch.nn.functional.multi_margin_loss(
                torch.tensor(x), torch.tensor(y), p=p, margin=margin)
            np.testing.assert_allclose(float(ours.numpy()), tl.item(),
                                       rtol=1e-5)
        assert nn.MultiMarginLoss()(t(x), t(y)).shape == []

    def test_triplet_with_distance_torch_parity(self):
        import torch

        rng = np.random.default_rng(3)
        a, pos, neg = (rng.normal(size=(4, 8)).astype("float32")
                       for _ in range(3))
        ours = F.triplet_margin_with_distance_loss(t(a), t(pos), t(neg),
                                                   margin=1.0)
        tl = torch.nn.functional.triplet_margin_with_distance_loss(
            torch.tensor(a), torch.tensor(pos), torch.tensor(neg),
            margin=1.0)
        np.testing.assert_allclose(float(ours.numpy()), tl.item(),
                                   rtol=1e-4)
        # custom distance fn keeps autograd
        at = t(a, stop_gradient=False)
        loss = F.triplet_margin_with_distance_loss(
            at, t(pos), t(neg),
            distance_function=lambda u, v: ((u - v) ** 2).sum(axis=-1))
        loss.backward()
        assert np.abs(np.asarray(at.grad.numpy())).sum() > 0
        assert nn.TripletMarginWithDistanceLoss(swap=True)(
            t(a), t(pos), t(neg)).shape == []

    def test_hsigmoid(self):
        rng = np.random.default_rng(4)
        x = rng.normal(size=(5, 6)).astype("float32")
        y = rng.integers(0, 8, (5,)).astype("int64")
        layer = nn.HSigmoidLoss(6, 8)
        loss = layer(t(x), t(y))
        assert loss.shape == [5, 1]
        assert (np.asarray(loss.numpy()) > 0).all()
        # custom path: two classes, single internal node
        pt = np.zeros((5, 1), "int64")
        pc = (y % 2).reshape(5, 1).astype("int64")
        w = rng.normal(size=(1, 6)).astype("float32")
        l2 = F.hsigmoid_loss(t(x), t(y), 2, t(w), path_table=t(pt),
                             path_code=t(pc))
        s = x @ w[0]
        want = np.log1p(np.exp(s)) - pc[:, 0] * s
        np.testing.assert_allclose(np.asarray(l2.numpy())[:, 0], want,
                                   rtol=1e-4)

    def test_margin_cross_entropy(self):
        rng = np.random.default_rng(5)
        feats = rng.normal(size=(6, 9)).astype("float32")
        cos = (feats / np.linalg.norm(feats, axis=1, keepdims=True)) @ \
            np.eye(9, 4, dtype="float32")
        label = rng.integers(0, 4, (6,)).astype("int64")
        loss, sm = F.margin_cross_entropy(t(cos), t(label),
                                          return_softmax=True,
                                          reduction=None)
        assert loss.shape == [6, 1] and sm.shape == [6, 4]
        # m1=1, m2=0, m3=0, scale=1 degenerates to plain softmax CE
        import scipy.special as sp

        plain, _ = F.margin_cross_entropy(
            t(cos), t(label), margin1=1.0, margin2=0.0, margin3=0.0,
            scale=1.0, return_softmax=True, reduction=None)
        want = -sp.log_softmax(cos, axis=1)[np.arange(6), label]
        np.testing.assert_allclose(np.asarray(plain.numpy())[:, 0], want,
                                   rtol=1e-4)

    def test_class_center_sample(self):
        label = t(np.array([1, 5, 5, 7], "int64"))
        remapped, sampled = F.class_center_sample(label, 20, 6)
        s = np.asarray(sampled.numpy())
        r = np.asarray(remapped.numpy())
        assert len(s) == 6
        assert {1, 5, 7} <= set(s.tolist())
        # remapped labels index into sampled
        np.testing.assert_array_equal(s[r], np.array([1, 5, 5, 7]))


class TestSequenceUtils:
    def test_sequence_mask(self):
        m = F.sequence_mask(t(np.array([2, 0, 3], "int64")), maxlen=4,
                            dtype="int32")
        np.testing.assert_array_equal(
            np.asarray(m.numpy()),
            [[1, 1, 0, 0], [0, 0, 0, 0], [1, 1, 1, 0]])

    def test_temporal_shift(self):
        x = np.arange(2 * 2 * 4 * 1 * 1, dtype="float32") \
            .reshape(4, 4, 1, 1)   # N=2, T=2, C=4
        out = F.temporal_shift(t(x), seg_num=2, shift_ratio=0.25)
        o = np.asarray(out.numpy()).reshape(2, 2, 4)
        xr = x.reshape(2, 2, 4)
        # channel 0: shifted backward (t gets t+1); last step zero
        np.testing.assert_allclose(o[:, 0, 0], xr[:, 1, 0])
        np.testing.assert_allclose(o[:, 1, 0], 0.0)
        # channel 1: shifted forward; first step zero
        np.testing.assert_allclose(o[:, 0, 1], 0.0)
        np.testing.assert_allclose(o[:, 1, 1], xr[:, 0, 1])
        # channels 2..: unchanged
        np.testing.assert_allclose(o[:, :, 2:], xr[:, :, 2:])

    def test_gather_tree(self):
        ids = t(np.array([[[2, 2]], [[3, 4]], [[5, 6]]], "int64"))
        parents = t(np.array([[[0, 0]], [[1, 0]], [[1, 0]]], "int64"))
        out = F.gather_tree(ids, parents)
        # beam 0 at final step came from parent chain 1 -> 0
        np.testing.assert_array_equal(
            np.asarray(out.numpy())[:, 0, 0], [2, 4, 5])

    def test_sparse_attention(self):
        rng = np.random.default_rng(6)
        B, H, S, D = 1, 2, 4, 8
        q, k, v = (rng.normal(size=(B, H, S, D)).astype("float32")
                   for _ in range(3))
        # full CSR = dense attention
        offset = np.tile(np.arange(S + 1, dtype="int32") * S, (B, H, 1))
        cols = np.tile(np.tile(np.arange(S, dtype="int32"), S), (B, H, 1))
        out = F.sparse_attention(t(q), t(k), t(v), t(offset), t(cols))
        logits = q @ k.transpose(0, 1, 3, 2) / np.sqrt(D)
        import scipy.special as sp

        want = sp.softmax(logits, axis=-1) @ v
        np.testing.assert_allclose(np.asarray(out.numpy()), want,
                                   rtol=1e-4)


class TestAttentionWrappers:
    def test_qkvpacked(self):
        rng = np.random.default_rng(7)
        qkv = rng.normal(size=(2, 6, 3, 2, 8)).astype("float32")
        out, _ = F.flash_attn_qkvpacked(t(qkv), causal=True)
        want, _ = F.flash_attention(t(qkv[:, :, 0]), t(qkv[:, :, 1]),
                                    t(qkv[:, :, 2]), causal=True)
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   np.asarray(want.numpy()), rtol=1e-5)

    def test_varlen_qkvpacked(self):
        rng = np.random.default_rng(8)
        qkv = rng.normal(size=(6, 3, 2, 8)).astype("float32")
        cu = np.array([0, 2, 6], "int32")
        out, _ = F.flash_attn_varlen_qkvpacked(t(qkv), t(cu), t(cu), 4, 4,
                                               None)
        assert out.shape == [6, 2, 8]

    def test_sparse_mask_flash(self):
        rng = np.random.default_rng(9)
        q, k, v = (rng.normal(size=(1, 4, 2, 8)).astype("float32")
                   for _ in range(3))
        starts = np.full((1, 2, 4), 4, "int32")   # nothing masked
        out, _ = F.flash_attention_with_sparse_mask(
            t(q), t(k), t(v), t(starts), is_causal=True)
        want, _ = F.flash_attention(t(q), t(k), t(v), causal=True)
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   np.asarray(want.numpy()), rtol=1e-5,
                                   atol=1e-6)


class TestInplaceActivations:
    def test_inplace_acts(self):
        x = t([-1.0, 2.0])
        F.relu_(x)
        np.testing.assert_allclose(np.asarray(x.numpy()), [0.0, 2.0])
        y = t([-1.0, 0.5])
        F.tanh_(y)
        np.testing.assert_allclose(np.asarray(y.numpy()),
                                   np.tanh([-1.0, 0.5]), rtol=1e-6)
        z = t([[1.0, 1.0]])
        F.softmax_(z)
        np.testing.assert_allclose(np.asarray(z.numpy()), [[0.5, 0.5]])


class TestBeamSearch:
    def test_beam_search_decode(self):
        """A deterministic 'cell' whose logits always prefer token 2, end
        token 3 — beam search must emit 2s then finish on 3."""
        vocab, beam = 5, 2

        class DummyCell:
            def __call__(self, inputs, states):
                # states: running count tensor [B*W, 1]
                cnt = states
                logits = np.full((cnt.shape[0], vocab), -5.0, "float32")
                n = np.asarray(cnt.numpy())[:, 0]
                logits[:, 2] = 2.0
                logits[n >= 2, 3] = 8.0      # after 2 steps, prefer EOS
                return paddle.to_tensor(logits), cnt + 1

        dec = nn.BeamSearchDecoder(DummyCell(), start_token=0, end_token=3,
                                   beam_size=beam)
        init = paddle.to_tensor(np.zeros((1, 1), "float32"))
        out, states = nn.dynamic_decode(dec, inits=init, max_step_num=8)
        ids = np.asarray(out.predicted_ids.numpy())   # [B, T, W]
        assert ids.shape[0] == 1 and ids.shape[2] == beam
        best = ids[0, :, 0]
        assert best[0] == 2 and 3 in best.tolist()

    def test_rnn_cell_base_exported(self):
        assert issubclass(nn.SimpleRNNCell, nn.RNNCellBase)
        assert issubclass(nn.LSTMCell, nn.RNNCellBase)


class TestOpRegistry:
    def test_registry_breadth(self):
        """VERDICT r2 ask: registered-op count >= 500 (primitives via
        @op_fn + composite surface via ops/composite.py)."""
        from paddle_tpu.ops._op import registered_ops

        reg = registered_ops()
        assert len(reg) >= 500, len(reg)
        # every entry is callable and its recorded name resolves in the
        # registry (aliases keep their first name: row_stack -> vstack)
        for name, fn in reg.items():
            assert callable(fn)
            assert getattr(fn, "op_name", name) in reg

    def test_composite_entries_dispatch(self):
        """Composite registry entries are the live API functions."""
        from paddle_tpu.ops._op import get_op

        out = get_op("hstack")([t(np.zeros(2, "float32")),
                                t(np.ones(2, "float32"))])
        assert out.shape == [4]
        assert get_op("allclose") is not None
        assert get_op("bmm") is not None


class TestAdviceR3Fixes:
    """Regressions for the round-3 advisor findings."""

    def test_worker_seed_differs_across_epochs(self):
        # WorkerInfo.seed must be base_seed + wid with a fresh base per
        # epoch, not a constant equal to the worker id.
        from paddle_tpu.io import DataLoader

        seen = []

        class DS:
            def __getitem__(self, i):
                from paddle_tpu.io.dataloader import get_worker_info
                return np.float32(get_worker_info().seed)

            def __len__(self):
                return 4

        dl = DataLoader(DS(), batch_size=4, num_workers=1,
                        worker_mode="process")
        for _ in range(2):
            for batch in dl:
                seen.append(int(np.asarray(batch.numpy())[0]))
        assert seen[0] != 0 or seen[1] != 0
        assert seen[0] != seen[1]   # fresh base seed per epoch

    def test_affine_transform_preserves_dtype_and_broadcasts_shape(self):
        import jax.numpy as jnp

        from paddle_tpu.distribution import AffineTransform
        import paddle_tpu as paddle

        tr = AffineTransform(paddle.to_tensor([0.0, 1.0]),
                             paddle.to_tensor([1.0, 2.0]))
        y = tr.forward(paddle.to_tensor(np.ones((3, 2), np.float16)))
        assert y._data.dtype == jnp.float16
        assert tr.forward_shape((3, 1)) == (3, 2)
        assert tr.inverse_shape((2,)) == (2,)
        ld = tr.forward_log_det_jacobian(paddle.to_tensor(
            np.ones((3, 1), np.float32)))
        assert list(ld.shape) == [3, 2]

    def test_sequence_mask_traced_without_maxlen_raises(self):
        import jax
        import pytest

        from paddle_tpu.nn.functional import sequence_mask
        import paddle_tpu as paddle

        assert sequence_mask(paddle.to_tensor([2, 3]), maxlen=None) \
            .shape == [2, 3]

        def f(x):
            return sequence_mask(x, maxlen=None)._data

        with pytest.raises(ValueError, match="explicit maxlen"):
            jax.jit(f)(np.array([2, 3]))

    def test_binomial_entropy_traced_raises(self):
        import jax
        import pytest

        from paddle_tpu.distribution import Binomial
        import paddle_tpu as paddle

        def f(n):
            return Binomial(n, paddle.to_tensor(0.5)).entropy()._data

        with pytest.raises(ValueError, match="concrete total_count"):
            jax.jit(f)(np.array(4.0, np.float32))
