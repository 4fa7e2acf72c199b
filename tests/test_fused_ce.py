"""Blockwise fused cross-entropy (kernels/fused_ce.py) parity tests.

Oracle: the materialising logsumexp xent. Checks fwd, grads wrt x AND
head, non-divisible vocab (masked tail chunk), bf16 inputs, jit, and the
llama loss_fn integration (fused vs einsum path must match)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.kernels import fused_ce
from paddle_tpu.kernels.fused_ce import fused_cross_entropy


def _naive(x, head, labels):
    logits = jnp.einsum("...d,vd->...v", x, head,
                        preferred_element_type=jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _case(n=6, s=7, d=16, v=33, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, s, d)), dtype)
    head = jnp.asarray(rng.normal(size=(v, d)) * 0.3, dtype)
    labels = jnp.asarray(rng.integers(0, v, (n, s)), jnp.int32)
    return x, head, labels


class TestFusedCE:
    @pytest.mark.parametrize("v,chunk", [(32, 8), (33, 8), (7, 16), (40, 40)])
    def test_forward_parity(self, v, chunk):
        x, head, labels = _case(v=v)
        got = fused_cross_entropy(x, head, labels, vocab_chunk=chunk)
        want = _naive(x, head, labels)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def test_grad_parity(self):
        x, head, labels = _case(v=33)
        gf = jax.grad(lambda x, h: fused_cross_entropy(
            x, h, labels, vocab_chunk=8), argnums=(0, 1))(x, head)
        gn = jax.grad(lambda x, h: _naive(x, h, labels),
                      argnums=(0, 1))(x, head)
        np.testing.assert_allclose(gf[0], gn[0], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(gf[1], gn[1], rtol=1e-5, atol=1e-6)

    def test_bf16_inputs(self):
        x, head, labels = _case(v=32, dtype=jnp.bfloat16)
        got = fused_cross_entropy(x, head, labels, vocab_chunk=8)
        want = _naive(x, head, labels)
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
        g = jax.grad(lambda x: fused_cross_entropy(
            x, head, labels, vocab_chunk=8))(x)
        assert g.dtype == jnp.bfloat16

    def test_jit_and_reductions(self):
        x, head, labels = _case(v=20)
        f = jax.jit(lambda x: fused_cross_entropy(
            x, head, labels, vocab_chunk=8, reduction="none"))
        per_tok = f(x)
        assert per_tok.shape == labels.shape
        np.testing.assert_allclose(jnp.mean(per_tok),
                                   _naive(x, head, labels),
                                   rtol=1e-6, atol=1e-6)
        s = fused_cross_entropy(x, head, labels, vocab_chunk=8,
                                reduction="sum")
        np.testing.assert_allclose(s, jnp.sum(per_tok), rtol=1e-6)

    def test_dispatcher_counts_and_fallback(self):
        x, head, labels = _case(v=16)
        kernels.reset_dispatch_stats()
        kernels.dispatched_fused_ce(x, head, labels, vocab_chunk=8)
        assert kernels.dispatch_stats()["fused_ce"] == 1
        # 1-D x is outside the guard -> fallback path, same math
        x1, l1 = x[0, 0], labels[0, 0]
        out = kernels.dispatched_fused_ce(x1, head, l1, vocab_chunk=8)
        assert kernels.dispatch_stats()["fused_ce_fallback"] == 1
        np.testing.assert_allclose(out, _naive(x1, head, l1), rtol=1e-6)

    @pytest.mark.slow  # tier-1 budget (ISSUE 3): heavy; run in the slow lane
    def test_llama_loss_fused_matches_einsum(self):
        from paddle_tpu.models import llama as L

        cfg_f = L.llama_tiny(num_hidden_layers=2, fused_ce=True,
                             fused_ce_chunk=64)
        cfg_e = L.llama_tiny(num_hidden_layers=2, fused_ce=False)
        params = L.init_params(cfg_f, jax.random.PRNGKey(0))
        ids = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg_f.vocab_size, (2, 17)), jnp.int32)
        lf = L.loss_fn(params, ids, cfg_f)
        le = L.loss_fn(params, ids, cfg_e)
        np.testing.assert_allclose(lf, le, rtol=1e-5, atol=1e-6)
        gf = jax.grad(lambda p: L.loss_fn(p, ids, cfg_f))(params)
        ge = jax.grad(lambda p: L.loss_fn(p, ids, cfg_e))(params)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5), gf, ge)

    @pytest.mark.slow  # tier-1 budget (ISSUE 20 rebalance): train-step integration dup;
    # grad_parity + dispatcher_fused_path_matches keep the seam fast
    def test_train_step_still_works(self):
        from paddle_tpu.models import llama as L

        cfg = L.llama_tiny(num_hidden_layers=2, fused_ce=True,
                           fused_ce_chunk=64)
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        opt = L.adamw_init(params)
        step = L.make_train_step(cfg, lr=1e-3)
        ids = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 33)), jnp.int32)
        losses = []
        for _ in range(5):
            params, opt, loss = step(params, opt, ids)
            losses.append(float(loss))
        assert losses[-1] < losses[0]


class TestIgnoreIndex:
    """ADVICE-r4 medium: -100 padded labels must zero out, not poison the
    mean with the masked-lane -1e30 gold logit; mean divides by valid
    count (reference F.cross_entropy ignore_index semantics)."""

    def _masked_oracle(self, x, head, labels, ignore=-100):
        return _oracle(x, head, labels, "mean", ignore)

    def test_padded_labels_finite_and_match_oracle(self):
        x, head, labels = _case(v=33)
        labels = labels.at[:, -3:].set(-100)   # right-padding convention
        got = fused_cross_entropy(x, head, labels, vocab_chunk=8)
        want = self._masked_oracle(x, head, labels)
        assert np.isfinite(float(got)) and float(got) < 1e6
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def test_grad_zero_on_ignored(self):
        x, head, labels = _case(v=33)
        labels = labels.at[0, :].set(-100)
        gx = jax.grad(lambda x: fused_cross_entropy(
            x, head, labels, vocab_chunk=8))(x)
        np.testing.assert_allclose(gx[0], np.zeros_like(gx[0]), atol=1e-9)
        assert float(jnp.abs(gx[1:]).max()) > 0
        gn = jax.grad(lambda x: self._masked_oracle(x, head, labels))(x)
        np.testing.assert_allclose(gx, gn, rtol=1e-5, atol=1e-6)

    def test_out_of_range_label_masked(self):
        x, head, labels = _case(v=33)
        labels = labels.at[1, 2].set(77)       # > V, not ignore_index
        got = fused_cross_entropy(x, head, labels, vocab_chunk=8)
        assert np.isfinite(float(got)) and float(got) < 1e6

    def test_custom_ignore_index(self):
        x, head, labels = _case(v=33)
        labels = labels.at[:, 0].set(0)
        a = fused_cross_entropy(x, head, labels, ignore_index=0,
                                vocab_chunk=8)
        want = self._masked_oracle(x, head, labels, ignore=0)
        np.testing.assert_allclose(a, want, rtol=1e-6, atol=1e-6)

    def test_all_ignored_is_zero_not_nan(self):
        x, head, labels = _case(v=33)
        labels = jnp.full_like(labels, -100)
        got = fused_cross_entropy(x, head, labels, vocab_chunk=8)
        assert float(got) == 0.0

    def test_dispatcher_fused_path_matches(self):
        x, head, labels = _case(v=33)
        labels = labels.at[:, -2:].set(-100)
        kernels.reset_dispatch_stats()
        a = kernels.dispatched_fused_ce(x, head, labels)
        assert kernels.dispatch_stats()["fused_ce"] == 1
        b = self._masked_oracle(x, head, labels)
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

    def test_dispatcher_fallback_masks_identically(self, monkeypatch):
        # force the materialising fallback on a full batch: its masking
        # (zeroed ignored tokens, valid-count mean) must match both the
        # oracle and the fused kernel on identical inputs
        from paddle_tpu.kernels import fused_ce as _fce
        x, head, labels = _case(v=33)
        labels = labels.at[:, ::2].set(-100)
        want = self._masked_oracle(x, head, labels)
        fused = fused_cross_entropy(x, head, labels, vocab_chunk=8)
        monkeypatch.setattr(_fce, "supported", lambda *a: False)
        kernels.reset_dispatch_stats()
        fell = kernels.dispatched_fused_ce(x, head, labels)
        assert kernels.dispatch_stats()["fused_ce_fallback"] == 1
        np.testing.assert_allclose(fell, want, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(fell, fused, rtol=1e-6, atol=1e-6)


def _oracle(x, head, labels, reduction, ignore=-100):
    """The materialising xent in float32, written out here: logits in
    HBM, jax's own differentiation."""
    valid = (labels != ignore) & (labels >= 0) & (labels < head.shape[0])
    logits = jnp.einsum("...d,vd->...v", x, head,
                        preferred_element_type=jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    per = jnp.where(valid, logz - gold, 0.0)
    if reduction == "sum":
        return jnp.sum(per)
    if reduction == "mean":
        return jnp.sum(per) / jnp.maximum(jnp.sum(valid), 1)
    return per


def _labels_for(case, labels, v):
    if case == "ignored":
        return labels.at[:, -3:].set(-100).at[2, :].set(-100)
    if case == "out_of_range":
        return labels.at[1, 2].set(v + 44).at[3, 0].set(-7)
    if case == "all_ignored":
        return jnp.full_like(labels, -100)
    return labels.at[0, 1].set(-100)


class TestOnePassRule:
    """A reduced loss differentiates through the rule whose forward makes
    the gradients over token blocks (ISSUE 34); the oracle materialises."""

    @pytest.mark.parametrize("case", [
        "ignored", "out_of_range", "all_ignored", "ragged_blocks", "scaled"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("reduction", ["mean", "sum"])
    def test_loss_and_gradients_match_the_oracle(self, monkeypatch,
                                                 reduction, dtype, case):
        v = 33                                  # 8 does not divide it
        x, head, labels = _case(v=v, dtype=dtype)
        labels = _labels_for(case, labels, v)
        if case == "ragged_blocks":             # 42 rows in blocks of 8
            monkeypatch.setattr(fused_ce, "ONEPASS_LOGITS_BYTES", 8 * 4 * v)
            assert fused_ce.token_block(42, v) == 8
        scale = 3.5 if case == "scaled" else 1.0    # the cotangent

        def both(f):
            return jax.value_and_grad(
                lambda x, h: scale * f(x, h), argnums=(0, 1))(x, head)

        kernels.reset_dispatch_stats()
        got, (gx, gh) = both(lambda x, h: fused_cross_entropy(
            x, h, labels, vocab_chunk=8, reduction=reduction))
        assert kernels.dispatch_stats()["fused_ce_onepass"] == 1
        want, (wx, wh) = both(lambda x, h: _oracle(x, h, labels, reduction))
        assert gx.dtype == gh.dtype == dtype
        # bf16: d_logits is rounded to 8 bits where the oracle's is not
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
        for g, w in ((gx, wx), (gh, wh)):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            np.testing.assert_allclose(
                g, w, rtol=tol, atol=tol * max(np.abs(w).max(), 1e-6))
        if case == "all_ignored":
            assert float(got) == 0.0 and not np.asarray(gx, np.float32).any()
        # the forward-only value is the vocabulary scan's: the same number
        only = scale * fused_cross_entropy(x, head, labels, vocab_chunk=8,
                                           reduction=reduction)
        np.testing.assert_allclose(only, got, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    def test_none_still_takes_a_vector_cotangent(self, dtype):
        x, head, labels = _case(v=33, dtype=dtype)
        labels = labels.at[:, -2:].set(-100)
        ct = jnp.asarray(np.random.default_rng(1).normal(size=labels.shape),
                         jnp.float32)
        kernels.reset_dispatch_stats()
        got, vjp = jax.vjp(lambda x, h: fused_cross_entropy(
            x, h, labels, vocab_chunk=8, reduction="none"), x, head)
        gx, gh = vjp(ct)
        assert kernels.dispatch_stats()["fused_ce_onepass"] == 0
        want, vjp = jax.vjp(lambda x, h: _oracle(x, h, labels, "none"),
                            x, head)
        wx, wh = vjp(ct)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
        for g, w in ((gx, wx), (gh, wh)):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            np.testing.assert_allclose(g, w, rtol=tol,
                                       atol=tol * np.abs(w).max())

    @pytest.mark.parametrize("how,count", [
        ("forward_only", 0), ("jit_of_grad", 1), ("grad_of_jit", 1),
        ("none_differentiated", 0)])
    def test_the_counter_says_which_rule_was_traced(self, how, count):
        x, head, labels = _case(v=40)
        red = "none" if how == "none_differentiated" else "mean"

        def loss(x, h):
            return jnp.sum(kernels.dispatched_fused_ce(
                x, h, labels, vocab_chunk=8, reduction=red))

        f = {"forward_only": jax.jit(loss),
             "jit_of_grad": jax.jit(jax.value_and_grad(loss, argnums=(0, 1))),
             "grad_of_jit": jax.grad(jax.jit(loss)),
             "none_differentiated": jax.jit(jax.grad(loss))}[how]
        kernels.reset_dispatch_stats()
        jax.block_until_ready(f(x, head))
        jax.block_until_ready(f(x, head))       # cached: traced once
        stats = kernels.dispatch_stats()
        assert stats["fused_ce"] == 1 and stats["fused_ce_fallback"] == 0
        assert stats["fused_ce_onepass"] == count

    @pytest.mark.parametrize("n,v,want", [
        (16384, 102400, 4096), (32768, 32768, 16384), (16380, 128256, 4096),
        (4096, 262272, 1024), (42, 33, 42), (1, 1 << 30, 1)])
    def test_token_block_comes_from_the_shape(self, n, v, want):
        nb = fused_ce.token_block(n, v)
        assert nb == want
        assert nb == n or (nb & (nb - 1) == 0
                           and nb * v * 4 <= fused_ce.ONEPASS_LOGITS_BYTES
                           < 2 * nb * v * 4)


def _vocab_products(text, v):
    """The dot_general lines of a lowered program that have ``v`` among
    their operands' or result's dimensions."""
    out = []
    for line in text.splitlines():
        if "stablehlo.dot_general" not in line:
            continue
        dims = re.findall(r"tensor<([0-9x]*)x[a-z]", line.split(" : ")[-1])
        if any(str(v) in d.split("x") for d in dims):
            out.append(line)
    return out


class TestLoweredPrograms:
    """What the rules put into a program (ISSUE 34): a differentiated
    loss three products over the vocabulary, a forward-only loss one,
    every op under the scope `ce` that prog.train.ce_ms reads."""

    V = 250     # no other width of the tiny configurations

    def _family(self, name):
        from paddle_tpu.models import llama, moe
        if name == "llama":
            return llama, llama.llama_tiny(vocab_size=self.V)
        return moe, moe.moe_tiny(vocab_size=self.V, dispatch_mode="capacity",
                                 remat=True)

    @pytest.mark.parametrize("name", ["llama", "moe"])
    @pytest.mark.parametrize("differentiated,products", [(True, 3), (False, 1)])
    def test_products_over_the_vocabulary(self, name, differentiated,
                                          products):
        fam, cfg = self._family(name)
        params = jax.eval_shape(
            lambda: fam.init_params(cfg, jax.random.key(0)))
        ids = jax.ShapeDtypeStruct((2, 17), jnp.int32)
        f = lambda p, ids: fam.loss_fn(p, ids, cfg)
        kernels.reset_dispatch_stats()
        text = jax.jit(jax.value_and_grad(f) if differentiated else f
                       ).lower(params, ids).as_text()
        found = _vocab_products(text, self.V)
        assert len(found) == products, "\n".join(found)
        assert kernels.dispatch_stats()["fused_ce_onepass"] == int(
            differentiated)

    @pytest.mark.parametrize("reduction", ["mean", "none"])
    def test_every_op_of_both_rules_is_under_ce(self, reduction):
        x, head, labels = _case(v=40)

        def loss_and_grads(x, h, ct):       # nothing here but the rules
            out, vjp = jax.vjp(lambda x, h: fused_cross_entropy(
                x, h, labels, vocab_chunk=16, reduction=reduction), x, h)
            return out, vjp(ct)

        ct = jnp.ones(labels.shape if reduction == "none" else ())
        hlo = jax.jit(loss_and_grads).lower(x, head, ct).compile().as_text()
        # an op's name is its path of scopes; a bare name is a parameter
        names = [n for n in re.findall(r'op_name="([^"]*)"', hlo)
                 if n.startswith("jit(")]
        assert len(names) > 20
        stray = [n for n in names if "ce" not in re.split(r"[/();:]", n)]
        assert not stray, stray[:5]
        assert any("transpose(" in n for n in names)    # a backward rule's
