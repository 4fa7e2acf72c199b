"""MoE + DiT model family tests (BASELINE config matrix:
DeepSeekMoE/Qwen2-MoE for EP, DiT/SD3 for diffusion). Strategy mirrors
tests/test_models.py: tiny configs, loss decreases, sharded-vs-local
parity on the 8-device CPU mesh."""
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import paddle_tpu as pt
from paddle_tpu.models import dit, moe


def mesh4(names):
    devs = np.array(jax.devices()[:8]).reshape(2, 2, 2, 1)
    return Mesh(devs, names)


class TestMoE:
    def test_forward_shapes_and_aux(self):
        cfg = moe.moe_tiny()
        params = moe.init_params(cfg, jax.random.key(0))
        ids = jnp.zeros((2, 16), jnp.int32)
        logits, aux = moe.forward(params, ids, cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        # balanced-routing lower bound: aux >= 1 (equality at uniform)
        assert float(aux) >= cfg.num_hidden_layers * 0.99

    @pytest.mark.slow  # tier-1 budget (ISSUE 20 rebalance): convergence run; forward_shapes_and_aux +
    # topk_routing + ep_sharded_matches_local keep the MoE seam fast
    def test_training_decreases_loss(self):
        cfg = moe.moe_tiny()
        params = moe.init_params(cfg, jax.random.key(1))
        opt = moe.adamw_init(params)
        step = moe.make_train_step(cfg, lr=3e-3)
        ids = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (4, 33)), jnp.int32)
        losses = []
        for _ in range(8):
            params, opt, loss = step(params, opt, ids)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_topk_routing_selects_k(self):
        cfg = moe.moe_tiny()
        params = moe.init_params(cfg, jax.random.key(2))
        ids = jnp.zeros((1, 8), jnp.int32)
        # run the router math directly on one layer slice
        x = jnp.take(params["embed"], ids, axis=0).reshape(8, -1)
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        logits = x.astype(jnp.float32) @ lp["router"]
        topv, topi = jax.lax.top_k(jax.nn.softmax(logits, -1),
                                   cfg.num_experts_per_tok)
        assert topi.shape == (8, cfg.num_experts_per_tok)

    def test_ep_sharded_matches_local(self):
        cfg = moe.moe_tiny()
        params = moe.init_params(cfg, jax.random.key(3))
        ids = jnp.asarray(np.random.default_rng(1).integers(
            0, cfg.vocab_size, (4, 17)), jnp.int32)
        local = moe.loss_fn(params, ids, cfg)
        mesh = mesh4(("dp", "fsdp", "ep", "tp"))
        with mesh:
            sharded = jax.jit(
                lambda p, b: moe.loss_fn(p, b, cfg, mesh=mesh))(params, ids)
        np.testing.assert_allclose(float(local), float(sharded), rtol=2e-4)

    def test_config_factories(self):
        assert moe.deepseek_moe_16b().num_experts == 64
        assert moe.qwen2_moe_a14b().num_experts_per_tok == 8
        # param count sanity on tiny
        assert moe.count_params(moe.moe_tiny()) > 0


def _oracle_slots(x, lp, config, T):
    """(topv, aux, keep [T*k], dest [T*k]) of the layer's routing: GShard's
    token-major priority order, slots past an expert's capacity dropped."""
    E, C = config.num_experts, moe.moe_capacity(config, T)
    topv, topi, aux = moe._route(x, lp, config)
    oh = jax.nn.one_hot(topi.reshape(-1), E, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(oh, axis=0) - oh) * oh, axis=-1)
    return topv, aux, pos < C, topi.reshape(-1) * C + pos


def _capacity_oracle(x, lp, config, T):
    """The capacity layer as plain autodiff sees it: the take / take body
    `_moe_mlp_capacity` had before its row movements got backward rules
    of their own (PR 32), kept here as the oracle. Its gradient holds the
    two row scatter-adds."""
    c = config
    E, k = c.num_experts, c.num_experts_per_tok
    C = moe.moe_capacity(c, T)
    topv, aux, keep, dest = _oracle_slots(x, lp, c, T)
    idx = jnp.full((E * C,), T, jnp.int32)
    idx = idx.at[jnp.where(keep, dest, E * C)].set(
        jnp.repeat(jnp.arange(T, dtype=jnp.int32), k), mode="drop")
    xp = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    xe = jnp.take(xp, idx, axis=0).reshape(E, C, -1)
    y = moe._expert_ffn(xe, lp)
    yk = jnp.take(y.reshape(E * C, -1), jnp.where(keep, dest, 0), axis=0)
    w = (topv.reshape(-1) * keep).astype(jnp.float32)[:, None]
    routed = jnp.sum((yk.astype(jnp.float32) * w).reshape(T, k, -1),
                     axis=1)
    return routed.astype(x.dtype), aux


def _kept(x, lp, config, T):
    """keep [T, k] of the layer's routing, by the oracle's bookkeeping."""
    keep = _oracle_slots(x, lp, config, T)[2]
    return np.asarray(keep).reshape(T, config.num_experts_per_tok)


def _row_scatters(jaxpr, width):
    """(primitive, operand shape) of every scatter in a jaxpr, nested
    ones too, whose operand's last dimension is ``width``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            shape = eqn.invars[0].aval.shape
            if shape and shape[-1] == width:
                found.append((eqn.primitive.name, shape))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _row_scatters(sub, width)
    return found


class TestMoECapacityDispatch:
    """GShard capacity gather dispatch (the single-chip default for the
    big configs; reference capacity_factor semantics from
    incubate/distributed/models/moe/gate)."""

    def _cfgs(self, **cap_kw):
        dense = moe.moe_tiny()
        capped = moe.moe_tiny(dispatch_mode="capacity", **cap_kw)
        return dense, capped

    @pytest.mark.slow  # tier-1 budget (ISSUE 3): heavy; run in the slow lane
    def test_matches_dense_when_nothing_drops(self):
        # capacity_factor = E/k makes C = T: no expert can overflow, so
        # capacity dispatch computes exactly the dense function
        dense, capped = self._cfgs(capacity_factor=2.0)  # E/k = 4/2
        params = moe.init_params(dense, jax.random.key(0))
        ids = jnp.asarray(np.random.default_rng(0).integers(
            0, dense.vocab_size, (2, 33)), jnp.int32)
        ld = jax.jit(lambda p: moe.loss_fn(p, ids, dense))(params)
        lc = jax.jit(lambda p: moe.loss_fn(p, ids, capped))(params)
        np.testing.assert_allclose(float(ld), float(lc), rtol=1e-5)
        # and grads agree too (the dispatch is differentiated through)
        gd = jax.grad(lambda p: moe.loss_fn(p, ids, dense))(params)
        gc = jax.grad(lambda p: moe.loss_fn(p, ids, capped))(params)
        for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gc)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)

    def test_over_capacity_slots_drop_not_crash(self):
        # capacity_factor tiny: C clamps to the minimum; most slots drop
        # but the loss stays finite and grads flow (dropped tokens keep
        # their shared-expert path)
        _, capped = self._cfgs(capacity_factor=0.01)
        assert moe.moe_capacity(capped, 64) == 8
        params = moe.init_params(capped, jax.random.key(1))
        ids = jnp.asarray(np.random.default_rng(1).integers(
            0, capped.vocab_size, (2, 33)), jnp.int32)
        loss, grads = jax.value_and_grad(
            lambda p: moe.loss_fn(p, ids, capped))(params)
        assert np.isfinite(float(loss))
        g = np.asarray(grads["layers"]["s_gate"])
        assert np.isfinite(g).all() and np.abs(g).sum() > 0

    def test_capacity_lane_alignment(self):
        big = moe.deepseek_moe_16b(num_hidden_layers=2)
        c = moe.moe_capacity(big, 2048)   # even share 192, x1.25 = 240
        assert c == 256 and c % 128 == 0
        # never exceeds the token count
        assert moe.moe_capacity(big, 64) <= 64

    @pytest.mark.slow  # tier-1 budget (ISSUE 20 rebalance): convergence run; matches_dense_when_nothing_drops
    # + dots_remat_policy_compiles keep the capacity-dispatch seam fast
    def test_trains_and_beats_init(self):
        cfg = moe.moe_tiny(dispatch_mode="capacity")
        params = moe.init_params(cfg, jax.random.key(2))
        opt = moe.adamw_init(params)
        step = moe.make_train_step(cfg, lr=3e-3)
        ids = jnp.asarray(np.random.default_rng(2).integers(
            0, cfg.vocab_size, (4, 33)), jnp.int32)
        losses = []
        for _ in range(8):
            params, opt, loss = step(params, opt, ids)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    @pytest.mark.slow  # tier-1 budget (ISSUE 20 rebalance): decode parity duplicated by
    # generate_greedy_matches_naive in this class
    def test_kv_cache_decode_matches_forward(self):
        # MoE incremental decode: prefill + steps pin to the full
        # forward's last logits (routing runs per decoded token)
        cfg = moe.moe_tiny()
        params = moe.init_params(cfg, jax.random.key(5))
        ids = jnp.asarray(np.random.default_rng(5).integers(
            0, cfg.vocab_size, (2, 6)), jnp.int32)
        cache = moe.init_cache(cfg, 2, 9)
        cache, logits = moe.prefill(params, ids, cfg, cache)
        full, _ = moe.forward(params, ids, cfg)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, -1, :]),
                                   rtol=2e-4, atol=2e-4)
        seq = ids
        for _ in range(2):
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            seq = jnp.concatenate([seq, tok[:, None]], axis=1)
            cache, logits = moe.decode_step(params, cache, tok, cfg)
            full, _ = moe.forward(params, seq, cfg)
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(full[:, -1, :]),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.slow  # tier-1 budget (ISSUE 3): heavy; run in the slow lane
    def test_beam_search_k1_equals_greedy(self):
        cfg = moe.moe_tiny()
        params = moe.init_params(cfg, jax.random.key(7))
        ids = jnp.asarray(np.random.default_rng(7).integers(
            0, cfg.vocab_size, (2, 5)), jnp.int32)
        greedy = np.asarray(moe.generate(params, ids, cfg,
                                         max_new_tokens=3))
        toks, scores = moe.beam_search(params, ids, cfg,
                                       max_new_tokens=3, num_beams=1)
        np.testing.assert_array_equal(np.asarray(toks), greedy)
        # and K=3 scores are at least as good as the greedy path's
        _, s3 = moe.beam_search(params, ids, cfg, max_new_tokens=3,
                                num_beams=3)
        assert (np.asarray(s3) >= np.asarray(scores) - 1e-5).all()

    def test_generate_greedy_matches_naive(self):
        cfg = moe.moe_tiny()
        params = moe.init_params(cfg, jax.random.key(6))
        ids = jnp.asarray(np.random.default_rng(6).integers(
            0, cfg.vocab_size, (2, 5)), jnp.int32)
        got = jax.jit(lambda p, i: moe.generate(
            p, i, cfg, max_new_tokens=3))(params, ids)
        seq = ids
        want = []
        for _ in range(3):
            logits, _ = moe.forward(params, seq, cfg)
            nxt = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)
            want.append(nxt)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.stack(want, axis=1))

    @pytest.mark.slow  # tier-1 budget (ISSUE 14 rebalance): MoE int8
    # decode parity duplicates the llama-family weight-only pins
    # (test_models TestWeightOnlyDecode) under the same contract
    def test_weight_only_int8_decode(self):
        # quantized tree == dequantized-fp tree through forward AND the
        # decode loop (same bit-exact contract as the llama family) —
        # under capacity dispatch, the measured on-chip configuration
        cfg = moe.moe_tiny(dispatch_mode="capacity")
        params = moe.init_params(cfg, jax.random.key(8))
        qp = moe.quantize_weights(params)
        deq = {"embed": params["embed"], "ln_f": params["ln_f"],
               "layers": {}}
        for k, w in qp["layers"].items():
            if isinstance(w, dict):
                s = w["s"]
                br = s[:, :, None, :] if w["q"].ndim == 4 else s[:, None, :]
                deq["layers"][k] = w["q"].astype(jnp.float32) * br
            else:
                deq["layers"][k] = w
        deq["lm_head"] = (qp["lm_head"]["q"].astype(jnp.float32)
                          * qp["lm_head"]["s"][:, None])
        ids = jnp.asarray(np.random.default_rng(8).integers(
            0, cfg.vocab_size, (2, 7)), jnp.int32)
        la, _ = moe.forward(qp, ids, cfg)
        lb, _ = moe.forward(deq, ids, cfg)
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-6, atol=1e-6)
        ga = np.asarray(moe.generate(qp, ids, cfg, max_new_tokens=3))
        gb = np.asarray(moe.generate(deq, ids, cfg, max_new_tokens=3))
        np.testing.assert_array_equal(ga, gb)

    def test_dots_remat_policy_compiles(self):
        cfg = moe.moe_tiny(dispatch_mode="capacity", remat=True,
                           remat_policy="dots")
        params = moe.init_params(cfg, jax.random.key(3))
        opt = moe.adamw_init(params)
        step = moe.make_train_step(cfg, lr=1e-3)
        ids = jnp.asarray(np.random.default_rng(3).integers(
            0, cfg.vocab_size, (2, 17)), jnp.int32)
        params, opt, loss = step(params, opt, ids)
        assert np.isfinite(float(loss))

    # -- rows move by gather in both directions (PR 32): the token <->
    # grid map is a partial permutation, so the backward of each gather
    # is a gather through the inverse map

    T = 96

    def _layer(self, seed=0, **kw):
        cfg = moe.moe_tiny(dispatch_mode="capacity", **kw)
        params = moe.init_params(cfg, jax.random.key(seed))
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        lp = {n: lp[n] for n in ("router", "e_gate", "e_up", "e_down")}
        x = jax.random.normal(jax.random.key(seed + 1),
                              (self.T, cfg.hidden_size), jnp.float32)
        tgt = jax.random.normal(jax.random.key(seed + 2), x.shape,
                                jnp.float32)
        return cfg, lp, x, tgt

    def _loss(self, layer, cfg, tgt):
        def loss(x, lp):
            routed, aux = layer(x, lp, cfg, self.T)
            return jnp.sum(routed * tgt) + 0.1 * aux
        return loss

    # moe_tiny: E 4, k 2; the third case E 8, k 3 (a sum of three). C =
    # T at factor E/k: nothing can drop. (factor, share of slots dropped)
    @pytest.mark.parametrize("kw,dropped", [
        (dict(capacity_factor=2.0), (0.0, 0.0)),
        (dict(capacity_factor=0.55), (0.25, 0.45)),
        (dict(capacity_factor=0.55, num_experts=8, num_experts_per_tok=3),
         (0.25, 0.5)),
    ], ids=["nothing-drops", "a-third-drops", "a-third-drops-k3"])
    def test_loss_and_every_gradient_equal_plain_autodiff(self, kw, dropped):
        cfg, lp, x, tgt = self._layer(**kw)
        share = 1.0 - _kept(x, lp, cfg, self.T).mean()
        assert dropped[0] <= share <= dropped[1], share
        got = jax.value_and_grad(self._loss(moe._moe_mlp_capacity, cfg, tgt),
                                 argnums=(0, 1))(x, lp)
        want = jax.value_and_grad(self._loss(_capacity_oracle, cfg, tgt),
                                  argnums=(0, 1))(x, lp)
        np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
        leaves = jax.tree_util.tree_leaves_with_path(got[1])
        assert len(leaves) == 5            # x, router, three expert grids
        for (path, a), b in zip(leaves, jax.tree.leaves(want[1])):
            assert float(jnp.max(jnp.abs(b))) > 1e-4, path
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5,
                err_msg=jax.tree_util.keystr(path))

    def test_a_dropped_slot_gives_no_gradient(self):
        cfg, lp, x, tgt = self._layer(seed=3, capacity_factor=0.01)
        E, k, C = (cfg.num_experts, cfg.num_experts_per_tok,
                   moe.moe_capacity(cfg, self.T))
        keep = _kept(x, lp, cfg, self.T)
        gone = ~keep.any(axis=1)           # tokens that lost every slot
        assert C == 8 and gone.sum() > 8 and keep.any(axis=1).sum() > 8
        # through the layer (the balance loss left out): a token whose
        # slots all dropped gets no gradient at all, the others do
        dx = jax.grad(lambda x: jnp.sum(
            moe._moe_mlp_capacity(x, lp, cfg, self.T)[0] * tgt))(x)
        dx = np.abs(np.asarray(dx)).sum(axis=1)
        assert (dx[gone] == 0).all() and (dx[~gone] > 0).all()
        # through each movement: with a cotangent of ones a token's row
        # gradient counts its kept slots, and a dropped slot's router
        # weight reads a zero row of y
        _, topi, _ = moe._route(x, lp, cfg)
        dest = np.full(keep.shape, E * C, np.int32)
        slot_of = np.full((E * C,), self.T * k, np.int32)
        fill = np.zeros(E, np.int32)
        for s, e in enumerate(np.asarray(topi).reshape(-1)):
            if fill[e] < C:
                dest[s // k, s % k] = e * C + fill[e]
                slot_of[e * C + fill[e]] = s
            fill[e] += 1
        assert ((dest < E * C) == keep).all()
        dest, slot_of = jnp.asarray(dest), jnp.asarray(slot_of)
        xe, vjp = jax.vjp(lambda x: moe._dispatch_rows(
            x, slot_of // k, dest), x)
        np.testing.assert_array_equal(
            np.asarray(vjp(jnp.ones_like(xe))[0]),
            np.repeat(keep.sum(axis=1, keepdims=True), x.shape[1], axis=1))
        y = jax.random.normal(jax.random.key(9), xe.shape, jnp.float32)
        w = jnp.ones(keep.shape, jnp.float32)
        dw = jax.grad(lambda w: jnp.sum(moe._combine_rows(
            y, w, slot_of // k, slot_of, dest) * tgt))(w)
        dw = np.asarray(dw)
        assert (dw[~keep] == 0).all() and (dw[keep] != 0).all()

    def test_the_gradient_scatters_no_rows(self):
        cfg, lp, x, tgt = self._layer()
        D = cfg.hidden_size
        assert D not in (self.T, cfg.num_experts, cfg.intermediate_size)
        grad = lambda layer: jax.make_jaxpr(jax.grad(
            self._loss(layer, cfg, tgt), argnums=(0, 1)))(x, lp).jaxpr
        # the walker does find the oracle's two: [E*C, D] and [T+1, D]
        C = moe.moe_capacity(cfg, self.T)
        assert sorted(_row_scatters(grad(_capacity_oracle), D)) == [
            ("scatter-add", (self.T + 1, D)),
            ("scatter-add", (cfg.num_experts * C, D))]
        assert _row_scatters(grad(moe._moe_mlp_capacity), D) == []
        # what is left scatters int32 indices (slot_of) or the router's
        # [T, E] (top_k's transpose), never a row
        text = str(grad(moe._moe_mlp_capacity))
        assert "scatter" in text

    def test_train_step_with_scan_and_full_remat_matches(self, monkeypatch):
        cfg = moe.moe_tiny(dispatch_mode="capacity", remat=True,
                           capacity_factor=0.55)
        assert cfg.num_hidden_layers == 2
        ids = jnp.asarray(np.random.default_rng(4).integers(
            0, cfg.vocab_size, (2, 49)), jnp.int32)

        def one_step():
            params = moe.init_params(cfg, jax.random.key(4))
            step = moe.make_train_step(cfg, lr=1e-3)
            return step(params, moe.adamw_init(params), ids)

        got = one_step()
        monkeypatch.setattr(moe, "_moe_mlp_capacity", _capacity_oracle)
        want = one_step()
        np.testing.assert_allclose(float(got[2]), float(want[2]), rtol=1e-6)
        # the first moment after one step is (1 - b1) * gradient: linear
        # in it, where the parameters' own update divides by its size
        ms = jax.tree_util.tree_leaves_with_path(got[1]["m"])
        for (path, a), b in zip(ms, jax.tree.leaves(want[1]["m"])):
            scale = float(jnp.max(jnp.abs(b)))
            assert scale > 0, path
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5 * scale,
                err_msg=jax.tree_util.keystr(path))

    def test_backward_gathers_keep_their_scopes(self):
        # trace_scope files an op by the scopes in its op_name: the
        # backward rules' gathers must read moe.dispatch / moe.combine,
        # or prog.train.moe_ms loses them to unscoped_ms
        cfg = moe.moe_tiny(dispatch_mode="capacity", remat=True)
        params = moe.init_params(cfg, jax.random.key(0))
        step = moe.make_train_step(cfg, lr=1e-3)
        hlo = step.lower(params, moe.adamw_init(params),
                         jnp.zeros((2, 17), jnp.int32)).compile().as_text()
        back = {"moe.dispatch": 0, "moe.combine": 0}
        for line in hlo.splitlines():
            op = re.search(r"= (\S+) gather\(.*op_name=\"([^\"]*)\"", line)
            if op is None or "transpose(" not in op.group(2) \
                    or "rematted_computation" in op.group(2):
                continue
            parts = re.split(r"[/();:]", op.group(2))
            scopes = [p for p in parts if p in back]
            assert scopes, line
            back[scopes[-1]] += 1
        # dispatch's k-sum reads one gather; combine's dy reads two (the
        # rows of d_routed and the cells' weights), its dw one (the
        # cells' products, read by the slots)
        assert back == {"moe.dispatch": 1, "moe.combine": 3}, back


class TestDiT:
    def test_forward_shape(self):
        cfg = dit.dit_tiny()
        params = dit.init_params(cfg, jax.random.key(0))
        x = jnp.zeros((2, cfg.in_channels, cfg.image_size, cfg.image_size))
        t = jnp.array([0, 500], jnp.int32)
        y = jnp.array([1, 2], jnp.int32)
        out = dit.forward(params, x, t, y, cfg)
        assert out.shape == x.shape

    def test_zero_init_identity(self):
        """adaLN-Zero: at init the final projection is zero, so the
        prediction is exactly zero (the DiT identity-residual property)."""
        cfg = dit.dit_tiny()
        params = dit.init_params(cfg, jax.random.key(1))
        x = jnp.ones((1, cfg.in_channels, cfg.image_size, cfg.image_size))
        out = dit.forward(params, x, jnp.array([3], jnp.int32),
                          jnp.array([0], jnp.int32), cfg)
        np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)

    def test_patchify_roundtrip(self):
        cfg = dit.dit_tiny()
        x = jnp.asarray(np.random.randn(2, cfg.in_channels, cfg.image_size,
                                        cfg.image_size), jnp.float32)
        p = dit.patchify(x, cfg)
        assert p.shape == (2, cfg.num_patches,
                           cfg.patch_size ** 2 * cfg.in_channels)
        np.testing.assert_allclose(np.asarray(dit.unpatchify(p, cfg)),
                                   np.asarray(x), rtol=1e-6)

    @pytest.mark.slow  # tier-1 budget (ISSUE 20 rebalance): convergence run; forward_shape +
    # zero_init_identity + ddim_sampling_loop keep the DiT seam fast
    def test_training_decreases_loss(self):
        cfg = dit.dit_tiny()
        params = dit.init_params(cfg, jax.random.key(2))
        opt = dit.adamw_init(params)
        step = dit.make_train_step(cfg, lr=1e-3)
        rng = np.random.default_rng(0)
        x0 = jnp.asarray(rng.normal(size=(4, cfg.in_channels,
                                          cfg.image_size, cfg.image_size)),
                         jnp.float32)
        t = jnp.asarray(rng.integers(0, 1000, (4,)), jnp.int32)
        y = jnp.asarray(rng.integers(0, cfg.num_classes, (4,)), jnp.int32)
        noise = jnp.asarray(rng.normal(size=x0.shape), jnp.float32)
        losses = []
        for _ in range(10):
            params, opt, loss = step(params, opt, (x0, t, y, noise))
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_ddim_sampling_loop(self):
        cfg = dit.dit_tiny()
        params = dit.init_params(cfg, jax.random.key(0))
        y = jnp.asarray([1, 3], jnp.int32)
        x = jax.jit(lambda p, y: dit.ddim_sample(
            p, y, cfg, steps=5, key=jax.random.PRNGKey(0)))(params, y)
        assert x.shape == (2, cfg.in_channels, cfg.image_size,
                           cfg.image_size)
        assert np.isfinite(np.asarray(x)).all()
        # eta=0 DDIM is deterministic given the init-noise key
        x2 = dit.ddim_sample(params, y, cfg, steps=5,
                             key=jax.random.PRNGKey(0))
        np.testing.assert_allclose(np.asarray(x), np.asarray(x2),
                                   rtol=1e-5, atol=1e-6)

    def test_ddim_cfg_null_branch(self):
        # guidance_scale != 1 runs the conditional + null-label batch;
        # at a zero-init output head both branches predict 0 so the
        # guided trajectory must match the unguided one exactly
        cfg = dit.dit_tiny()
        params = dit.init_params(cfg, jax.random.key(1))
        y = jnp.asarray([0, 2], jnp.int32)
        a = dit.ddim_sample(params, y, cfg, steps=3,
                            key=jax.random.PRNGKey(1))
        b = dit.ddim_sample(params, y, cfg, steps=3, guidance_scale=4.0,
                            key=jax.random.PRNGKey(1))
        # final_w is zero-init -> eps == 0 for both branches
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    def test_sharded_matches_local(self):
        cfg = dit.dit_tiny()
        params = dit.init_params(cfg, jax.random.key(3))
        rng = np.random.default_rng(2)
        batch = (jnp.asarray(rng.normal(size=(4, cfg.in_channels,
                                              cfg.image_size,
                                              cfg.image_size)), jnp.float32),
                 jnp.asarray(rng.integers(0, 1000, (4,)), jnp.int32),
                 jnp.asarray(rng.integers(0, cfg.num_classes, (4,)),
                             jnp.int32),
                 jnp.asarray(rng.normal(size=(4, cfg.in_channels,
                                              cfg.image_size,
                                              cfg.image_size)), jnp.float32))
        local = dit.loss_fn(params, batch, cfg)
        devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
        mesh = Mesh(devs, ("dp", "fsdp", "tp"))
        with mesh:
            sharded = jax.jit(
                lambda p, b: dit.loss_fn(p, b, cfg, mesh=mesh))(params, batch)
        np.testing.assert_allclose(float(local), float(sharded), rtol=2e-4)


class TestMoEReviewRegressions:
    def test_gates_scale_outputs_not_inputs(self):
        """Router weights must scale expert OUTPUTS (nonlinear experts):
        doubling a token's router weight share must NOT change what the
        expert computes on it, only its contribution."""
        cfg = moe.moe_tiny(num_experts=2, num_experts_per_tok=1)
        params = moe.init_params(cfg, jax.random.key(0))
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        h = jnp.asarray(np.random.default_rng(3).normal(
            size=(1, 4, cfg.hidden_size)), jnp.float32)
        out, _ = moe._moe_mlp(h, lp, cfg, None)
        # reference computation: for each token, MLP(x) of its top expert
        # times its (renormalized=1.0 for k=1) gate + shared expert
        x = h.reshape(4, -1)
        logits = x @ lp["router"]
        top = jnp.argmax(logits, axis=-1)
        expect = []
        for ti in range(4):
            e = int(top[ti])
            g = jax.nn.silu(x[ti] @ lp["e_gate"][e]) * (x[ti] @ lp["e_up"][e])
            routed = g @ lp["e_down"][e]
            sg = jax.nn.silu(x[ti] @ lp["s_gate"]) * (x[ti] @ lp["s_up"])
            expect.append(routed + sg @ lp["s_down"])
        np.testing.assert_allclose(np.asarray(out.reshape(4, -1)),
                                   np.asarray(jnp.stack(expect)),
                                   rtol=2e-4, atol=1e-5)


class TestDomainReviewRegressions:
    def test_tuner_local_bs_counts_sharding(self):
        from paddle_tpu.distributed.auto_tuner import generate_candidates
        cands = generate_candidates({"num_chips": 8, "global_batch_size": 8})
        for c in cands:
            ways = c["dp_degree"] * c["sharding_degree"]
            assert c["micro_batch_size"] * c["acc_steps"] == 8 // ways

    def test_quanter_frozen_in_eval(self):
        from paddle_tpu import quantization as Q
        import paddle_tpu.nn as nn

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(4, 4)

            def forward(self, x):
                return self.fc(x)

        qat = Q.QAT(Q.QuantConfig(
            activation=Q.FakeQuanterWithAbsMaxObserver()))
        m = qat.quantize(Net())
        x1 = pt.to_tensor(np.ones((2, 4), "float32"))
        m.train()
        m(x1)
        from paddle_tpu.quantization.wrapper import ObserveWrapper
        w = [s for _, s in m.named_sublayers()
             if isinstance(s, ObserveWrapper)][0]
        s_before = w._act._scale
        m.eval()
        m(pt.to_tensor(100 * np.ones((2, 4), "float32")))
        assert w._act._scale == s_before      # eval must not recalibrate

    def test_quanted_state_dict_roundtrip(self):
        from paddle_tpu import quantization as Q
        import paddle_tpu.nn as nn

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(4, 4)

            def forward(self, x):
                return self.fc(x)

        ptq = Q.PTQ(Q.QuantConfig(weight=Q.AbsmaxObserver()))
        m = ptq.quantize(Net())
        m(pt.to_tensor(np.random.randn(4, 4).astype("float32")))
        conv = ptq.convert(m)
        sd = conv.state_dict()
        assert any("qweight" in k for k in sd), list(sd)
        assert any("w_scale" in k for k in sd), list(sd)

    def test_sparse_scalar_add_densifies(self):
        d = np.array([[0.0, 1.0], [0.0, 0.0]], "float32")
        s = pt.sparse.sparse_coo_tensor_from_dense(d)
        out = pt.sparse.add(s, 1.0)
        np.testing.assert_allclose(out.to_dense().numpy(), d + 1.0)
        # mul keeps value space (zeros preserved)
        out2 = pt.sparse.multiply(s, 2.0)
        np.testing.assert_allclose(out2.to_dense().numpy(), d * 2.0)
        assert out2.nnz() == s.nnz()

    def test_segment_sum_under_jit(self):
        f = jax.jit(lambda d, i: pt.geometric.segment_sum(
            pt.Tensor(d), pt.Tensor(i))._data)
        d = jnp.asarray(np.ones((4, 2), "float32"))
        i = jnp.asarray(np.array([0, 1, 1, 0], "int32"))
        out = f(d, i)
        # jit path pads to the static upper bound (rows of data)
        np.testing.assert_allclose(np.asarray(out)[:2],
                                   [[2.0, 2.0], [2.0, 2.0]])

    def test_sample_neighbors_eids(self):
        row = np.array([1, 2, 0, 0, 1], "int64")
        colptr = np.array([0, 2, 3, 5], "int64")
        nodes = np.array([0, 2], "int64")
        n, c, e = pt.geometric.sample_neighbors(
            pt.to_tensor(row), pt.to_tensor(colptr), pt.to_tensor(nodes),
            return_eids=True)
        np.testing.assert_array_equal(np.asarray(e.numpy()), [0, 1, 3, 4])


class TestOCRRecognizer:
    @pytest.mark.slow  # tier-1 budget (ISSUE 3): heavy; run in the slow lane
    def test_ocr_rec_trains_with_ctc(self):
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu.models import ocr
        from paddle_tpu.optimizer import Adam

        cfg = ocr.ocr_rec_tiny()
        model = ocr.OCRRecognizer(cfg)
        opt = Adam(learning_rate=1e-3, parameters=model.parameters())
        step = ocr.ctc_train_step(model, opt)
        rng = np.random.default_rng(0)
        imgs = paddle.to_tensor(
            rng.normal(size=(2, 3, cfg.image_height, 48)).astype("float32"))
        labels = paddle.to_tensor(
            rng.integers(1, cfg.num_classes, (2, 5)).astype("int32"))
        lens = paddle.to_tensor(np.array([5, 4], "int32"))
        l0 = float(step(imgs, labels, lens).numpy())
        for _ in range(8):
            last = float(step(imgs, labels, lens).numpy())
        assert np.isfinite(last) and last < l0

    def test_ctc_greedy_decode(self):
        import numpy as np

        from paddle_tpu.models import ocr

        # hand-built logits: frames argmax to [blank, 5, 5, blank, 3, 3]
        # -> collapse repeats, drop blanks -> [5, 3]
        C = 8
        frames = [0, 5, 5, 0, 3, 3]
        logits = np.full((1, len(frames), C), -5.0, np.float32)
        for t, k in enumerate(frames):
            logits[0, t, k] = 5.0
        texts, confs = ocr.ctc_greedy_decode(logits)
        assert texts == [[5, 3]]
        assert 0.9 < confs[0] <= 1.0
        # all-blank row decodes empty with zero confidence
        blank = np.zeros((1, 4, C), np.float32)
        blank[..., 0] = 9.0
        texts, confs = ocr.ctc_greedy_decode(blank)
        assert texts == [[]] and confs[0] == 0.0

    def test_ernie_config(self):
        from paddle_tpu.models import moe

        cfg = moe.ernie_4_5_a3b(num_hidden_layers=2)
        assert cfg.num_experts == 64 and cfg.num_experts_per_tok == 6


class TestScaleLowering:
    def test_llama_70b_shapes_lower_on_mesh(self):
        """BASELINE config matrix: Llama-3-70B shapes must COMPILE under
        the hybrid sharding (shape-level lowering only — no 70B of memory
        is materialized; jit.lower accepts ShapeDtypeStructs)."""
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from paddle_tpu.models import llama as L

        cfg = L.LlamaConfig(
            vocab_size=128256, hidden_size=8192, intermediate_size=28672,
            num_hidden_layers=2,          # layer count is scan-stacked;
            num_attention_heads=64,       # 2 layers proves the shapes
            num_key_value_heads=8, max_position_embeddings=8192,
            rope_theta=500000.0)
        devs = np.array(jax.devices()[:8]).reshape(1, 4, 2)
        mesh = Mesh(devs, ("dp", "fsdp", "tp"))
        step = L.make_train_step(cfg, mesh, lr=1e-4, sp=True)
        pshape = jax.eval_shape(lambda k: L.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        oshape = jax.eval_shape(L.adamw_init, pshape)
        ids = jax.ShapeDtypeStruct((4, 4097), np.int32)
        lowered = step.lower(pshape, oshape, ids)
        text = lowered.as_text()
        assert "sharding" in text          # GSPMD annotations present
        # the gate projection's declared placement shards the ffn dim on
        # tp and the hidden dim on fsdp (ZeRO-3 + Megatron TP)
        from jax.sharding import PartitionSpec as P

        specs = L.param_specs(cfg)
        assert specs["layers"]["gate"] == P(None, "fsdp", "tp")
        assert specs["embed"] == P("tp", "fsdp")
