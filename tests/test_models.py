"""Flagship model family tests (SURVEY.md §7 phase 8 start): functional
Llama core vs eager Layer model, sharded hybrid-parallel train step on the
8-device CPU mesh (the reference's N-local-process strategy, SURVEY.md §4).
"""
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu.models import llama as L


def tiny(**kw):
    return L.llama_tiny(**kw)


class TestKVCacheDecode:
    """Static ring-buffer decode path vs the full forward (reference:
    nn/layer/transformer.py gen_cache incremental decoding)."""

    def _setup(self, seed=0, B=2, S=7):
        cfg = tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(seed))
        ids = jnp.asarray(np.random.default_rng(seed).integers(
            0, cfg.vocab_size, (B, S)), jnp.int32)
        return cfg, params, ids

    def test_prefill_matches_forward_last_logits(self):
        cfg, params, ids = self._setup()
        cache = L.init_cache(cfg, ids.shape[0], 16)
        cache, logits = L.prefill(params, ids, cfg, cache)
        full = L.forward(params, ids, cfg)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, -1, :]),
                                   rtol=2e-4, atol=2e-4)
        assert int(cache["pos"]) == ids.shape[1]

    @pytest.mark.slow  # tier-1 budget (ISSUE 5): heavy; the greedy/beam
    # naive-loop parities below keep KV-cache decode covered in tier-1
    def test_decode_steps_match_full_forward(self):
        cfg, params, ids = self._setup(seed=1)
        B, S = ids.shape
        extra = jnp.asarray(np.random.default_rng(9).integers(
            0, cfg.vocab_size, (B, 3)), jnp.int32)
        cache = L.init_cache(cfg, B, S + 3)
        cache, logits = L.prefill(params, ids, cfg, cache)
        seq = ids
        for t in range(3):
            tok = extra[:, t]
            cache, logits = L.decode_step(params, cache, tok, cfg)
            seq = jnp.concatenate([seq, tok[:, None]], axis=1)
            full = L.forward(params, seq, cfg)[:, -1, :]
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(full),
                                       rtol=2e-4, atol=2e-4)

    def test_greedy_generate_matches_naive_loop(self):
        cfg, params, ids = self._setup(seed=2, B=2, S=5)
        got = L.generate(params, ids, cfg, max_new_tokens=4)
        # naive: re-run the full forward for every new token
        seq = ids
        want = []
        for _ in range(4):
            nxt = jnp.argmax(L.forward(params, seq, cfg)[:, -1, :],
                             axis=-1).astype(jnp.int32)
            want.append(nxt)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.stack(want, axis=1))

    def test_generate_jits_once_and_reruns(self):
        cfg, params, ids = self._setup(seed=3)
        gen = jax.jit(lambda p, i: L.generate(p, i, cfg,
                                              max_new_tokens=3))
        a = gen(params, ids)
        b = gen(params, ids + 0)
        assert a.shape == (2, 3)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_cache_overflow_typed_error(self):
        from paddle_tpu.core import enforce as E
        cfg, params, ids = self._setup()
        with pytest.raises(E.EnforceError):
            L.generate(params, ids, cfg, max_new_tokens=4, max_len=8)
        cache = L.init_cache(cfg, 2, 4)
        with pytest.raises(E.EnforceError):
            L.prefill(params, ids, cfg, cache)

    def test_tp_sharded_generate_matches_single_device(self):
        """Distributed serving: the same jit-once generate program runs
        with GSPMD tensor-parallel-sharded weights (param_specs over a
        (dp,fsdp,tp) mesh) and must produce identical greedy tokens."""
        cfg, params, ids = self._setup(seed=5)
        want = np.asarray(L.generate(params, ids, cfg, max_new_tokens=4))
        devs = np.array(jax.devices()[:8]).reshape(1, 2, 4)
        mesh = Mesh(devs, ("dp", "fsdp", "tp"))
        specs = L.param_specs(cfg)
        pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                              is_leaf=lambda s: isinstance(s, P))
        sharded = jax.device_put(params, pshard)
        with mesh:
            got = np.asarray(jax.jit(
                lambda p, i: L.generate(p, i, cfg, max_new_tokens=4))(
                    sharded, ids))
        np.testing.assert_array_equal(got, want)

    def test_temperature_sampling_draws_valid_tokens(self):
        cfg, params, ids = self._setup(seed=4)
        toks = L.generate(params, ids, cfg, max_new_tokens=5,
                          temperature=1.0, key=jax.random.PRNGKey(7))
        t = np.asarray(toks)
        assert t.shape == (2, 5)
        assert (t >= 0).all() and (t < cfg.vocab_size).all()

    def test_gqa_decode_matches_full_forward(self):
        # grouped-query attention through the cache: kv heads < q heads
        cfg = tiny(num_attention_heads=4, num_key_value_heads=2)
        params = L.init_params(cfg, jax.random.PRNGKey(6))
        ids = jnp.asarray(np.random.default_rng(6).integers(
            0, cfg.vocab_size, (2, 6)), jnp.int32)
        cache = L.init_cache(cfg, 2, 9)
        cache, logits = L.prefill(params, ids, cfg, cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        seq = jnp.concatenate([ids, tok[:, None]], axis=1)
        cache, logits = L.decode_step(params, cache, tok, cfg)
        full = L.forward(params, seq, cfg)[:, -1, :]
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                                   rtol=2e-4, atol=2e-4)

    def test_top_k_restricts_support(self):
        # with top_k=1, temperature sampling must equal greedy
        cfg, params, ids = self._setup(seed=7)
        greedy = L.generate(params, ids, cfg, max_new_tokens=4)
        topk1 = L.generate(params, ids, cfg, max_new_tokens=4,
                           temperature=1.3, top_k=1,
                           key=jax.random.PRNGKey(11))
        np.testing.assert_array_equal(np.asarray(greedy),
                                      np.asarray(topk1))

    def test_beam_search_k1_equals_greedy(self):
        cfg, params, ids = self._setup(seed=10)
        greedy = np.asarray(L.generate(params, ids, cfg,
                                       max_new_tokens=4))
        toks, scores = L.beam_search(params, ids, cfg, max_new_tokens=4,
                                     num_beams=1)
        np.testing.assert_array_equal(np.asarray(toks), greedy)
        assert np.isfinite(np.asarray(scores)).all()

    @pytest.mark.slow  # tier-1 budget (ISSUE 19 rebalance): beam-vs-naive sweep; greedy cache parity +
    # the beam invariant units keep the seam fast
    def test_beam_search_matches_naive_reference(self):
        """Differential test: the jitted static beam search must agree
        with a naive python beam search that re-runs the full forward
        for every candidate prefix."""
        cfg, params, ids = self._setup(seed=11, B=1, S=4)
        K, T = 2, 3
        toks, scores = L.beam_search(params, ids, cfg, max_new_tokens=T,
                                     num_beams=K)

        def logp_next(prefix):
            lg = L.forward(params, jnp.asarray(prefix[None]), cfg)
            return np.asarray(
                jax.nn.log_softmax(lg[0, -1].astype(jnp.float32)))

        prompt = np.asarray(ids[0])
        beams = [(0.0, prompt, [])]
        for _ in range(T):
            cands = []
            for sc, pref, out in beams:
                lp = logp_next(pref)
                top = np.argsort(lp)[::-1][:K]
                for t in top:
                    cands.append((sc + lp[t],
                                  np.concatenate([pref, [t]]),
                                  out + [int(t)]))
            cands.sort(key=lambda x: -x[0])
            beams = cands[:K]
        want_toks = beams[0][2]
        want_score = beams[0][0]
        np.testing.assert_array_equal(np.asarray(toks)[0], want_toks)
        np.testing.assert_allclose(float(scores[0]), want_score,
                                   rtol=1e-4)

    def test_beam_search_eos_freezes_beam(self):
        cfg, params, ids = self._setup(seed=12)
        base, _ = L.beam_search(params, ids, cfg, max_new_tokens=5,
                                num_beams=2)
        base = np.asarray(base)
        eos = int(base[0, 1])
        toks, _ = L.beam_search(params, ids, cfg, max_new_tokens=5,
                                num_beams=2, eos_token_id=eos,
                                pad_token_id=-1)
        toks = np.asarray(toks)
        for b in range(toks.shape[0]):
            row = toks[b].tolist()
            if eos in row:
                i = row.index(eos)
                assert all(t == -1 for t in row[i + 1:]), row

    def test_eos_stops_and_pads(self):
        cfg, params, ids = self._setup(seed=9)
        # find what greedy emits, then declare its SECOND token the EOS:
        # position 0..1 must be emitted as-is, everything after padded
        base = np.asarray(L.generate(params, ids, cfg, max_new_tokens=5))
        eos = int(base[0, 1])
        got = np.asarray(L.generate(params, ids, cfg, max_new_tokens=5,
                                    eos_token_id=eos, pad_token_id=-1))
        assert got[0, 1] == eos            # the EOS itself is emitted
        assert (got[0, 2:] == -1).all()    # then padding
        # a row that never hits EOS is untouched
        for b in range(base.shape[0]):
            if eos not in base[b]:
                np.testing.assert_array_equal(got[b], base[b])

    def test_top_p_tiny_equals_greedy_and_validates(self):
        cfg, params, ids = self._setup(seed=8)
        # a tiny nucleus keeps only the argmax token
        nucleus = L.generate(params, ids, cfg, max_new_tokens=4,
                             temperature=1.0, top_p=1e-6,
                             key=jax.random.PRNGKey(13))
        greedy = L.generate(params, ids, cfg, max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(nucleus),
                                      np.asarray(greedy))
        from paddle_tpu.core import enforce as E
        with pytest.raises(E.EnforceError):
            L.generate(params, ids, cfg, max_new_tokens=2, top_p=0.0)


class TestWeightOnlyDecode:
    """Serving with weight-only int8 weights (reference:
    nn.quant.weight_quantize in the inference pipelines): the quantized
    pytree drops into every functional entry point."""

    def _quant_and_deq(self, seed=0):
        cfg = tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(seed))
        qp = L.quantize_weights(params)
        # fp tree with the DEQUANTIZED weights: running it through the
        # plain path must match the quantized path bit-for-bit (proves
        # the _mm routing computes exactly dequant-then-matmul)
        deq = {"embed": params["embed"], "ln_f": params["ln_f"],
               "layers": {}}
        for k, w in qp["layers"].items():
            if isinstance(w, dict):
                deq["layers"][k] = (w["q"].astype(jnp.float32)
                                    * w["s"][:, None, :])
            else:
                deq["layers"][k] = w
        deq["lm_head"] = (qp["lm_head"]["q"].astype(jnp.float32)
                          * qp["lm_head"]["s"][:, None])
        return cfg, params, qp, deq

    def test_quantized_forward_equals_dequantized(self):
        cfg, _, qp, deq = self._quant_and_deq()
        ids = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 9)), jnp.int32)
        a = L.forward(qp, ids, cfg)
        b = L.forward(deq, ids, cfg)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)

    def test_quantized_logits_close_to_fp(self):
        cfg, params, qp, _ = self._quant_and_deq(seed=1)
        ids = jnp.asarray(np.random.default_rng(1).integers(
            0, cfg.vocab_size, (2, 9)), jnp.int32)
        fp = np.asarray(L.forward(params, ids, cfg))
        q = np.asarray(L.forward(qp, ids, cfg))
        # per-channel int8 keeps logits close on a tiny random model
        denom = np.maximum(np.abs(fp).max(), 1e-6)
        assert np.abs(q - fp).max() / denom < 0.05

    def test_quantized_generate_and_beam(self):
        cfg, _, qp, deq = self._quant_and_deq(seed=2)
        ids = jnp.asarray(np.random.default_rng(2).integers(
            0, cfg.vocab_size, (2, 6)), jnp.int32)
        gq = np.asarray(L.generate(qp, ids, cfg, max_new_tokens=4))
        gd = np.asarray(L.generate(deq, ids, cfg, max_new_tokens=4))
        np.testing.assert_array_equal(gq, gd)
        bq, _ = L.beam_search(qp, ids, cfg, max_new_tokens=3, num_beams=2)
        bd, _ = L.beam_search(deq, ids, cfg, max_new_tokens=3,
                              num_beams=2)
        np.testing.assert_array_equal(np.asarray(bq), np.asarray(bd))


class TestFunctionalLlama:
    def test_forward_shapes_gqa(self):
        cfg = tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        ids = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 16)))
        logits = L.forward(params, ids, cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_param_count_matches_init(self):
        cfg = tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        assert L.count_params(cfg) == sum(
            x.size for x in jax.tree.leaves(params))

    def test_causality(self):
        """Changing a future token must not change past logits."""
        cfg = tiny(num_hidden_layers=1)
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (1, 12))
        ids2 = ids.copy()
        ids2[0, -1] = (ids2[0, -1] + 1) % cfg.vocab_size
        l1 = L.forward(params, jnp.asarray(ids), cfg)
        l2 = L.forward(params, jnp.asarray(ids2), cfg)
        np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], rtol=2e-5,
                                   atol=2e-5)

    def test_train_step_converges(self):
        cfg = tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        ost = L.adamw_init(params)
        step = L.make_train_step(cfg, lr=1e-2)
        ids = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (4, 17)))
        losses = []
        for _ in range(10):
            params, ost, loss = step(params, ost, ids)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.75, losses

    @pytest.mark.slow  # tier-1 budget (ISSUE 5): heavy; run in slow lane
    def test_remat_matches_no_remat(self):
        cfg = tiny(remat=False)
        cfg_r = tiny(remat=True)
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        ids = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 9)))
        g1 = jax.grad(lambda p: L.loss_fn(p, ids, cfg))(params)
        g2 = jax.grad(lambda p: L.loss_fn(p, ids, cfg_r))(params)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class TestShardedLlama:
    def _mesh(self):
        return Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                    ("dp", "fsdp", "tp"))

    def test_sharded_step_matches_single_device(self):
        """Hybrid dp/fsdp/tp(+sp) sharded loss == single-device loss."""
        # fused_ce=False: the single-device ref must compute the SAME
        # einsum loss the GSPMD path uses, else adam amplifies the
        # blockwise-vs-materialised rounding delta past the tolerance
        # (fused-vs-einsum equivalence is tested in test_fused_ce.py)
        cfg = dataclasses.replace(tiny(), fused_ce=False)
        mesh = self._mesh()
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        ids = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (4, 17)))

        ref_step = L.make_train_step(cfg, lr=1e-2, donate=False)
        ref_params, ref_ost, ref_loss = ref_step(
            params, L.adamw_init(params), ids)

        sp_params = L.shard_params(params, cfg, mesh)
        s_step = L.make_train_step(cfg, mesh, lr=1e-2, sp=True,
                                   donate=False)
        s_ids = jax.device_put(
            ids, NamedSharding(mesh, P(("dp", "fsdp"), None)))
        s_params, s_ost, s_loss = s_step(
            sp_params, L.adamw_init(sp_params), s_ids)

        np.testing.assert_allclose(float(ref_loss), float(s_loss),
                                   rtol=1e-5)
        # gradients match (GSPMD + shard_map == single-device math): after
        # one step the first moment is (1 - b1) * g, so the step's own
        # output gives every gradient, held to f32 rounding of the leaf's
        # largest one (measured 5.5e-7)
        eps, b1 = 1e-8, 0.9                     # _adamw_update defaults
        grads = [(np.asarray(m) / (1 - b1), np.asarray(sm) / (1 - b1))
                 for m, sm in zip(jax.tree.leaves(ref_ost["m"]),
                                  jax.tree.leaves(s_ost["m"]))]
        for g, sg in grads:
            np.testing.assert_allclose(sg, g, rtol=0,
                                       atol=2e-6 * np.abs(g).max())
        # updated weights match too. Adam's first step is
        # g / (|g| + eps): the gradient's sign where |g| >> eps, but of
        # slope 1/eps where 0 < |g| < 100 eps, which turns the last bit of
        # two correct programs' re-ordered sums into percent of an lr
        # step. Those few elements are held by the gradient bound above
        # and to 5% of lr here; every other element keeps the tight bound
        n_floor = n_total = 0
        for a, b, (g, _) in zip(jax.tree.leaves(ref_params),
                                jax.tree.leaves(s_params), grads):
            a, b = np.asarray(a), np.asarray(b)
            floor = (g != 0) & (np.abs(g) < 100 * eps)
            n_floor, n_total = n_floor + floor.sum(), n_total + floor.size
            np.testing.assert_allclose(a[~floor], b[~floor],
                                       rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(a[floor], b[floor],
                                       rtol=2e-4, atol=5e-4)
        assert n_floor <= 0.005 * n_total, (n_floor, n_total)

    def test_mesh_must_divide_attention_shapes(self):
        # one attention path under a mesh (the shard_map): a batch the
        # mesh does not divide is an error here as it would be on a chip
        cfg, mesh = tiny(), self._mesh()
        params = L.shard_params(L.init_params(cfg, jax.random.PRNGKey(0)),
                                cfg, mesh)
        ids = jnp.zeros((3, 9), jnp.int32)
        with pytest.raises(ValueError, match="does not divide"):
            L.forward(params, ids, cfg, mesh=mesh)

    def test_param_placement(self):
        cfg = tiny()
        mesh = self._mesh()
        params = L.shard_params(L.init_params(cfg, jax.random.PRNGKey(0)),
                                cfg, mesh)
        assert params["layers"]["wq"].sharding.spec == P(None, "fsdp", "tp")
        assert params["embed"].sharding.spec == P("tp", "fsdp")


class TestEagerLlama:
    def test_eager_matches_functional_forward(self):
        """The Layer model and functional core compute the same function
        when weights are copied across."""
        cfg = tiny(num_hidden_layers=2)
        m = L.LlamaForCausalLM(cfg)
        params = L.init_params(cfg, jax.random.PRNGKey(3))
        # copy functional params into the Layer model
        m.embed_tokens.weight.set_value(np.asarray(params["embed"]))
        for i, layer in enumerate(m.layers):
            lp = jax.tree.map(lambda x: np.asarray(x[i]), params["layers"])
            layer.input_layernorm.weight.set_value(lp["ln1"])
            layer.q_proj.weight.set_value(lp["wq"])
            layer.k_proj.weight.set_value(lp["wk"])
            layer.v_proj.weight.set_value(lp["wv"])
            layer.o_proj.weight.set_value(lp["wo"])
            layer.post_attention_layernorm.weight.set_value(lp["ln2"])
            layer.gate_proj.weight.set_value(lp["gate"])
            layer.up_proj.weight.set_value(lp["up"])
            layer.down_proj.weight.set_value(lp["down"])
        m.norm.weight.set_value(np.asarray(params["ln_f"]))
        m.lm_head.weight.set_value(np.asarray(params["lm_head"]).T)

        ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 11))
        ref = L.forward(params, jnp.asarray(ids), cfg)
        out = m(paddle.to_tensor(ids))
        np.testing.assert_allclose(out.numpy(), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.slow  # tier-1 budget (ISSUE 3): heavy; run in the slow lane
    def test_functional_params_roundtrip_and_generate(self):
        """Layer -> functional export computes the identical function,
        and the eager .generate delegates onto the static-cache path."""
        cfg = tiny(num_hidden_layers=2)
        m = L.LlamaForCausalLM(cfg)
        params = m.functional_params()
        ids = np.random.default_rng(5).integers(0, cfg.vocab_size, (2, 9))
        ref = L.forward(params, jnp.asarray(ids), cfg)
        out = m(paddle.to_tensor(ids))
        np.testing.assert_allclose(out.numpy(), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        toks = m.generate(paddle.to_tensor(ids), max_new_tokens=3)
        want = L.generate(params, jnp.asarray(ids, jnp.int32), cfg,
                          max_new_tokens=3)
        np.testing.assert_array_equal(toks.numpy(), np.asarray(want))
        # num_beams routes to beam search through the same entry point
        bt = m.generate(paddle.to_tensor(ids), max_new_tokens=3,
                        num_beams=2)
        bw, _ = L.beam_search(params, jnp.asarray(ids, jnp.int32), cfg,
                              max_new_tokens=3, num_beams=2)
        np.testing.assert_array_equal(bt.numpy(), np.asarray(bw))

    @pytest.mark.slow  # tier-1 budget (ISSUE 20 rebalance): convergence run;
    # eager_matches_functional_forward keeps the Layer-vs-functional seam fast
    def test_eager_training_memorizes(self):
        cfg = tiny(num_hidden_layers=1)
        m = L.LlamaForCausalLM(cfg)
        o = opt.AdamW(learning_rate=3e-3, parameters=m.parameters())
        data = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (4, 17)).astype(np.int64)
        inp = paddle.to_tensor(data[:, :-1])
        tgt = paddle.to_tensor(data[:, 1:])
        first = last = None
        for _ in range(30):
            logits = m(inp)
            loss = F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                                   tgt.reshape([-1]))
            loss.backward()
            o.step()
            o.clear_grad()
            if first is None:
                first = float(loss)
            last = float(loss)
        assert last < first * 0.7, (first, last)


class TestGraftEntry:
    def test_entry_jits(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "__graft_entry__", "__graft_entry__.py")
        g = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(g)
        fn, args = g.entry()
        out = jax.jit(fn)(*args)
        assert out.shape[-1] == 256

    @pytest.mark.slow  # tier-1 budget (ISSUE 3): heavy; run in the slow lane
    def test_dryrun_multichip(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "__graft_entry__", "__graft_entry__.py")
        g = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(g)
        g.dryrun_multichip(8)
