"""On-chip correctness sweep (lesson source: interpret mode cannot see a
Mosaic lowering failure — CPU-green is not TPU-green).

Runs fwd (+bwd where differentiable) ON THE TPU for:
  weight_only_linear int8/int4, varlen flash attention, fused
  MHA/FFN/EcMoE, grid_sample, sparse.nn conv, ring attention (shard_map
  over however many devices exist), blockwise fused CE, every Pallas
  kernel at a main-path shape against its reference, the serving and
  observability planes — and then pre-tunes the kernel block sizes for
  the bench and chip_smoke.py shapes into the tracked autotune cache
  (autotune_cache.json) that both read in their never-measure "cached"
  mode.

One process holds the chip; the kill/resume cases start children that
are forced to the CPU. Emits TPU_SMOKE.json (not tracked):
{"results": {case: "ok" | "FAIL: ..."}, ...}. Exit code 0 when all
green, 1 when any case failed or the first device is not a TPU
(``SMOKE_ALLOW_CPU=1`` runs the sweep on the CPU, for checking the
script itself).
"""
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "TPU_SMOKE.json")

DEADLINE_S = float(os.environ.get("SMOKE_DEADLINE_S", "1500"))
_T0 = time.monotonic()


def _watchdog():
    while True:
        time.sleep(2)
        if time.monotonic() - _T0 > DEADLINE_S:
            _emit({"error":
                   f"smoke sweep exceeded {DEADLINE_S}s; killed by its "
                   "own watchdog"})
            os._exit(2)


def _emit(payload):
    payload["elapsed_s"] = round(time.monotonic() - _T0, 1)
    with open(OUT, "w") as f:
        json.dump(payload, f, indent=1)
    print(json.dumps(payload))


def main():
    threading.Thread(target=_watchdog, daemon=True).start()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()

    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    if not on_tpu and os.environ.get("SMOKE_ALLOW_CPU") != "1":
        _emit({"error": f"first device is {devs[0].platform}, not TPU"})
        return 1

    results = {}

    def case(name):
        def deco(fn):
            t0 = time.monotonic()
            try:
                fn()
                results[name] = "ok"
            except Exception as e:
                results[name] = f"FAIL: {type(e).__name__}: {e}"[:400]
            print(f"[{time.monotonic() - t0:6.1f}s] {name}: "
                  f"{results[name][:120]}", file=sys.stderr)
            return fn
        return deco

    rng = np.random.default_rng(0)

    @case("weight_only_linear_int8")
    def _():
        from paddle_tpu.nn.quant import weight_only_linear, weight_quantize
        x = paddle.to_tensor(rng.normal(size=(8, 256)).astype("float32"))
        w = paddle.to_tensor(rng.normal(size=(256, 128)).astype("float32"))
        qw, scale = weight_quantize(w, algo="weight_only_int8")
        out = weight_only_linear(x, qw, weight_scale=scale,
                                 weight_dtype="int8")
        float(out.sum().numpy())

    @case("weight_only_linear_int4")
    def _():
        from paddle_tpu.nn.quant import weight_only_linear, weight_quantize
        x = paddle.to_tensor(rng.normal(size=(8, 256)).astype("float32"))
        w = paddle.to_tensor(rng.normal(size=(256, 128)).astype("float32"))
        qw, scale = weight_quantize(w, algo="weight_only_int4")
        out = weight_only_linear(x, qw, weight_scale=scale,
                                 weight_dtype="int4")
        float(out.sum().numpy())

    @case("varlen_flash_attention")
    def _():
        import paddle_tpu.nn.functional as F
        q = paddle.to_tensor(
            rng.normal(size=(6, 4, 64)).astype("float32"),
            stop_gradient=False)
        cu = paddle.to_tensor(np.array([0, 2, 6], "int32"))
        out, _sm = F.flash_attn_unpadded(q, q, q, cu, cu, 4, 4)
        out.sum().backward()
        float(q.grad.sum().numpy())

    @case("fused_mha_ffn_ecmoe")
    def _():
        import paddle_tpu.incubate.nn.functional as IF
        d, nh = 64, 4
        x = paddle.to_tensor(rng.normal(size=(2, 8, d)).astype("float32"),
                             stop_gradient=False)
        qkvw = paddle.to_tensor(
            rng.normal(size=(3, nh, d // nh, d)).astype("float32") * 0.05)
        lw = paddle.to_tensor(rng.normal(size=(d, d)).astype("float32")
                              * 0.05)
        out = IF.fused_multi_head_attention(x, qkvw, lw, num_heads=nh)
        l1 = paddle.to_tensor(rng.normal(size=(d, 128)).astype("float32")
                              * 0.05)
        l2 = paddle.to_tensor(rng.normal(size=(128, d)).astype("float32")
                              * 0.05)
        out = IF.fused_feedforward(out, l1, l2)
        ne, dh = 4, 128
        gw = paddle.to_tensor(rng.normal(size=(d, ne)).astype("float32"))
        ew1 = paddle.to_tensor(
            rng.normal(size=(ne, d, dh)).astype("float32") * 0.05)
        eb1 = paddle.to_tensor(np.zeros((ne, dh), "float32"))
        ew2 = paddle.to_tensor(
            rng.normal(size=(ne, dh, d)).astype("float32") * 0.05)
        eb2 = paddle.to_tensor(np.zeros((ne, d), "float32"))
        out = IF.fused_ec_moe(out, gw, ew1, eb1, ew2, eb2)
        out.sum().backward()
        float(x.grad.sum().numpy())

    @case("grid_sample_grad")
    def _():
        import paddle_tpu.nn.functional as F
        x = paddle.to_tensor(
            rng.normal(size=(1, 2, 8, 8)).astype("float32"),
            stop_gradient=False)
        grid = paddle.to_tensor(
            rng.uniform(-1, 1, size=(1, 4, 4, 2)).astype("float32"))
        out = F.grid_sample(x, grid)
        out.sum().backward()
        float(x.grad.sum().numpy())

    @case("sparse_conv")
    def _():
        import paddle_tpu.sparse as sparse
        dense = np.zeros((1, 8, 8, 3), "float32")
        dense[0, 2, 3, :] = 1.0
        st = sparse.sparse_coo_tensor_from_dense(paddle.to_tensor(dense))
        conv = sparse.nn.Conv2D(3, 4, 3, padding=1)
        out = conv(st)
        float(out.to_dense().sum().numpy())

    @case("ring_attention_shard_map")
    def _():
        from jax.sharding import Mesh

        from paddle_tpu.kernels import ring_attention
        n = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()).reshape(n), ("sp",))
        q = jnp.asarray(rng.normal(size=(2, 16 * n, 2, 32)), jnp.float32)
        out = ring_attention(q, q, q, mesh, causal=True)
        float(jnp.sum(out).astype(jnp.float32))

    @case("fused_cross_entropy_grad")
    def _():
        from paddle_tpu.kernels import fused_cross_entropy
        x = jnp.asarray(rng.normal(size=(4, 16, 64)), jnp.bfloat16)
        head = jnp.asarray(rng.normal(size=(1000, 64)) * 0.1, jnp.bfloat16)
        labels = jnp.asarray(rng.integers(0, 1000, (4, 16)), jnp.int32)
        loss, grads = jax.value_and_grad(
            lambda x, h: fused_cross_entropy(x, h, labels,
                                             vocab_chunk=256),
            argnums=(0, 1))(x, head)
        float(loss)

    @case("moe_capacity_dispatch_train")
    def _():
        # the bench MoE rung's dispatch mode, at toy shapes: capacity
        # gather + expert matmuls + drop path must compile AND grad
        from paddle_tpu.models import llama as L
        from paddle_tpu.models import moe as M
        cfg = M.moe_tiny(dispatch_mode="capacity", dtype=jnp.bfloat16,
                         capacity_factor=1.0)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        opt = L.adamw_init(params)
        # guard=False: this stage measures MoE dispatch, not the
        # sentinel gate (nan_skip_resume covers the guarded step) —
        # and must keep its 3-in/3-out shape under chaos-run flags
        step = M.make_train_step(cfg, lr=1e-3, guard=False)
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 17)),
                          jnp.int32)
        _, _, loss = step(params, opt, ids)
        assert np.isfinite(float(loss))

    @case("kv_cache_decode")
    def _():
        # the bench decode rung's path at toy shapes: prefill + jitted
        # generate scan over decode steps
        from paddle_tpu.models import llama as L
        cfg = L.llama_tiny(num_hidden_layers=2, dtype=jnp.bfloat16)
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)),
                          jnp.int32)
        toks = jax.jit(lambda p, i: L.generate(
            p, i, cfg, max_new_tokens=4))(params, ids)
        t = np.asarray(toks)
        assert t.shape == (2, 4) and (t >= 0).all()

    @case("paged_decode")
    def _():
        # the serving engine's full lifecycle on the real chip: prefill,
        # a request JOINING mid-stream (continuous batching), EOS/max-len
        # retirement, and the page pool draining back to empty
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L
        cfg = L.llama_tiny(num_hidden_layers=2, dtype=jnp.bfloat16)
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        # page_size 16 = the bf16 sublane tile, so on-chip this drives
        # the pallas kernel through the engine (8 would jnp-fallback)
        eng = ServingEngine(L, params, cfg, num_slots=2, max_len=32,
                            page_size=16, decode_chunk=2)
        eng.submit(Request(rid=0, prompt=rng.integers(
            0, cfg.vocab_size, (6,)).astype(np.int32), max_new_tokens=8))
        eng.step()                      # rid 0 prefilled + decoding
        assert eng.stats.admitted == 1
        eng.submit(Request(rid=1, prompt=rng.integers(
            0, cfg.vocab_size, (4,)).astype(np.int32), max_new_tokens=4))
        eng.step()                      # rid 1 joins mid-stream
        assert eng.stats.admitted == 2
        outs = eng.run()                # decode to retirement
        assert sorted(outs) == [0, 1]
        assert len(outs[0].tokens) == 8 and len(outs[1].tokens) == 4
        # retirement freed every page
        assert eng.cache.alloc.used_pages == 0, \
            f"leaked pages: {eng.cache.alloc.used_pages}"
        eng.cache.alloc.check_invariants()

    @case("kv_quant_decode")
    def _():
        # quantized memory plane (FLAGS_serving_kv_quant) on the real
        # chip: the same trace served from int8 page pools must emit
        # the full-precision pools' greedy tokens and drain the pool.
        # page_size 32 = the int8 sublane tile, so on-chip this drives
        # the quantized pallas kernel arm (not the jnp fallback)
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L
        # f32 like the prefix_cache stage: a random tiny model's logit
        # gaps sit inside bf16 cross-program rounding noise. int8 KV
        # quantization is additionally LOSSY, so even in f32 a greedy
        # argmax whose top-2 gap is inside the quantization noise can
        # legitimately flip — the assert below tolerates exactly that
        # (runner-up token at a tiny fp gap) and nothing else.
        cfg = L.llama_tiny(num_hidden_layers=2)
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (6, 9)]

        def serve(kv_quant):
            eng = ServingEngine(L, params, cfg, num_slots=2, max_len=64,
                                page_size=32, decode_chunk=2,
                                kv_quant=kv_quant)
            outs = eng.run([Request(rid=i, prompt=p, max_new_tokens=6)
                            for i, p in enumerate(prompts)])
            assert eng.cache.alloc.used_pages == 0, \
                f"leaked pages: {eng.cache.alloc.used_pages}"
            eng.cache.alloc.check_invariants()
            return {i: np.asarray(o.tokens) for i, o in outs.items()}, eng

        want, _ = serve(kv_quant=False)
        got, qeng = serve(kv_quant=True)
        assert isinstance(qeng.cache.pool["k"], dict), "pool not quantized"
        for i in want:
            eq = want[i] == got[i]
            if eq.all():
                continue
            # benign near-tie flip: the quant run may take the greedy
            # runner-up when the fp top-2 gap is inside the int8 noise
            # floor; anything else (wrong rank, fat gap) is a real bug
            k = int(np.argmin(eq))
            ctx = np.concatenate([prompts[i], want[i][:k]])
            lg = np.asarray(
                L.forward(params, jnp.asarray(ctx)[None, :], cfg)[0, -1],
                np.float64)
            order = np.argsort(lg)[::-1]
            gap = float(lg[order[0]] - lg[order[1]])
            assert int(order[1]) == int(got[i][k]) and gap < 1e-2, (
                f"rid {i} diverged at token {k}: fp={want[i][k]} "
                f"quant={got[i][k]}, fp top-2 gap {gap:.3e} — not a "
                f"near-tie flip")

    @case("operator_scrape")
    def _():
        # the operator plane against the real chip: start the telemetry
        # server, run a serving chunk, scrape /metrics + /healthz, and
        # assert the text parses with the key gauges nonzero — the
        # end-to-end proof an external Prometheus would see real numbers
        import json as _json
        import urllib.request
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L
        from paddle_tpu.monitor import server as mon_server
        paddle.set_flags({"FLAGS_enable_monitor": True,
                          "FLAGS_enable_monitor_server": True})
        try:
            cfg = L.llama_tiny(num_hidden_layers=2, dtype=jnp.bfloat16)
            params = L.init_params(cfg, jax.random.PRNGKey(0))
            eng = ServingEngine(L, params, cfg, num_slots=2, max_len=32,
                                page_size=16, decode_chunk=2)
            srv = mon_server.get_server()
            assert srv is not None, "engine did not start the server"
            outs = eng.run([Request(
                rid=i, prompt=rng.integers(0, cfg.vocab_size, (6,))
                .astype(np.int32), max_new_tokens=6) for i in range(3)])
            assert len(outs) == 3
            txt = urllib.request.urlopen(
                f"{srv.url}/metrics", timeout=10).read().decode()
            # parseable: every non-comment line is "name[{labels}] value"
            samples = {}
            for line in txt.splitlines():
                if not line or line.startswith("#"):
                    continue
                name, val = line.rsplit(" ", 1)
                samples[name.split("{")[0]] = float(val)
            for gauge in ("serving_tokens_generated",
                          "serving_pages_total",
                          "serving_latency_ttft_ms_count",
                          "jit_program_flops"):
                assert samples.get(gauge, 0) > 0, \
                    f"{gauge} missing/zero in /metrics: " \
                    f"{sorted(samples)[:40]}"
            hz = urllib.request.urlopen(f"{srv.url}/healthz", timeout=10)
            payload = _json.load(hz)
            assert hz.status == 200 and payload["status"] == "ok"
            assert any(k.startswith("serving:")
                       for k in payload["providers"])
            mem = _json.load(urllib.request.urlopen(
                f"{srv.url}/memory", timeout=10))
            if on_tpu:   # the TPU PJRT client reports memory_stats
                assert mem["hbm"]["totals"].get("bytes_in_use", 0) > 0
        finally:
            mon_server.stop_server()
            paddle.set_flags({"FLAGS_enable_monitor": False,
                              "FLAGS_enable_monitor_server": False})
            from paddle_tpu import monitor as _mon
            _mon.reset()

    @case("roofline_scrape")
    def _():
        # comm/roofline observability on the real chip: a guarded train
        # step + an engine run populate the program registry, then
        # /roofline must classify both (nonzero FLOPs + bytes-accessed,
        # non-null boundedness verdict) and /sharding must report
        # per-leaf layouts. On TPU the HBM-bandwidth denominator must
        # come from the real generation table, not a fallback.
        import json as _json
        import urllib.request
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L
        from paddle_tpu.monitor import server as mon_server
        paddle.set_flags({"FLAGS_enable_monitor": True,
                          "FLAGS_enable_monitor_server": True})
        from paddle_tpu.monitor import exectime as mon_exectime
        mon_exectime.set_sample_rate(1)   # every dispatch measured
        try:
            cfg = L.llama_tiny(num_hidden_layers=2, dtype=jnp.bfloat16)
            params = L.init_params(cfg, jax.random.PRNGKey(0))
            # guarded train step through the to_static-equivalent
            # registration path: the registry must see a training
            # program, not just serving
            from paddle_tpu.monitor import programs as mon_programs
            step = L.make_train_step(cfg, lr=1e-3, donate=False,
                                     guard=False)
            opt = L.adamw_init(params)
            ids = jnp.asarray(rng.integers(
                0, cfg.vocab_size, (2, 32)).astype(np.int32))
            params, opt, _loss = step(params, opt, ids)
            mon_programs.record_jit_call(
                ("smoke.train_step",), "llama.train_step", step,
                (params, opt, ids))
            # measured side: an explicitly timed execution so the
            # train-step record carries exec stats for calibration
            mon_exectime.time_call(("smoke.train_step",), step,
                                   params, opt, ids)
            eng = ServingEngine(L, params, cfg, num_slots=2, max_len=32,
                                page_size=16, decode_chunk=2)
            eng.run([Request(
                rid=i, prompt=rng.integers(0, cfg.vocab_size, (6,))
                .astype(np.int32), max_new_tokens=4) for i in range(2)])
            srv = mon_server.get_server()
            assert srv is not None, "engine did not start the server"
            rl = _json.load(urllib.request.urlopen(
                f"{srv.url}/roofline", timeout=30))
            progs = {p["name"]: p for p in rl["programs"]}
            assert "llama.train_step" in progs, sorted(progs)
            assert any(n.startswith("serving.decode_chunk")
                       for n in progs), sorted(progs)
            for name, p in progs.items():
                if name == "llama.train_step" or \
                        name.startswith("serving.decode_chunk"):
                    assert p["flops"] and p["flops"] > 0, (name, p)
                    assert p["bytes_accessed"] and \
                        p["bytes_accessed"] > 0, (name, p)
                    assert p["verdict"] in ("compute-bound",
                                            "hbm-bound",
                                            "comm-bound"), (name, p)
                    # comm accounting ran (counts may be 0 on one chip,
                    # but the scan itself must have happened)
                    assert p["comms_analyzed"], (name, p)
                    assert isinstance(p["collective_ops"], int)
            if on_tpu:
                assert rl["peaks"]["hbm_source"] == "table", rl["peaks"]
            # roofline CALIBRATION: at least one registered program
            # must report a measured/modeled error ratio (non-null,
            # never fabricated) — the acceptance gate of the measured
            # performance plane
            measured = [p for p in rl["programs"]
                        if p.get("model_error_ratio") is not None]
            assert measured, \
                "no program reported model_error_ratio at /roofline"
            assert rl["calibration"]["measured_programs"] >= 1, \
                rl["calibration"]
            assert rl["calibration"]["max_error_ratio"] > 0
            sh = _json.load(urllib.request.urlopen(
                f"{srv.url}/sharding", timeout=10))
            assert any(k.endswith(".params") for k in sh["trees"]), \
                sorted(sh["trees"])
            tree = next(v for k, v in sh["trees"].items()
                        if k.endswith(".params"))
            assert tree["num_arrays"] > 0 and tree["leaves"]
            leaf = tree["leaves"][0]
            assert leaf["shard_bytes"] > 0 and leaf["dtype"]
            assert any(p["name"].startswith("serving.")
                       for p in sh["programs"])
        finally:
            mon_exectime.set_sample_rate(None)
            mon_server.stop_server()
            paddle.set_flags({"FLAGS_enable_monitor": False,
                              "FLAGS_enable_monitor_server": False})
            from paddle_tpu import monitor as _mon
            _mon.reset()

    @case("profile_capture")
    def _():
        # on-demand device profiler capture end to end: flags on, a
        # short engine run DURING the /profile?seconds=1 window, then a
        # parseable trace directory. TPU asserts device events landed
        # in the xplane (CPU accepts host-only traces).
        import json as _json
        import tempfile
        import urllib.request
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L
        from paddle_tpu.monitor import profile_capture as pcap
        from paddle_tpu.monitor import server as mon_server
        paddle.set_flags({"FLAGS_enable_monitor": True,
                          "FLAGS_enable_monitor_server": True})
        prof_dir = tempfile.mkdtemp(prefix="smoke_prof_")
        os.environ["PADDLE_TPU_PROFILE_DIR"] = prof_dir
        try:
            cfg = L.llama_tiny(num_hidden_layers=2, dtype=jnp.bfloat16)
            params = L.init_params(cfg, jax.random.PRNGKey(0))
            eng = ServingEngine(L, params, cfg, num_slots=2, max_len=32,
                                page_size=16, decode_chunk=2)
            eng.run([Request(       # compile OUTSIDE the window
                rid=0, prompt=rng.integers(0, cfg.vocab_size, (6,))
                .astype(np.int32), max_new_tokens=4)])
            srv = mon_server.get_server()
            assert srv is not None

            stop = threading.Event()

            def churn():
                # throttled: the point is device events DURING the
                # window, not maximum op volume — an unthrottled tiny-
                # model loop floods the host tracer and stop_trace
                # then spends a minute serializing it on CPU
                rid = 100
                while not stop.is_set():
                    eng.run([Request(
                        rid=rid, prompt=rng.integers(
                            0, cfg.vocab_size, (6,)).astype(np.int32),
                        max_new_tokens=4)])
                    rid += 1
                    stop.wait(0.25)

            t = threading.Thread(target=churn, daemon=True)
            t.start()
            try:
                # generous timeout: the window is 1s but stop_trace
                # serialization scales with traced op volume
                info = _json.load(urllib.request.urlopen(
                    f"{srv.url}/profile?seconds=1", timeout=240))
            finally:
                stop.set()
                t.join(timeout=60)
            assert info["files"], f"empty capture: {info}"
            xplanes = [f for f in info["files"]
                       if f["path"].endswith(".xplane.pb")
                       and (f["bytes"] or 0) > 0]
            assert xplanes, f"no xplane in capture: {info['files']}"
            assert os.path.isdir(info["dir"])
            if on_tpu:
                blob = b""
                for f in xplanes:
                    with open(os.path.join(info["dir"], f["path"]),
                              "rb") as fh:
                        blob += fh.read()
                assert b"TPU" in blob, \
                    "no device events in the TPU capture"
        finally:
            mon_server.stop_server()
            os.environ.pop("PADDLE_TPU_PROFILE_DIR", None)
            paddle.set_flags({"FLAGS_enable_monitor": False,
                              "FLAGS_enable_monitor_server": False})
            from paddle_tpu import monitor as _mon
            _mon.reset()

    @case("drift_detect")
    def _():
        # step-time drift detection end to end through the StepTimer
        # seam: a synthetic slowdown (sleep-padded compute phases) must
        # trip train.step.drift_ratio and the /timeseries drift report
        from paddle_tpu import monitor as _mon
        from paddle_tpu.monitor import timeseries as ts
        paddle.set_flags({"FLAGS_enable_monitor": True})
        try:
            _mon.reset()
            st = _mon.StepTimer("smoke.drift")
            for i in range(16):          # baseline: fast steps
                with st.compute():
                    time.sleep(0.004)
                st.end_step()
            for i in range(8):           # recent: 4x slower
                with st.compute():
                    time.sleep(0.016)
                st.end_step()
            status = ts.drift_status()
            assert status["ratio"] and status["ratio"] > 1.25, status
            assert status["drifting"], status
            g = _mon.snapshot()["gauges"].get("train.step.drift_ratio")
            assert g and g > 1.25, f"drift gauge did not trip: {g}"
        finally:
            paddle.set_flags({"FLAGS_enable_monitor": False})
            _mon.reset()

    @case("numerics_scrape")
    def _():
        # the numerics plane end to end: numerics-enabled guarded
        # steps + engine churn with KV sampling, then /numerics must
        # serve per-layer grad stats, a worst-layer attribution, a
        # finite nonzero int8 SQNR audit, and KV-page absmax samples
        import json as _json
        import urllib.request
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L
        from paddle_tpu.monitor import numerics as mon_numerics
        from paddle_tpu.monitor import server as mon_server
        from paddle_tpu.training.sentinel import (AnomalySentinel,
                                                  SentinelConfig,
                                                  SentinelLoop)
        paddle.set_flags({"FLAGS_enable_monitor": True,
                          "FLAGS_enable_monitor_server": True,
                          "FLAGS_enable_numerics": True})
        mon_numerics.set_kv_sample_rate(1)
        try:
            cfg = L.llama_tiny(num_hidden_layers=2, vocab_size=64)
            params = L.init_params(cfg, jax.random.PRNGKey(0))
            opt = L.adamw_init(params)
            step = L.make_train_step(cfg, lr=1e-3, guard=True,
                                     donate=False)

            def batches():
                for i in range(4):
                    r = np.random.default_rng(2000 + i)
                    ids = r.integers(0, 64, (2, 33)).astype(np.int32)
                    yield ids[:, :-1], ids[:, 1:]

            loop = SentinelLoop(step, params, opt, batches,
                                sentinel=AnomalySentinel(
                                    SentinelConfig(agree=False)))
            out = loop.run(4)
            assert out["applied"] == 4, out
            # int8 audit through the shared seam contract
            mon_numerics.audit_quantized_tree(
                params, L.quantize_weights(params),
                serving_dtype=jnp.bfloat16)
            eng = ServingEngine(L, params, cfg, num_slots=2,
                                max_len=32, page_size=16,
                                decode_chunk=2)
            eng.run([Request(
                rid=i, prompt=rng.integers(0, 64, (6,))
                .astype(np.int32), max_new_tokens=6)
                for i in range(2)])
            srv = mon_server.get_server()
            assert srv is not None, "loop did not start the server"
            p = _json.load(urllib.request.urlopen(
                f"{srv.url}/numerics", timeout=10))
            assert p["total_steps"] == 4, p["total_steps"]
            assert any(k.startswith("layers.") for k in p["tensors"]), \
                sorted(p["tensors"])[:10]
            wq0 = p["tensors"]["layers.wq[0]"]
            assert wq0["gnorm"] and wq0["gnorm"] > 0
            assert wq0["absmax_ema"] and wq0["absmax_ema"] > 0
            assert p["worst_layer"]["name"] and \
                p["worst_layer"]["finite"]
            for name, ent in p["quant"]["tensors"].items():
                assert ent["sqnr_db"] and ent["sqnr_db"] > 0, \
                    (name, ent)
            assert p["quant"]["min_sqnr_db"] > 0
            assert p["kv"]["samples"] > 0 and p["kv"]["max"] > 0
            # sentinel health report names a layer
            hz = _json.load(urllib.request.urlopen(
                f"{srv.url}/healthz", timeout=10))
            sent = next(v for k, v in hz["providers"].items()
                        if k.startswith("sentinel:"))
            assert sent["worst_layer"], sent
        finally:
            mon_numerics.set_kv_sample_rate(None)
            mon_server.stop_server()
            paddle.set_flags({"FLAGS_enable_monitor": False,
                              "FLAGS_enable_monitor_server": False,
                              "FLAGS_enable_numerics": False})
            from paddle_tpu import monitor as _mon
            _mon.reset()

    @case("slo_scrape")
    def _():
        # the SLO accounting plane end to end: a mixed-tenant engine
        # run with one forced preemption (tiny page pool), scraped
        # mid-run (autoscale demand nonzero) and after drain — /slo
        # must serve finite burn rates + per-tenant cost aggregates,
        # /metrics must carry the tenant-labeled series (hostile
        # tenant names escaped, not corrupting), and a malformed
        # submission must land in the availability window
        import json as _json
        import urllib.request
        from paddle_tpu.inference import (Request, RequestRejected,
                                          ServingEngine)
        from paddle_tpu.models import llama as L
        from paddle_tpu.monitor import server as mon_server
        from paddle_tpu.monitor import slo as mon_slo
        paddle.set_flags({"FLAGS_enable_monitor": True,
                          "FLAGS_enable_monitor_server": True})
        try:
            cfg = L.llama_tiny(num_hidden_layers=2)
            params = L.init_params(cfg, jax.random.PRNGKey(0))
            # 5-page pool, 2 slots: three 12-token sequences cannot
            # coexist -> at least one recompute preemption (the
            # test_trace token-invariant shape)
            eng = ServingEngine(L, params, cfg, num_slots=2,
                                max_len=16, page_size=4, num_pages=5,
                                decode_chunk=2)
            tenants = ["alpha", "beta", 'evil"\n\\tenant']
            # 15 requests: 5 per tenant, clearing the per-tenant
            # min-sample floor (5) so tenant_compliance can answer
            reqs = [Request(rid=i,
                            prompt=rng.integers(0, cfg.vocab_size, (4,))
                            .astype(np.int32),
                            max_new_tokens=8 if i < 3 else 3,
                            tenant=tenants[i % 3], priority=i % 2)
                    for i in range(15)]
            for r in reqs:
                eng.submit(r)
            for _i in range(3):                # mid-run: backlog live
                eng.step()
            srv = mon_server.get_server()
            assert srv is not None, "engine did not start the server"
            mid = _json.load(urllib.request.urlopen(
                f"{srv.url}/slo", timeout=30))
            asc = mid["autoscale"]
            assert asc["available"] and not asc["drain_safe"], asc
            assert asc["demand_estimate"] > 0, asc
            assert asc["desired_capacity_hint"] >= 1, asc
            eng.run()                          # drain
            assert eng.stats.preempted >= 1, eng.stats.as_dict()
            try:
                # malformed AFTER alpha earned its label slot: the
                # rejection attributes to the claimed tenant and
                # enters the availability window
                eng.submit(Request(rid=99, prompt=reqs[0].prompt,
                                   max_new_tokens=3, tenant="alpha",
                                   priority=1.5))      # not integral
                raise AssertionError("bad priority was not rejected")
            except RequestRejected:
                pass
            pre = [o for o in eng.outputs.values()
                   if o.cost and o.cost.preemptions >= 1]
            assert pre, "no output carries a preempted cost record"
            assert pre[0].cost.queue_wait_ms > 0
            p = _json.load(urllib.request.urlopen(
                f"{srv.url}/slo", timeout=30))
            comp = p["compliance"]["objectives"]
            for obj in ("availability", "ttft_p99_ms", "e2e_p99_ms"):
                st = comp[obj]
                assert st["compliance"] is not None, (obj, st)
                for k in ("burn_fast", "burn_slow", "budget_remaining"):
                    assert st[k] is not None and \
                        np.isfinite(st[k]), (obj, k, st)
            # the rejected submission entered the availability window
            assert comp["availability"]["compliance"] < 1.0, comp
            tl = p["tenants"]["tenants"]
            for t in tenants:
                assert t in tl, sorted(tl)
                assert tl[t]["decode_tokens"] > 0, (t, tl[t])
                assert tl[t]["page_seconds"] > 0, (t, tl[t])
            tc = p["tenant_compliance"]
            assert tc["alpha"]["availability"] is not None, tc
            assert tl["alpha"]["rejected"] >= 1, tl["alpha"]
            assert p["autoscale"]["drain_safe"], p["autoscale"]
            text = urllib.request.urlopen(
                f"{srv.url}/metrics", timeout=30).read().decode()
            assert 'slo_tenant_requests{tenant="alpha"}' in text
            # hostile tenant name rides label ESCAPING, never raw bytes
            assert 'tenant="evil\\"\\n\\\\tenant"' in text, \
                [ln for ln in text.splitlines() if "slo_tenant" in ln][:3]
            assert "serving_autoscale_drain_safe 1" in text
            assert "slo_window_requests" in text
        finally:
            mon_server.stop_server()
            paddle.set_flags({"FLAGS_enable_monitor": False,
                              "FLAGS_enable_monitor_server": False})
            from paddle_tpu import monitor as _mon
            _mon.reset()

    @case("overload_drain")
    def _():
        # the acting control plane end to end on the real backend:
        # submit -> shed -> drain. A bounded-queue priority-admission
        # engine under a burst must shed low-priority work with a
        # typed EngineOverloaded + demand-model retry hint, displace
        # for high priority, expire a deadline, finish everything
        # admitted, then drain clean (drain_safe flips, queue shed
        # with hints, live decodes retired) — every submit accounted
        # in exactly one terminal state
        from paddle_tpu.inference import (EngineOverloaded, Request,
                                          ServingEngine)
        from paddle_tpu.models import llama as L

        cfg = L.llama_tiny(num_hidden_layers=2)
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(L, params, cfg, num_slots=2, max_len=16,
                            page_size=4, decode_chunk=2,
                            priority_admission=True, max_queue=3,
                            slo_preemption=True)

        def mk(rid, **kw):
            return Request(rid=rid,
                           prompt=rng.integers(0, cfg.vocab_size, (5,))
                           .astype(np.int32),
                           max_new_tokens=6, **kw)
        shed_rids, submitted = [], []
        for i in range(8):                      # burst > slots + queue
            try:
                eng.submit(mk(i, priority=0))
                submitted.append(i)
            except EngineOverloaded as e:
                assert e.retry_after_s >= 1.0, e.retry_after_s
                shed_rids.append(i)
        assert shed_rids, "burst did not shed over the bounded queue"
        eng.submit(mk(100, priority=5))          # displaces a low
        submitted.append(100)
        displaced = [r for r, o in eng.outputs.items()
                     if o.finish_reason == "shed"]
        assert len(displaced) == 1, displaced
        eng.submit(mk(101, priority=5, deadline_s=1e-4))
        submitted.append(101)
        time.sleep(0.01)                        # deadline burns out
        for _ in range(3):
            eng.step()
        eng.begin_drain()                        # shed queue, finish live
        try:
            eng.submit(mk(200))
            raise AssertionError("draining engine accepted a submit")
        except EngineOverloaded:
            shed_rids.append(200)                # drain refusal counts
        eng.run()
        assert eng.drain_complete
        assert eng.autoscale_payload()["drain_safe"]
        states = {r: o.finish_reason for r, o in eng.outputs.items()}
        assert sorted(states) == sorted(submitted), (states, submitted)
        assert states[100] == "completed", states
        assert states[101] == "expired", states
        assert eng.stats.completed + eng.stats.expired \
            + eng.stats.shed == len(submitted) + len(shed_rids)
        emitted = sum(len(o.tokens) for o in eng.outputs.values())
        assert eng.stats.tokens_generated \
            - eng.stats.tokens_discarded == emitted
        eng.cache.alloc.check_invariants()
        assert eng.cache.alloc.free_pages == eng.cache.num_pages

    @case("request_forensics")
    def _():
        # the forensics plane end to end on the real backend: a
        # mixed-priority overload run with forced preemption (tiny
        # page pool), then scrape /forensics and /requests/<rid> —
        # the preempted request's timeline must show the preemption
        # with its victim-selection inputs, every terminal request
        # exactly one terminal event, and phases summing to e2e
        import json as _json
        import urllib.request
        from paddle_tpu.inference import (EngineOverloaded, Request,
                                          ServingEngine)
        from paddle_tpu.models import llama as L
        from paddle_tpu.monitor import forensics as mon_forensics
        from paddle_tpu.monitor import server as mon_server
        paddle.set_flags({"FLAGS_enable_monitor": True,
                          "FLAGS_enable_monitor_server": True})
        try:
            cfg = L.llama_tiny(num_hidden_layers=2)
            params = L.init_params(cfg, jax.random.PRNGKey(0))
            # 5-page pool, 2 slots: three 12-token sequences cannot
            # coexist -> at least one recompute preemption
            eng = ServingEngine(L, params, cfg, num_slots=2,
                                max_len=16, page_size=4, num_pages=5,
                                decode_chunk=2, max_queue=3)

            def mk(rid, **kw):
                return Request(rid=rid, prompt=rng.integers(
                    0, cfg.vocab_size, (4,)).astype(np.int32),
                    max_new_tokens=8, **kw)
            shed = []
            for i in range(6):                  # burst > slots + queue
                try:
                    eng.submit(mk(i, priority=i % 2,
                                  tenant=f"t{i % 2}"))
                except EngineOverloaded:
                    shed.append(i)
            assert shed, "burst did not shed over the bounded queue"
            eng.run()
            assert eng.stats.preempted >= 1, eng.stats.as_dict()
            srv = mon_server.get_server()
            assert srv is not None, "engine did not start the server"
            p = _json.load(urllib.request.urlopen(
                f"{srv.url}/forensics", timeout=30))
            assert p["kind"] == "paddle_tpu.forensics"
            by_state = p["terminal_by_state"]
            assert by_state.get("completed") and by_state.get("shed")
            assert p["decisions"]["by_kind"].get("preempt"), \
                p["decisions"]["by_kind"]
            term = set(mon_forensics._TERMINAL_KIND.values())
            preempted = None
            for rid_s in p["requests"]:
                tl = _json.load(urllib.request.urlopen(
                    f"{srv.url}/requests/{rid_s}", timeout=30))
                assert tl["state"] is not None, tl
                kinds = [e["kind"] for e in tl["events"]]
                assert sum(k in term for k in kinds) == 1, tl
                if tl["e2e_ms"] is not None:
                    assert abs(tl["phase_sum_ms"] - tl["e2e_ms"]) \
                        <= 1.0, tl
                if "preempt" in kinds:
                    preempted = tl
            assert preempted is not None, "no timeline saw preemption"
            ev = next(e for e in preempted["events"]
                      if e["kind"] == "preempt")
            for k in ("policy", "slot", "prior_preemptions", "work",
                      "discarded"):
                assert k in ev, (k, ev)
            assert preempted["phases"]["preempted_out"] > 0, preempted
            # a shed rid answers on /requests/<rid> too (terminal-only)
            tl = _json.load(urllib.request.urlopen(
                f"{srv.url}/requests/{shed[0]}", timeout=30))
            assert tl["state"] == "shed", tl
        finally:
            mon_server.stop_server()
            paddle.set_flags({"FLAGS_enable_monitor": False,
                              "FLAGS_enable_monitor_server": False})
            from paddle_tpu import monitor as _mon
            _mon.reset()

    @case("prefix_cache")
    def _():
        # radix shared-prefix KV cache on the real backend: two
        # requests opening with the same 16-token system prefix run
        # serially (the first's retirement seeds the radix), the
        # second must fork cached pages — its prefill token count
        # shrinks by the page-aligned prefix — and every emitted token
        # must match the cache-off run byte for byte. A spec-decode
        # engine then replays one request and must also match.
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L

        # f32: the parity asserts compare tokens across differently
        # shaped programs (full vs shared prefill, turbo chunk vs
        # verify window) — identical math, but this random model's
        # logit gaps sit inside bf16 cross-program rounding noise, so
        # bf16 argmax ties could flip on the real chip
        cfg = L.llama_tiny(num_hidden_layers=2, dtype=jnp.float32)
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        prompts = [np.concatenate([prefix, rng.integers(
            0, cfg.vocab_size, (n,)).astype(np.int32)]) for n in (5, 3)]

        def serve(**kw):
            eng = ServingEngine(L, params, cfg, num_slots=2, max_len=48,
                                page_size=4, decode_chunk=2, **kw)
            outs = {}
            for i, p in enumerate(prompts):     # serial: retire seeds
                outs.update(eng.run([Request(
                    rid=i, prompt=p, max_new_tokens=5)]))
            eng.cache.alloc.check_invariants()
            return eng, outs

        eng_off, outs_off = serve()
        eng_on, outs_on = serve(prefix_cache=True)
        for i in range(len(prompts)):
            np.testing.assert_array_equal(outs_on[i].tokens,
                                          outs_off[i].tokens)
        assert eng_on.stats.prefix_hits >= 1, eng_on.stats.as_dict()
        saved = eng_on.stats.prefix_tokens_saved
        assert saved >= 16, saved               # the full aligned prefix
        assert eng_on.stats.tokens_prefilled \
            == eng_off.stats.tokens_prefilled - saved
        # cache holds outlive retirement: the radix pins pages the
        # free-pool no longer counts (the off engine drained to empty)
        assert eng_off.cache.alloc.free_pages == eng_off.cache.num_pages
        assert eng_on.cache.alloc.free_pages < eng_on.cache.num_pages
        # spec decode: greedy token identity through the verify window
        eng_sp = ServingEngine(L, params, cfg, num_slots=1, max_len=64,
                               page_size=4, decode_chunk=2,
                               spec_decode=True)
        outs_sp = eng_sp.run([Request(rid=0, prompt=prompts[0],
                                      max_new_tokens=16)])
        eng_ref = ServingEngine(L, params, cfg, num_slots=1, max_len=64,
                                page_size=4, decode_chunk=2)
        outs_ref = eng_ref.run([Request(rid=0, prompt=prompts[0],
                                        max_new_tokens=16)])
        np.testing.assert_array_equal(outs_sp[0].tokens,
                                      outs_ref[0].tokens)
        assert eng_sp.stats.spec_rounds > 0, eng_sp.stats.as_dict()
        eng_sp.cache.alloc.check_invariants()

    @case("fleet_federation")
    def _():
        # fleet SLO federation end to end on the real backend: two
        # in-process engines publish telemetry frames through the
        # name-keyed heartbeat transport; the elastic controller
        # (FLAGS_serving_fleet_burn_scaling on) scales OUT on an
        # injected fast-burn at flat demand and refuses scale-in
        # while it alerts; /fleet/serving names the burning replica
        # on attribution line 1; beat files are swept on retirement
        import json as _json
        import tempfile
        import threading
        import urllib.request
        from paddle_tpu.distributed import heartbeat as hb
        from paddle_tpu.distributed.fleet.elastic import (
            AdaptiveElasticManager)
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L
        from paddle_tpu.monitor import federation as fed
        from paddle_tpu.monitor import server as mon_server
        paddle.set_flags({"FLAGS_enable_monitor": True,
                          "FLAGS_enable_monitor_server": True})
        fed.reset()
        hb_dir = tempfile.mkdtemp(prefix="smoke_fed_")
        cfg = L.llama_tiny(num_hidden_layers=1)
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        burning = [True]

        def burn_report():
            # injected per-replica report: replica0 fast-burns while
            # `burning` holds (the in-process engines share the global
            # slo ring, so per-replica burns are injected here)
            hot = burning[0]
            return {"objectives": {"ttft_p99_ms": {
                "compliance": 0.5 if hot else 1.0,
                "burn_fast": 40.0 if hot else 0.0,
                "burn_slow": 30.0 if hot else 0.0,
                "samples_slow": 64, "samples_fast": 32,
                "target_ratio": 0.99}},
                "alerting": ["ttft_p99_ms"] if hot else []}

        def healthy_report():
            return {"objectives": {"ttft_p99_ms": {
                "compliance": 1.0, "burn_fast": 0.0, "burn_slow": 0.0,
                "samples_slow": 64, "samples_fast": 32,
                "target_ratio": 0.99}}, "alerting": []}

        engines = {}
        stoppers = {}        # name -> (run_stop event, churn thread)
        stopped = []

        def spawn(name):
            eng = ServingEngine(L, params, cfg, num_slots=2,
                                max_len=16, page_size=4,
                                decode_chunk=2)
            eng.publish_frames(
                name, hb_dir, min_interval_s=0.0,
                slo_fn=burn_report if name == "replica0"
                else healthy_report)
            engines[name] = eng
            run_stop = threading.Event()

            def churn():
                # a short real burst, then idle stepping: demand
                # settles to ~0 (FLAT — the scale-out below must be
                # attributable to the injected burn, not to load),
                # while the per-step hook keeps publishing frames
                for rid in range(3):
                    try:
                        eng.submit(Request(
                            rid=rid,
                            prompt=rng.integers(
                                0, cfg.vocab_size, (3,))
                            .astype(np.int32),
                            max_new_tokens=2))
                    except Exception:
                        pass
                while not run_stop.is_set():
                    eng.step()
                    time.sleep(0.002)

            churn_th = threading.Thread(target=churn, daemon=True)
            churn_th.start()
            stoppers[name] = (run_stop, churn_th)
            return eng

        def stop(name, h):
            # a real stop: halt the replica's loop BEFORE returning,
            # so it cannot republish a frame after the controller's
            # beat-file sweep
            ev_th = stoppers.get(name)
            if ev_th is not None:
                ev_th[0].set()
                ev_th[1].join(timeout=10)
            stopped.append(name)

        view = fed.FleetSLOView(hb_dir, staleness_s=10.0)
        mgr = AdaptiveElasticManager()
        done = threading.Event()

        def run_ctl():
            mgr.run_serving(spawn, stop, min_replicas=1,
                            max_replicas=2, poll_interval=0.02,
                            heartbeat_dir=hb_dir, federation=view,
                            fleet_burn_scaling=True,
                            max_ticks=100_000, stop_event=done)

        th = threading.Thread(target=run_ctl, daemon=True)
        th.start()
        try:
            # injected fast-burn at flat demand -> scale-out to 2
            deadline = time.monotonic() + 30
            while len(engines) < 2 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert len(engines) == 2, mgr.events
            assert not stopped          # scale-in refused while hot
            srv = mon_server.get_server()
            assert srv is not None
            deadline = time.monotonic() + 30
            while True:
                p = _json.load(urllib.request.urlopen(
                    f"{srv.url}/fleet/serving", timeout=30))
                if sorted(p["frames"]) == ["replica0", "replica1"] \
                        or time.monotonic() >= deadline:
                    break
                time.sleep(0.05)
            assert p["source"] == "controller", p["source"]
            assert sorted(p["frames"]) == ["replica0", "replica1"], \
                sorted(p["frames"])
            att = p["report"]["attribution"]
            assert att[0]["replica"] == "replica0", att
            assert att[0]["alerting"] is True, att
            assert p["report"]["alerting"] == ["ttft_p99_ms"]
            reasons = [d.get("reason") for _, _s, d in mgr.events]
            assert "burn-pressure" in reasons, reasons
            # burn clears -> demand (~0) wants 1 replica -> newest
            # drained, stopped, beat file swept
            burning[0] = False
            deadline = time.monotonic() + 30
            while "replica1" not in stopped \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            assert stopped == ["replica1"], (stopped, mgr.events)
            deadline = time.monotonic() + 10
            beat = os.path.join(hb_dir, "replica1.alive")
            while os.path.exists(beat) \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            assert not os.path.exists(beat)
        finally:
            done.set()
            th.join(timeout=10)
            for ev, _th in stoppers.values():
                ev.set()
            mon_server.stop_server()
            paddle.set_flags({"FLAGS_enable_monitor": False,
                              "FLAGS_enable_monitor_server": False})
            from paddle_tpu import monitor as _mon
            _mon.reset()
            import shutil
            shutil.rmtree(hb_dir, ignore_errors=True)

    @case("trace_replay")
    def _():
        # the loadgen harness end to end on the real backend: a small
        # seeded multi-tenant trace with one scripted overload burst
        # replays open-loop through a live bounded-queue engine; the
        # scorecard must JSON-parse, every submission must sit in
        # exactly one typed terminal state, and every shed must carry
        # a retry-after hint
        import json as _json
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.loadgen import (Episode, TenantSpec,
                                        build_scorecard, generate_trace,
                                        replay_trace)
        from paddle_tpu.models import llama as L

        cfg = L.llama_tiny(num_hidden_layers=2)
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(L, params, cfg, num_slots=2, max_len=24,
                            page_size=4, decode_chunk=2,
                            priority_admission=True, max_queue=3)
        trace = generate_trace(
            99, duration_s=0.5, rate=24.0,
            tenants=[TenantSpec("interactive", priority=2),
                     TenantSpec("batch", share=2.0)],
            prompt_len=(3, 8), max_new_tokens=(2, 8))
        result = replay_trace(
            eng, trace, dt_per_step=0.02,
            episodes=[Episode("burst", at_s=0.25, n_requests=10)])
        card = build_scorecard(result)
        card = _json.loads(_json.dumps(card))    # survives the wire
        assert card["verdict"]["pass"], card["verdict"]
        # exactly one typed terminal state per submission (trace +
        # burst), no accounting hole
        assert result.offered == len(trace.requests) + 10
        assert len(result.terminal) == result.offered
        states = set(card["deterministic"]["terminal"])
        assert states <= {"completed", "shed", "expired", "rejected"}, \
            states
        assert sum(card["deterministic"]["terminal"].values()) \
            == result.offered
        # the burst overran slots+queue: typed sheds with retry hints
        sheds = [r for r in result.terminal.values()
                 if r["state"] == "shed"]
        assert sheds, "burst did not shed over the bounded queue"
        for rec in sheds:
            assert rec.get("retry_after_s") is not None, rec
        assert card["deterministic"]["shed_by_reason"], card
        assert card["deterministic"]["goodput"]["request_goodput"] < 1.0

    @case("failover_replay")
    def _():
        # exactly-once failover on the real backend: a fleet replay
        # with FLAGS_serving_failover on kills one replica mid-trace;
        # the victim's journaled in-flight work must re-dispatch onto
        # survivors and settle — zero ``lost``, lineage recorded,
        # token conservation intact
        import tempfile
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.loadgen import (Episode, TenantSpec,
                                        build_scorecard, generate_trace)
        from paddle_tpu.loadgen.replay import replay_fleet
        from paddle_tpu.models import llama as L
        from paddle_tpu.monitor import federation as fed

        cfg = L.llama_tiny(num_hidden_layers=1)
        params = L.init_params(cfg, jax.random.PRNGKey(3))
        fed.reset()
        try:
            trace = generate_trace(
                41, duration_s=1.2, rate=24.0,
                tenants=[TenantSpec("t0"), TenantSpec("t1")],
                prompt_len=(3, 8), max_new_tokens=(4, 12))
            with tempfile.TemporaryDirectory() as hb_dir:
                res = replay_fleet(
                    lambda name: ServingEngine(
                        L, params, cfg, num_slots=2, max_len=24,
                        page_size=4, decode_chunk=2, failover=True),
                    trace, replicas=2,
                    episodes=[Episode("kill", at_s=0.3,
                                      replica="replica1")],
                    dt_per_tick=0.02, steps_per_tick=1,
                    heartbeat_dir=hb_dir, heartbeat_timeout=6.0,
                    failover=True)
            counts = res.terminal_counts()
            assert counts.get("lost", 0) == 0, counts
            assert len(res.terminal) == res.offered
            assert res.failover["counters"]["stranded"] >= 1, \
                res.failover
            assert any(r.get("recovered_from")
                       for r in res.terminal.values())
            card = build_scorecard(res)
            assert card["verdict"]["pass"], card["verdict"]
        finally:
            fed.reset()

    # ---- every Pallas kernel, compiled NATIVELY by Mosaic (interpret
    # only under SMOKE_ALLOW_CPU) at the main path's shape — Llama-3-8B
    # heads 32/8 x 128, hidden 4096, 4 x 2048 tokens, bf16 — forward and
    # backward, against its reference. Off the chip the shapes shrink:
    # the interpreter at these sizes would take hours.
    KB, KS = (4, 2048) if on_tpu else (1, 128)
    # (the package rebinds the name ``flash_attention`` to the function)
    import importlib
    FA = importlib.import_module("paddle_tpu.kernels.flash_attention")

    def close(got, want, tol=3e-2):
        # bf16 tolerance relative to the reference's largest magnitude
        got = np.asarray(got).astype(np.float32)
        want = np.asarray(want).astype(np.float32)
        err = float(np.max(np.abs(got - want)))
        ref = float(np.max(np.abs(want)))
        assert np.isfinite(got).all() and err <= tol * ref + 1e-6, \
            f"max |got - want| = {err:.3e} vs max |want| = {ref:.3e}"

    def attention_case(kernel, reference):
        # the kernel runs the whole batch; the reference (which
        # materialises S x S scores) checks batch row 0 — rows are
        # independent
        q = jnp.asarray(rng.normal(size=(KB, KS, 32, 128)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(KB, KS, 8, 128)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(KB, KS, 8, 128)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

        def run(fn, n):
            def loss(q, k, v):
                return jnp.sum(fn(q, k, v, n).astype(jnp.float32) * w[:n])
            out = jax.jit(lambda q, k, v: fn(q, k, v, n))(
                q[:n], k[:n], v[:n])
            grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
                q[:n], k[:n], v[:n])
            return (out,) + grads

        for got, want in zip(run(kernel, KB), run(reference, 1)):
            close(got[:1], want)

    @case("flash_attention_kernel")
    def _():
        from paddle_tpu.kernels import autotune as at
        from paddle_tpu.nn.functional.attention import sdpa_reference
        # the blocks the dispatcher would use at this shape
        bq, bk = at.flash_blocks((KB, KS, 32, 128), (KB, KS, 8, 128),
                                 jnp.bfloat16, True)
        attention_case(
            lambda q, k, v, n: FA.flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk,
                interpret=not on_tpu),
            lambda q, k, v, n: sdpa_reference(q, k, v, causal=True))

    @case("segment_flash_kernel")
    def _():
        # packed rows: documents of mixed length, then a padding tail
        seg = np.full((KB, KS), -1, np.int32)
        pos = np.zeros((KB, KS), np.int32)
        for r in range(KB):
            o = i = 0
            while o < KS - KS // 16:
                ln = min(int(rng.integers(KS // 8, KS // 2)), KS - o)
                seg[r, o:o + ln] = i
                pos[r, o:o + ln] = np.arange(ln)
                o, i = o + ln, i + 1
        seg, pos = jnp.asarray(seg), jnp.asarray(pos)
        attention_case(
            lambda q, k, v, n: FA.flash_attention_segments(
                q, k, v, seg[:n], seg[:n], pos[:n], pos[:n], causal=True,
                interpret=not on_tpu),
            lambda q, k, v, n: FA.segment_attention_ref(
                q, k, v, seg[:n], seg[:n], pos[:n], pos[:n], causal=True))

    @case("fused_rms_norm_kernel")
    def _():
        from paddle_tpu.kernels import rms_norm as RN
        d = 4096
        x = jnp.asarray(rng.normal(size=(KB * KS, d)), jnp.bfloat16)
        w = jnp.asarray(1 + 0.1 * rng.normal(size=(d,)), jnp.bfloat16)
        g = jnp.asarray(rng.normal(size=x.shape), jnp.float32)

        def ref(x, w):
            xf = x.astype(jnp.float32)
            r = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-5)
            return (xf * r * w.astype(jnp.float32)).astype(x.dtype)

        def run(fn):
            def loss(x, w):
                return jnp.sum(fn(x, w).astype(jnp.float32) * g)
            return (jax.jit(fn)(x, w),) + jax.jit(
                jax.grad(loss, argnums=(0, 1)))(x, w)

        kern = run(lambda x, w: RN.rms_norm(
            x, w, 1e-5, RN.DEFAULT_BLOCK_ROWS, not on_tpu))
        for got, want in zip(kern, run(ref)):
            close(got, want)

    def paged_case(quant):
        # decode shape: 8 slots x up to 1024 cached tokens, ragged
        from paddle_tpu.kernels import paged_attention as PA
        B, nh, kvh, hd = 8, 32, 8, 128
        ps = 32 if quant else 16            # int8 sublane tile is 32
        maxp = (1024 if on_tpu else 128) // ps
        P = B * maxp
        q = jnp.asarray(rng.normal(size=(B, nh, hd)), jnp.bfloat16)
        kp = rng.normal(size=(P, kvh, ps, hd)).astype(np.float32)
        vp = rng.normal(size=(P, kvh, ps, hd)).astype(np.float32)
        bt = jnp.asarray(rng.permutation(P).reshape(B, maxp), jnp.int32)
        ln = jnp.asarray(rng.integers(1, maxp * ps + 1, (B,)), jnp.int32)
        ln = ln.at[0].set(maxp * ps).at[1].set(0)     # full and empty
        kw = {}
        if quant:
            def codes(a):
                s = np.abs(a).max(axis=(2, 3)) / 127.0
                return (jnp.asarray(np.round(a / s[:, :, None, None]),
                                    jnp.int8), jnp.asarray(s))
            (kp, ks), (vp, vs) = codes(kp), codes(vp)
            kw = dict(k_scales=ks, v_scales=vs)
        else:
            kp, vp = (jnp.asarray(a, jnp.bfloat16) for a in (kp, vp))
        got = jax.jit(lambda q, kp, vp: PA.ragged_paged_attention(
            q, kp, vp, bt, ln, interpret=not on_tpu, **kw))(q, kp, vp)
        want = PA.paged_attention_ref(q, kp, vp, bt, ln, **kw)
        assert PA.supported(q, kp, bt, quant=quant)
        np.testing.assert_allclose(
            np.asarray(got).astype(np.float32),
            np.asarray(want).astype(np.float32), rtol=3e-2, atol=3e-2)

    @case("ragged_paged_attention_kernel")
    def _():
        paged_case(quant=False)

    @case("ragged_paged_attention_kernel_int8")
    def _():
        paged_case(quant=True)

    @case("packed_train_step")
    def _():
        # sequence-packed training on the real chip: the NATIVE segment
        # flash kernel must engage (dispatch counter, not a silent
        # fallback), the loss must be finite, and an aligned trace
        # (documents exactly one row long) must match the equivalent
        # unpacked batch
        from paddle_tpu import kernels
        from paddle_tpu.io.packing import pack_documents, packed_train_batch
        from paddle_tpu.models import llama as L
        cfg = L.llama_tiny(num_hidden_layers=2, dtype=jnp.bfloat16)
        S = 128
        docs = [rng.integers(0, cfg.vocab_size, (ln,)).astype(np.int32)
                for ln in (96, 32, 64, 48, 128, 16)]
        batch = packed_train_batch(pack_documents(docs, S))
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        opt = L.adamw_init(params)
        step = L.make_train_step(cfg, lr=1e-3, donate=False,
                                 guard=False)
        kernels.reset_dispatch_stats()
        _, _, loss = step(params, opt, batch)
        assert np.isfinite(float(loss)), f"packed loss {float(loss)}"
        st = kernels.dispatch_stats()
        if on_tpu:
            assert st["varlen"] > 0, \
                f"segment kernel did not engage: {st}"
        # parity on an aligned trace: one doc per row -> packing is the
        # identity layout, so packed loss == unpacked loss
        docs2 = [rng.integers(0, cfg.vocab_size, (S,)).astype(np.int32)
                 for _ in range(2)]
        b2 = packed_train_batch(pack_documents(docs2, S))
        _, _, lp = step(params, opt, b2)
        ids = np.stack(docs2)
        labels = np.full((2, S), -100, np.int32)
        labels[:, :-1] = ids[:, 1:]
        _, _, lu = step(params, opt, (jnp.asarray(ids),
                                      jnp.asarray(labels)))
        np.testing.assert_allclose(float(lp), float(lu),
                                   rtol=2e-2, atol=2e-2)

    @case("checkpoint_save_kill_resume")
    def _():
        # crash-consistency on the real machine: a child process commits
        # step 1, is kill -9'd (via the fault harness) mid-step-2 save,
        # and THIS process must restore step 1 bit-for-bit
        import subprocess
        import tempfile

        from paddle_tpu.distributed.checkpoint import CheckpointManager
        from paddle_tpu.testing import faults as _faults

        root = os.path.join(tempfile.mkdtemp(prefix="smoke_ckpt_"), "root")
        child = (
            "import os, sys\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "import numpy as _np\n"
            "import paddle_tpu as _pt\n"
            "from paddle_tpu.distributed.checkpoint import "
            "CheckpointManager\n"
            "m = CheckpointManager(sys.argv[1], keep_last_n=3)\n"
            "w = _np.arange(12, dtype='float32').reshape(3, 4)\n"
            "m.save(1, {'w': _pt.to_tensor(w + 1), 'step': 1})\n"
            "m.save(2, {'w': _pt.to_tensor(w + 2), 'step': 2})\n"
            "print('SAVED2')\n")
        r = subprocess.run(
            [sys.executable, "-c", child, root],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
                     FLAGS_fault_injection="checkpoint.rename:kill:2"))
        if r.returncode != _faults.KILL_EXIT_CODE or "SAVED2" in r.stdout:
            raise RuntimeError(
                f"child survived the injected kill: rc={r.returncode} "
                f"{r.stderr[-500:]}")
        mgr = CheckpointManager(root)
        target = {"w": paddle.to_tensor(np.zeros((3, 4), "float32")),
                  "step": 0}
        step = mgr.restore_latest(target)
        got = np.asarray(target["w"].numpy())
        want = np.arange(12, dtype="float32").reshape(3, 4) + 1
        if step != 1 or not np.array_equal(got, want):
            raise RuntimeError(
                f"resume after kill wrong: step={step} w={got.tolist()}")

    @case("nan_skip_resume")
    def _():
        # the anomaly sentinel end to end on the real chip: a corrupt
        # batch (fault-injected NaN) must leave the guarded step's
        # params byte-identical, the loop must SKIP it and keep
        # training, and the loss must still converge-ish afterwards
        from paddle_tpu.models import llama as L
        from paddle_tpu.testing import faults as _faults
        from paddle_tpu.training.sentinel import AnomalySentinel, \
            SentinelLoop

        cfg = L.llama_tiny(num_hidden_layers=2, vocab_size=64)
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        opt = L.adamw_init(params)
        step = L.make_train_step(cfg, lr=1e-3, guard=True, donate=False)

        def batch(i):
            # DISTINCT deterministic batches (identical batches would
            # alias in the quarantine, which is hash-keyed) with a
            # LEARNABLE pattern (consecutive ids mod vocab), so the
            # post-skip loss provably drops
            r = np.random.default_rng(1000 + i)
            start = r.integers(0, cfg.vocab_size, (2, 1))
            ids = ((start + np.arange(33)) % cfg.vocab_size).astype(
                np.int32)
            return ids[:, :-1], ids[:, 1:]

        # 1) a NaN-corrupted batch leaves params byte-identical
        inf_cap = jnp.asarray(np.inf, jnp.float32)
        try:
            _faults.inject("smoke.batch", action="corrupt")
            bad = _faults.corrupt("smoke.batch", (
                jnp.asarray(batch(0)[0], jnp.float32),))  # float leaf
        finally:
            _faults.clear()
        assert not np.isfinite(np.asarray(bad[0])).all(), \
            "corrupt action did not plant a non-finite value"
        bad_ids = np.array(batch(0)[0])
        bad_ids[0, 0] = np.iinfo(np.int32).min      # int-pipeline rot
        p2, o2, _, h = step(params, opt,
                            (bad_ids, batch(0)[1]), inf_cap)
        assert not bool(h["finite"]), "guard missed the corrupt batch"
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                raise RuntimeError("anomalous step mutated params")

        # 2) loop: corrupt the 3rd batch mid-run -> exactly one skip,
        # training continues, loss drops vs the start
        def make_stream():
            return (batch(i) for i in range(40))

        loop = SentinelLoop(step, params, opt, make_stream,
                            sentinel=AnomalySentinel())
        _, _, first_loss, _ = step(params, opt, batch(0), inf_cap)
        try:
            _faults.inject("train.batch", action="corrupt", nth=3)
            out = loop.run(40)
        finally:
            _faults.clear()
        if out["skipped"] != 1 or out["applied"] != 39:
            raise RuntimeError(f"skip accounting wrong: {out}")
        if not (out["last_loss"] < float(first_loss)):
            raise RuntimeError(
                f"no convergence after skip: first {float(first_loss)} "
                f"last {out['last_loss']}")

    @case("rank_kill_resume")
    def _():
        # survivable multi-host training end to end (ISSUE 14): a
        # 2-process world is launched through the elastic manager; on
        # run 0 rank 1 kill -9s itself mid-gather; rank 0 must log a
        # typed PeerLostError NAMING rank 1 (tombstone fast path) and
        # exit through coordinated_abort; the elastic restart resumes
        # the per-rank DataLoader from committed state and the stitched
        # sample log shows every index consumed exactly once
        import re
        import tempfile

        from paddle_tpu.distributed.fleet.elastic import \
            AdaptiveElasticManager

        work = tempfile.mkdtemp(prefix="smoke_rank_kill_")
        worker = os.path.join(work, "worker.py")
        with open(worker, "w") as f:
            f.write(
                "import os, sys, time\n"
                "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
                "import jax; jax.config.update('jax_platforms', 'cpu')\n"
                "import numpy as np\n"
                "import paddle_tpu.distributed as dist\n"
                "from paddle_tpu.distributed import collective as coll\n"
                "from paddle_tpu.distributed.fleet import elastic\n"
                "from paddle_tpu.io import DataLoader\n"
                "from paddle_tpu.io.dataset import Dataset\n"
                "N, BS, TOTAL = 16, 2, 8\n"
                "class DS(Dataset):\n"
                "    def __len__(self): return N\n"
                "    def __getitem__(self, i):\n"
                "        return np.asarray([i], np.int64)\n"
                "log_path = sys.argv[1]\n"
                "dist.init_parallel_env()\n"
                "rank, run = dist.get_rank(), elastic.elastic_run_index()\n"
                "loader = DataLoader(DS(), batch_size=BS, shuffle=True,\n"
                "                    seed=5)\n"
                "start, state = elastic.load_state(\n"
                "    {'data': loader.state_dict(), 'step': 0})\n"
                "if start: loader.set_state_dict(state['data'])\n"
                "step = int(start)\n"
                "with coll.abort_on_collective_fault():\n"
                "    log = open(f'{log_path}.rank{rank}', 'a')\n"
                "    for batch in loader:\n"
                "        if step >= TOTAL: break\n"
                "        ids = ' '.join(str(int(x)) for x in\n"
                "                       np.asarray(batch.numpy()).ravel())\n"
                "        log.write(f'run={run} step={step} ids={ids}\\n')\n"
                "        log.flush()\n"
                "        step += 1\n"
                "        # collective save: EVERY rank participates in\n"
                "        # the commit-status gathers\n"
                "        elastic.save_state(step,\n"
                "            {'data': dict(loader.state_dict()),\n"
                "             'step': step}, blocking=True)\n"
                "        if run == 0 and rank == 1 and step == 3:\n"
                "            os.kill(os.getpid(), 9)  # mid-gather kill\n"
                "        dist.all_gather_object([], step,\n"
                "                               tag=f'r{run}s{step}',\n"
                "                               timeout_s=45)\n"
                "print(f'SMOKE_DONE rank={rank} run={run}', flush=True)\n")
        log = os.path.join(work, "samples")
        # readmit_after=0: the killed slot re-admits immediately — the
        # restart keeps the full world size
        mgr = AdaptiveElasticManager(max_restarts=2, restart_delay=0.2,
                                     readmit_after=0.0)
        rc = mgr.run_adaptive(
            worker, (log,), nproc_per_node=2,
            ckpt_dir=os.path.join(work, "ckpt"),
            log_dir=os.path.join(work, "logs"),
            extra_env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
        if rc != 0:
            raise RuntimeError(f"elastic world never completed: rc={rc}")
        wl = ""
        for run_dir in sorted(os.listdir(os.path.join(work, "logs"))):
            for fn in sorted(os.listdir(
                    os.path.join(work, "logs", run_dir))):
                if fn.startswith("workerlog"):
                    wl += open(os.path.join(work, "logs", run_dir,
                                            fn)).read()
        if "PeerLostError" not in wl or "[1]" not in wl:
            raise RuntimeError(
                f"survivor did not raise a typed error naming rank 1:\n"
                f"{wl[-2000:]}")
        restarts = [d for _, s, d in mgr.events if s == "restart"]
        if not restarts:
            raise RuntimeError("elastic manager recorded no restart")
        # rank 0's stitched sample log: every step exactly once
        lines = [ln for ln in open(f"{log}.rank0").read().splitlines()
                 if ln]
        steps = [int(re.search(r"step=(\d+)", ln).group(1))
                 for ln in lines]
        if steps != list(range(8)):
            raise RuntimeError(f"sample accounting broken: {steps}")
        ids = [int(x) for ln in lines
               for x in re.search(r"ids=(.*)$", ln).group(1).split()]
        if sorted(ids) != list(range(16)):
            raise RuntimeError(f"samples not exactly-once: {sorted(ids)}")

    @case("flash_block_autotune_bench_shape")
    def _():
        # pre-tune the bench and chip_smoke.py shapes; winners land in
        # the tracked autotune_cache.json (the autotuner's default path)
        # that both read without ever measuring
        os.environ["PADDLE_TPU_AUTOTUNE"] = "1"
        from paddle_tpu.kernels import autotune as at
        # the dense train shape (bench rung 1 and chip_smoke.py's
        # trainer) + the MoE rung's shape (DeepSeekMoE-16B slice at
        # b8/s1024: 16 heads, d128)
        for b, h, kvh, s, d in ((4, 32, 8, 2048, 128),
                                (8, 16, 16, 1024, 128)):
            blocks = at.flash_blocks((b, s, h, d), (b, s, kvh, d),
                                     jnp.bfloat16, True)
            print(f"tuned blocks for s={s}: {blocks}", file=sys.stderr)
            # a silent all-candidates-failed sweep falls back to the
            # defaults — that is a smoke FAILURE, not a timing tie. The
            # dispatch decision record carries the exact key + source.
            (key, used), = [(k, u) for k, u in at.used_blocks().items()
                            if f"q{s}k{s}" in k]
            if on_tpu and used["source"] not in ("measured", "cache"):
                raise RuntimeError(
                    f"autotune sweep did not measure: {used} "
                    f"(cache entry: {at._CACHE.get(key)})")
        # varlen (segment-kernel) blocks at the packed-training rung's
        # shape: the rung's packed row count is a deterministic function
        # of the shared heavy-tailed trace (io.packing), so the sweep
        # here lands on exactly the key a packed training run looks up
        from paddle_tpu.io import packing as pk
        lens = pk.heavy_tailed_lengths(2048, 24, seed=7)
        pb = pk.pack_documents(
            [np.zeros(ln, np.int32) for ln in lens], 2048)["ids"].shape[0]
        vblocks = at.varlen_blocks((pb, 2048, 32, 128),
                                   (pb, 2048, 8, 128), jnp.bfloat16, True)
        print(f"tuned varlen blocks for b={pb}: {vblocks}",
              file=sys.stderr)
        (key, used), = [(k, u) for k, u in at.used_blocks().items()
                        if k.startswith("varlen:") and "q2048" in k]
        if on_tpu and used["source"] not in ("measured", "cache"):
            raise RuntimeError(
                f"varlen autotune sweep did not measure: {used} "
                f"(cache entry: {at._CACHE.get(key)})")
        # fused-CE vocab-chunk sweeps at the loss shapes: bench dense
        # rung (b4*s2048 tokens, 32k vocab, d4096), chip_smoke.py's
        # trainer (the same tokens at the published 128,256 vocab) and
        # the MoE rung (b2*s1024, 102k vocab, d2048)
        for n, d, v in ((8192, 4096, 32000), (8192, 4096, 128256),
                        (2048, 2048, 102400)):
            chunk = at.ce_chunk(n, d, v, jnp.bfloat16)
            print(f"tuned ce chunk for n={n} v={v}: {chunk}",
                  file=sys.stderr)
            (key, used), = [(k, u) for k, u in at.used_blocks().items()
                            if f"n{n}v{v}" in k]
            if on_tpu and used["source"] not in ("measured", "cache"):
                raise RuntimeError(
                    f"ce autotune sweep did not measure: {used} "
                    f"(cache entry: {at._CACHE.get(key)})")

    fails = [k for k, v in results.items() if v != "ok"]
    _emit({"results": results,
           "platform": devs[0].platform,
           "device_kind": devs[0].device_kind,
           "n_devices": len(devs), "failed": fails})
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
