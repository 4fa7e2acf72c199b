"""Measured performance plane (monitor/exectime.py, profile_capture.py,
timeseries.py, roofline calibration, /profile + /timeseries routes).

The load-bearing contracts:

- **Sampling math**: 1-in-N on cache-HIT dispatches only; rate 0 or
  monitor-off adds ZERO ``block_until_ready`` calls and zero
  registrations (pinned by monkeypatching the sync indirection).
- **Calibration honesty**: ``model_error_ratio`` is measured/modeled
  when both legs exist and None otherwise — never fabricated; the
  worst ratio exports as ``roofline.model.max_error_ratio``.
- **Capture exclusivity**: one ``/profile`` window at a time (409 on
  the second), capture directory bounded (oldest evicted).
- **Drift detection**: recent-median vs trailing-baseline ratio trips
  the gauge + the warn-level /healthz provider (which never fails
  liveness), and the sentinel sees it observe-only.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.monitor import exectime
from paddle_tpu.monitor import profile_capture as pcap
from paddle_tpu.monitor import programs
from paddle_tpu.monitor import roofline
from paddle_tpu.monitor import server
from paddle_tpu.monitor import timeseries
from paddle_tpu.monitor import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def mon():
    """Monitor on, clean state; everything torn down after."""
    monitor.reset()
    server.stop_server()
    pt.set_flags({"FLAGS_enable_monitor": True})
    yield monitor
    server.stop_server()
    server.unregister_health_provider("steptime_drift")
    timeseries._PROVIDER_REGISTERED[0] = False
    exectime.set_sample_rate(None)
    timeseries.set_capacity(None)
    pt.set_flags({"FLAGS_enable_monitor": False,
                  "FLAGS_enable_monitor_server": False})
    monitor.reset()


@pytest.fixture
def count_blocks(monkeypatch):
    """Count the sampler's added device synchronizations."""
    calls = []
    real = exectime._block_until_ready

    def counting(outputs):
        calls.append(1)
        real(outputs)

    monkeypatch.setattr(exectime, "_block_until_ready", counting)
    return calls


def _static_fn():
    import paddle_tpu.jit as jit

    @jit.to_static
    def f(x):
        return x * 2.0 + 1.0
    return f


def _get(url, timeout=30):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

class TestExecSampling:
    def test_rate_resolution(self, mon, monkeypatch):
        exectime.set_sample_rate(None)
        monkeypatch.delenv("PADDLE_TPU_EXEC_SAMPLE", raising=False)
        assert exectime.sample_rate() == 16           # default
        exectime.set_sample_rate(None)
        monkeypatch.setenv("PADDLE_TPU_EXEC_SAMPLE", "4")
        assert exectime.sample_rate() == 4
        exectime.set_sample_rate(None)
        monkeypatch.setenv("PADDLE_TPU_EXEC_SAMPLE", "garbage")
        assert exectime.sample_rate() == 16           # invalid -> default
        exectime.set_sample_rate(0)
        assert exectime.sample_rate() == 0

    def test_hit_calls_sampled_into_histogram_and_record(self, mon):
        exectime.set_sample_rate(1)
        f = _static_fn()
        x = pt.to_tensor(np.ones((2, 4), "float32"))
        for _ in range(3):
            f(x)                       # 1 miss + 2 hits
        snap = monitor.snapshot()
        h = snap["histograms"]["jit.program.exec_ms"]
        assert h["count"] == 2         # misses are never exec-sampled
        assert snap["counters"]["jit.program.exec.samples"] == 2
        (rec,) = programs.programs_snapshot()
        assert rec["exec_samples"] == 2
        assert rec["exec_mean_ms"] > 0
        assert rec["exec_max_ms"] >= rec["exec_mean_ms"]

    def test_one_in_n(self, mon):
        exectime.set_sample_rate(4)
        f = _static_fn()
        x = pt.to_tensor(np.ones((2, 4), "float32"))
        f(x)                           # miss
        for _ in range(8):             # 8 hits at 1-in-4 -> 2 samples
            f(x)
        assert monitor.snapshot()["counters"][
            "jit.program.exec.samples"] == 2

    def test_rate_zero_adds_zero_syncs(self, mon, count_blocks):
        exectime.set_sample_rate(0)
        f = _static_fn()
        x = pt.to_tensor(np.ones((2, 4), "float32"))
        for _ in range(4):
            f(x)
        assert count_blocks == []
        snap = monitor.snapshot()
        assert "jit.program.exec_ms" not in snap.get("histograms", {})
        assert "jit.program.exec.samples" not in snap.get("counters", {})

    def test_monitor_off_zero_syncs_and_registrations(self, count_blocks):
        monitor.reset()
        pt.set_flags({"FLAGS_enable_monitor": False})
        exectime.set_sample_rate(1)
        try:
            f = _static_fn()
            x = pt.to_tensor(np.ones((2, 4), "float32"))
            for _ in range(4):
                f(x)
            assert count_blocks == []
            assert monitor.snapshot() == {}
            assert programs.programs_snapshot() == []
            assert exectime.maybe_sample(("k",)) is None
        finally:
            exectime.set_sample_rate(None)
            monitor.reset()

    def test_grad_path_hits_sampled(self, mon):
        exectime.set_sample_rate(1)
        import paddle_tpu.jit as jit

        @jit.to_static
        def f(x):
            return (x * x).sum()

        x = pt.to_tensor(np.ones((2, 3), "float32"),
                         stop_gradient=False)
        f(x)                                    # miss
        out = f(x)                              # hit on the grad path
        out.backward()
        assert monitor.snapshot()["counters"][
            "jit.program.exec.samples"] >= 1

    def test_time_call_and_last_sample_feed(self, mon):
        out, ms = exectime.time_call(
            ("t", "k"), lambda a, b: a + b, 1, 2)
        assert out == 3 and ms >= 0
        assert exectime.take_last_sample_ms() == ms
        assert exectime.take_last_sample_ms() is None   # consumed

    def test_reset_clears_sampler_state(self, mon):
        exectime.set_sample_rate(2)
        assert exectime.maybe_sample("k") is None       # count 1 of 2
        monitor.reset()
        # counts cleared: the next call is count 1 again, not a sample
        assert exectime.maybe_sample("k") is None
        assert exectime.maybe_sample("k") is not None


# ---------------------------------------------------------------------------
# program-record staleness (note_hit satellite)
# ---------------------------------------------------------------------------

class TestStaleness:
    def test_last_hit_age(self, mon):
        programs.record_program("k1", "p1", source="test")
        (rec,) = programs.programs_snapshot()
        assert rec["last_hit_age_s"] is None            # never hit
        programs.note_hit("k1")
        (rec,) = programs.programs_snapshot()
        assert rec["last_hit_age_s"] is not None
        assert 0 <= rec["last_hit_age_s"] < 5.0

    def test_note_exec_unknown_key_noop(self, mon):
        programs.note_exec(("nope",), 1.0)              # must not raise


# ---------------------------------------------------------------------------
# roofline calibration
# ---------------------------------------------------------------------------

class TestCalibration:
    def _peaks_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e9")
        monkeypatch.setenv("PADDLE_TPU_PEAK_HBM_GBS", "1")
        monkeypatch.setenv("PADDLE_TPU_PEAK_ICI_GBS", "1")

    def test_model_error_ratio_measured_vs_modeled(self, mon,
                                                   monkeypatch):
        self._peaks_env(monkeypatch)
        programs.record_program("m1", "measured", source="test",
                                flops=1e6, bytes_accessed=1e6)
        programs.note_exec("m1", 5.0)
        programs.note_exec("m1", 7.0)
        programs.record_program("m2", "unsampled", source="test",
                                flops=1e6, bytes_accessed=1e6)
        rs = roofline.roofline_snapshot(analyze=False)
        by = {p["name"]: p for p in rs["programs"]}
        m = by["measured"]
        # modeled: max(1e6/1e9, 1e6/1e9) = 1 ms; measured mean 6 ms
        assert m["model_error_ratio"] == pytest.approx(6.0, rel=1e-3)
        assert by["unsampled"]["model_error_ratio"] is None
        assert rs["calibration"]["measured_programs"] == 1
        assert rs["calibration"]["max_error_ratio"] == pytest.approx(
            6.0, rel=1e-3)
        g = monitor.snapshot()["gauges"]["roofline.model.max_error_ratio"]
        assert g == pytest.approx(6.0, rel=1e-3)

    def test_unclassified_program_never_gets_ratio(self, mon,
                                                   monkeypatch):
        self._peaks_env(monkeypatch)
        # sampled but cost-analysis unavailable: no modeled time
        programs.record_program("m3", "nocost", source="test",
                                flops=None, bytes_accessed=None)
        programs.note_exec("m3", 5.0)
        rs = roofline.roofline_snapshot(analyze=False)
        (p,) = [q for q in rs["programs"] if q["name"] == "nocost"]
        assert p["verdict"] is None
        assert p["model_error_ratio"] is None
        assert rs["calibration"]["measured_programs"] == 0
        assert rs["calibration"]["max_error_ratio"] is None

    def test_divergence_flag_both_directions(self, mon, monkeypatch):
        self._peaks_env(monkeypatch)
        monkeypatch.setenv("PADDLE_TPU_ROOFLINE_ERROR_MAX", "2")
        for key, name, ms in (("d1", "way_over", 10.0),
                              ("d2", "way_under", 0.1),
                              ("d3", "близко", 1.2)):
            programs.record_program(key, name, source="test",
                                    flops=1e6, bytes_accessed=1e6)
            programs.note_exec(key, ms)
        rs = roofline.roofline_snapshot(analyze=False)
        by = {p["name"]: p for p in rs["programs"]}
        assert by["way_over"]["model_divergent"] is True     # 10x
        assert by["way_under"]["model_divergent"] is True    # 0.1x
        assert by["близко"]["model_divergent"] is False      # 1.2x
        names = {d["name"] for d in rs["calibration"]["divergent"]}
        assert names == {"way_over", "way_under"}

    def test_max_error_ratio_worst_in_either_direction(self, mon,
                                                       monkeypatch):
        # a 0.05x ratio (model 20x overestimates) must outrank a 1.1x
        # in the gauge — raw max() would mask it behind the ratio
        # nearer 1
        self._peaks_env(monkeypatch)
        for key, name, ms in (("w1", "slightly_over", 1.1),
                              ("w2", "far_under", 0.05)):
            programs.record_program(key, name, source="test",
                                    flops=1e6, bytes_accessed=1e6)
            programs.note_exec(key, ms)
        rs = roofline.roofline_snapshot(analyze=False)
        assert rs["calibration"]["max_error_ratio"] == pytest.approx(
            0.05, rel=1e-3)
        g = monitor.snapshot()["gauges"][
            "roofline.model.max_error_ratio"]
        assert g == pytest.approx(0.05, rel=1e-3)

    def test_threshold_env_parsing(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_ROOFLINE_ERROR_MAX",
                           raising=False)
        assert roofline.model_error_threshold() == 4.0
        monkeypatch.setenv("PADDLE_TPU_ROOFLINE_ERROR_MAX", "0.5")
        assert roofline.model_error_threshold() == 4.0   # must be > 1
        monkeypatch.setenv("PADDLE_TPU_ROOFLINE_ERROR_MAX", "junk")
        assert roofline.model_error_threshold() == 4.0


# ---------------------------------------------------------------------------
# timeseries + drift
# ---------------------------------------------------------------------------

class TestTimeseries:
    def test_off_path_records_nothing(self):
        monitor.reset()
        pt.set_flags({"FLAGS_enable_monitor": False})
        timeseries.record_step(total_ms=1.0)
        assert timeseries.rows() == []
        assert monitor.snapshot() == {}

    def test_ring_bounded(self, mon):
        timeseries.set_capacity(16)
        for i in range(40):
            timeseries.record_step(step=i, total_ms=1.0)
        assert len(timeseries.rows()) == 16
        assert timeseries.total_rows() == 40
        assert timeseries.rows()[-1]["step"] == 39

    def test_auto_step_index(self, mon):
        timeseries.record_step(total_ms=1.0)
        timeseries.record_step(total_ms=1.0)
        assert [r["step"] for r in timeseries.rows()] == [1, 2]

    def test_drift_none_until_windows_fill(self, mon):
        for i in range(10):
            timeseries.record_step(total_ms=10.0)
        st = timeseries.drift_status()     # < 2*recent(8) rows
        assert st["ratio"] is None and st["drifting"] is False
        assert "train.step.drift_ratio" not in \
            monitor.snapshot().get("gauges", {})

    def test_drift_trips_on_slowdown(self, mon):
        for i in range(32):
            timeseries.record_step(total_ms=10.0)
        for i in range(8):
            timeseries.record_step(total_ms=30.0)
        st = timeseries.drift_status()
        assert st["ratio"] == pytest.approx(3.0)
        assert st["drifting"] is True
        assert monitor.snapshot()["gauges"][
            "train.step.drift_ratio"] == pytest.approx(3.0)

    def test_steady_run_does_not_drift(self, mon):
        for i in range(48):
            timeseries.record_step(total_ms=10.0 + (i % 3) * 0.1)
        st = timeseries.drift_status()
        assert st["ratio"] == pytest.approx(1.0, abs=0.05)
        assert st["drifting"] is False

    def test_warn_level_healthz_provider_never_fails_liveness(self,
                                                              mon):
        for i in range(32):
            timeseries.record_step(total_ms=10.0)
        for i in range(8):
            timeseries.record_step(total_ms=100.0)    # 10x drift
        ok, payload = server.health()
        assert ok                                     # warn-level
        rep = payload["providers"]["steptime_drift"]
        assert rep["level"] == "warn"
        assert rep["drifting"] is True and rep["ratio"] > 5

    def test_grad_norm_ema_filled_from_gauge(self, mon):
        monitor.set_gauge("train.anomaly.grad_norm_ema", 1.25)
        timeseries.record_step(total_ms=5.0)
        assert timeseries.rows()[-1]["grad_norm_ema"] == 1.25

    def test_flight_record_carries_timeseries(self, mon):
        timeseries.record_step(total_ms=5.0, loss=2.5)
        payload = trace.flight_payload()
        assert payload["timeseries"]["rows"][-1]["loss"] == 2.5
        assert "drift" in payload["timeseries"]

    def test_steptimer_feeds_rows(self, mon):
        st = monitor.StepTimer("t")
        with st.compute():
            time.sleep(0.002)
        st.end_step(useful_tokens=100, loss=3.5)
        (row,) = timeseries.rows()
        assert row["step"] == 1
        assert row["compute_ms"] >= 1.0
        assert row["total_ms"] >= row["compute_ms"]
        assert row["loss"] == 3.5
        assert row["goodput_tokens_per_sec"] > 0

    def test_timeseries_route(self, mon):
        srv = server.start_server(port=0)
        timeseries.record_step(total_ms=7.0)
        status, body = _get(f"{srv.url}/timeseries")
        assert status == 200
        payload = json.loads(body)
        assert payload["rows"][-1]["total_ms"] == 7.0
        assert "drift" in payload and "capacity" in payload


# ---------------------------------------------------------------------------
# sentinel drift visibility (observe-only)
# ---------------------------------------------------------------------------

class TestSentinelDrift:
    def test_loop_feeds_timeseries_and_surfaces_drift(self, mon):
        from paddle_tpu.training.sentinel import (AnomalySentinel,
                                                  SentinelLoop)

        def fake_step(params, opt, batch, cap):
            return params, opt, 0.5, {"finite": True, "grad_norm": 1.0}

        def make_stream():
            return iter([(i,) for i in range(24)])

        loop = SentinelLoop(fake_step, {"w": 0}, {"m": 0}, make_stream,
                            sentinel=AnomalySentinel())
        out = loop.run(24)
        assert out["applied"] == 24
        rows = timeseries.rows()
        assert len(rows) == 24
        assert rows[-1]["total_ms"] is not None
        assert rows[-1]["loss"] == 0.5
        assert rows[-1]["grad_norm_ema"] is not None
        # drift visible on the sentinel (observe-only: all applied)
        assert loop.sentinel.step_time_drift == \
            timeseries.drift_status()["ratio"]
        # and in the health provider payload
        from paddle_tpu.training.sentinel import \
            _sentinel_health_provider
        import weakref
        rep = _sentinel_health_provider(weakref.ref(loop))()
        assert "step_time_drift" in rep


# ---------------------------------------------------------------------------
# profile capture
# ---------------------------------------------------------------------------

@pytest.fixture
def fake_profiler(monkeypatch):
    """Stub jax.profiler start/stop for the capture LOGIC tests.

    The real profiler cannot run in the shared tier-1 process: once
    test_device_plugin registers its fake PJRT plugin (a permanent
    in-process registration), this jaxlib's ``start_trace`` segfaults
    collecting from a plugin with no profiler extension. The stub
    keeps the exclusivity/eviction/route logic honest (it writes a
    marker trace file per capture); the REAL profiler integration is
    pinned by ``test_real_capture_in_subprocess`` (fresh process, no
    plugin) and the ``profile_capture`` tpu_smoke stage."""
    import jax
    state = {"dir": None}

    def start(d, *a, **kw):
        state["dir"] = d

    def stop():
        d = state.pop("dir", None)
        if d:
            sub = os.path.join(d, "plugins", "profile", "stub")
            os.makedirs(sub, exist_ok=True)
            with open(os.path.join(sub, "stub.xplane.pb"), "wb") as f:
                f.write(b"stub-trace")

    monkeypatch.setattr(jax.profiler, "start_trace", start)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop)
    return state


class TestProfileCapture:
    def test_capture_writes_trace_and_evicts(self, mon, tmp_path,
                                             monkeypatch,
                                             fake_profiler):
        base = str(tmp_path / "caps")
        monkeypatch.setenv("PADDLE_TPU_PROFILE_KEEP", "2")
        infos = []
        for _ in range(3):
            infos.append(pcap.capture_sync(0.05, base_dir=base))
            time.sleep(0.01)       # distinct capture-dir microseconds
        assert infos[-1]["files"], infos[-1]
        # bounded: only the newest 2 remain, oldest evicted
        kept = pcap.list_captures(base)
        assert len(kept) == 2
        assert os.path.basename(infos[0]["dir"]) not in kept
        assert os.path.basename(infos[-1]["dir"]) in kept
        assert infos[-1]["evicted"] >= 1
        assert monitor.snapshot()["counters"][
            "monitor.profile.captures"] == 3

    def test_concurrent_capture_raises_busy(self, mon, tmp_path,
                                            fake_profiler):
        base = str(tmp_path / "caps")
        started = threading.Event()
        results = {}

        def long_capture():
            started.set()
            results["first"] = pcap.capture_sync(0.6, base_dir=base)

        t = threading.Thread(target=long_capture)
        t.start()
        started.wait()
        deadline = time.time() + 2
        while not pcap.capturing() and time.time() < deadline:
            time.sleep(0.01)
        with pytest.raises(pcap.CaptureBusy):
            pcap.capture_sync(0.05, base_dir=base)
        t.join()
        assert results["first"]["files"]
        assert not pcap.capturing()

    def test_profile_route_409_and_400(self, mon, tmp_path,
                                       monkeypatch, fake_profiler):
        monkeypatch.setenv("PADDLE_TPU_PROFILE_DIR",
                           str(tmp_path / "caps"))
        srv = server.start_server(port=0)
        results = []

        def hit():
            results.append(_get(f"{srv.url}/profile?seconds=0.5"))

        ts = [threading.Thread(target=hit) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        codes = sorted(r[0] for r in results)
        assert codes == [200, 409], codes
        ok_body = json.loads([r[1] for r in results
                              if r[0] == 200][0])
        assert ok_body["files"]
        assert monitor.snapshot()["counters"][
            "monitor.profile.busy_rejected"] == 1
        assert _get(f"{srv.url}/profile?seconds=abc")[0] == 400
        assert _get(f"{srv.url}/profile?seconds=0")[0] == 400
        assert _get(f"{srv.url}/profile?seconds=999")[0] == 400

    def test_spans_annotate_inside_and_outside_capture(
            self, mon, tmp_path, monkeypatch, fake_profiler):
        """The merged primitive: ``trace.span`` / ``step_span`` enter
        their profiler annotation whether or not a ``/profile`` capture
        is live (the capture module has no annotation API of its own
        and spans never ask it), and record in the ring beside it."""
        assert not hasattr(pcap, "annotate")
        assert not hasattr(pcap, "annotate_step")
        seen = []

        class Ann:
            def __init__(self, name, **kw):
                self.name, self.kw = name, kw

            def __enter__(self):
                seen.append((self.name, self.kw, pcap.capturing()))

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(trace, "_TraceAnnotation", Ann)
        monkeypatch.setattr(trace, "_StepTraceAnnotation", Ann)

        def spans():
            with trace.span("x"), trace.step_span("y", 3):
                pass

        spans()                                   # no capture live
        t = threading.Thread(target=pcap.capture_sync, args=(0.4,),
                             kwargs={"base_dir": str(tmp_path)})
        t.start()
        deadline = time.time() + 2
        while not pcap.capturing() and time.time() < deadline:
            time.sleep(0.01)
        spans()                                   # inside the window
        t.join(timeout=10)
        assert not t.is_alive() and not pcap.capturing()
        assert seen == [("x", {}, False), ("y", {"step_num": 3}, False),
                        ("x", {}, True), ("y", {"step_num": 3}, True)]
        names = [e["name"] for e in trace.events()
                 if e["name"] in ("x", "y")]
        assert names == ["y", "x", "y", "x"]

    def test_bad_seconds_rejected(self):
        with pytest.raises(ValueError):
            pcap.capture_sync(0)
        with pytest.raises(ValueError):
            pcap.capture_sync(-1)

    @pytest.mark.slow
    def test_real_capture_in_subprocess(self, tmp_path):
        """The REAL jax.profiler path — in a fresh process, where no
        fake PJRT plugin (test_device_plugin) can segfault the
        tracer's device collection. Asserts a nonempty xplane landed
        while jnp work ran inside the window.

        Slow lane (tier-1 rebalance): ~26s of fresh-interpreter + jax
        import; the fast lane keeps every capture LOGIC pin (stubbed
        profiler) and scripts/tpu_smoke.py's profile_capture stage
        drives this same real path end to end."""
        code = (
            "import os, sys, threading\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            "import paddle_tpu as pt\n"
            "pt.set_flags({'FLAGS_enable_monitor': True})\n"
            "import jax.numpy as jnp\n"
            "from paddle_tpu.monitor import profile_capture as pcap\n"
            "stop = threading.Event()\n"
            "def work():\n"
            "    while not stop.is_set():\n"
            "        jnp.ones((64, 64)).sum().block_until_ready()\n"
            "        stop.wait(0.02)\n"
            "t = threading.Thread(target=work); t.start()\n"
            "try:\n"
            "    info = pcap.capture_sync(0.3, base_dir=sys.argv[1])\n"
            "finally:\n"
            "    stop.set(); t.join()\n"
            "assert any(f['path'].endswith('.xplane.pb')\n"
            "           and (f['bytes'] or 0) > 0\n"
            "           for f in info['files']), info\n"
            "print('CAPTURE_OK')\n")
        r = subprocess.run(
            [sys.executable, "-c", code, str(tmp_path)],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"))
        assert r.returncode == 0 and "CAPTURE_OK" in r.stdout, \
            (r.returncode, r.stderr[-2000:])


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

class TestEngineExec:
    def test_serving_programs_sampled(self, mon):
        exectime.set_sample_rate(1)
        import jax
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L
        cfg = L.llama_tiny(num_hidden_layers=2)
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(L, params, cfg, num_slots=2, max_len=32,
                            page_size=8, decode_chunk=2)
        rng = np.random.default_rng(0)
        outs = eng.run([Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, (6,))
            .astype(np.int32), max_new_tokens=4) for i in range(2)])
        assert sorted(outs) == [0, 1]
        by = {r["name"]: r for r in programs.programs_snapshot()}
        chunk = next(v for k, v in by.items()
                     if k.startswith("serving.decode_chunk"))
        assert chunk["exec_samples"] >= 1
        assert chunk["exec_mean_ms"] > 0
        # repeat dispatches count as hits -> staleness stamped
        assert chunk["hits"] >= 1
        assert chunk["last_hit_age_s"] is not None
        # engine samples must NOT feed the step-timeseries last-sample
        # slot — a decode-chunk sample between two train steps would
        # otherwise be misattributed as that train step's exec time
        assert exectime.take_last_sample_ms() is None
