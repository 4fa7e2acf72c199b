"""End-to-end tracing + SLO latency + MFU/goodput layer
(monitor/trace.py, monitor/steptimer.py, monitor/mfu.py, the
Histogram quantile estimator, and the serving-engine lifecycle
instrumentation).

The load-bearing contracts:

- the trace ring is BOUNDED (flight records stay small) yet always
  holds the most recent events;
- a firing fault point / preemption leaves a parseable flight record
  (last spans + full metrics snapshot) — including through
  ``os._exit`` kills (subprocess case);
- the ``serving.latency.*`` histograms populate through a REAL
  ServingEngine trace and their interpolated quantiles agree with
  numpy on synthetic data, degrading to the observed max (never
  inf/NaN) under a hostile bucket layout;
- ``serving.tokens.generated - serving.tokens.discarded`` equals the
  tokens actually emitted to clients, preemption or not;
- with the flag off, every seam registers NOTHING;
- every literal metric name registered in code is documented in
  docs/observability.md (drift check).
"""
import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.monitor import StepTimer, trace
from paddle_tpu.monitor import mfu as mfu_mod
from paddle_tpu.monitor.registry import Histogram
from paddle_tpu.testing import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def mon():
    """Fresh registry + empty trace ring with the flag ON; teardown
    disables BEFORE reset so late finalizers can't re-register."""
    monitor.reset()
    pt.set_flags({"FLAGS_enable_monitor": True})
    yield monitor
    pt.set_flags({"FLAGS_enable_monitor": False})
    # restore the as-imported destination state (an explicit None would
    # mean "disarmed, env ignored" — see set_flight_record_path)
    trace._FLIGHT_PATH[0] = trace._UNSET
    monitor.reset()


# ---------------------------------------------------------------------------
# ring buffer
# ---------------------------------------------------------------------------

class TestRing:
    def test_span_records_duration_and_attrs(self, mon):
        with trace.span("unit.phase", step=3, kind="test"):
            time.sleep(0.001)
        evs = trace.events()
        ev = evs[-1]
        assert ev["name"] == "unit.phase" and ev["ph"] == "X"
        assert ev["dur_ns"] >= 1_000_000
        assert ev["args"] == {"step": 3, "kind": "test"}

    def test_nesting_by_timestamp_containment(self, mon):
        with trace.span("outer"):
            with trace.span("inner"):
                pass
        inner, outer = trace.events()[-2:]
        assert (inner["name"], outer["name"]) == ("inner", "outer")
        assert outer["t_ns"] <= inner["t_ns"]
        assert inner["t_ns"] + inner["dur_ns"] \
            <= outer["t_ns"] + outer["dur_ns"]

    def test_instant(self, mon):
        trace.instant("mark", rid=7)
        ev = trace.events()[-1]
        assert ev["ph"] == "i" and ev["dur_ns"] == 0
        assert ev["args"] == {"rid": 7}

    def test_ring_is_bounded(self, mon):
        trace.clear()
        cap = trace.capacity()
        extra = 64
        for i in range(cap + extra):
            trace.instant("flood", i=i)
        evs = trace.events()
        assert len(evs) == cap                     # bounded
        assert trace.total_events() == cap + extra  # lifetime count
        # and it holds the MOST RECENT events (flight-recorder contract)
        assert evs[0]["args"]["i"] == extra
        assert evs[-1]["args"]["i"] == cap + extra - 1

    def test_reused_span_instance_repairs_t0(self, mon):
        sp = trace.span("reused")
        with sp:
            pass
        with sp:
            pass
        spans = [e for e in trace.events() if e["name"] == "reused"]
        assert len(spans) == 2
        assert spans[1]["t_ns"] > spans[0]["t_ns"]

    def test_off_path_records_nothing(self):
        monitor.reset()
        assert not monitor.enabled()
        with trace.span("off.span", x=1):
            pass
        trace.instant("off.instant")
        assert trace.events() == []
        assert monitor.snapshot() == {}


# ---------------------------------------------------------------------------
# the one span primitive: ring + profiler annotation
# ---------------------------------------------------------------------------

@pytest.fixture
def annotations(monkeypatch):
    """Stub the two ``jax.profiler`` annotation classes ``trace.span``
    enters; returns the log of ``(what, kind, name, kwargs)``. No test
    opens a real profiler session in this process (after
    test_device_plugin's fake PJRT plugin ``start_trace`` segfaults)."""
    log = []

    def stub(kind):
        class Ann:
            def __init__(self, name, **kw):
                self.name, self.kw = name, kw

            def __enter__(self):
                log.append(("enter", kind, self.name, self.kw))
                return self

            def __exit__(self, *exc):
                log.append(("exit", kind, self.name, self.kw))
                return False
        return Ann

    monkeypatch.setattr(trace, "_TraceAnnotation", stub("span"))
    monkeypatch.setattr(trace, "_StepTraceAnnotation", stub("step"))
    return log


def span_tree(log):
    """(name, depth) of every annotation in entry order, after checking
    that they nest properly (each exit closes the innermost open)."""
    out, stack = [], []
    for what, _kind, name, _kw in log:
        if what == "enter":
            out.append((name, len(stack)))
            stack.append(name)
        else:
            assert stack and stack[-1] == name, (name, stack)
            stack.pop()
    assert not stack, stack
    return out


class TestSpanAnnotation:
    @pytest.mark.parametrize("flag", [False, True])
    def test_span_enters_and_leaves_annotation(self, annotations, flag):
        """The profiler annotation is entered flag or no flag; the ring
        records only with the flag on, as before."""
        monitor.reset()
        pt.set_flags({"FLAGS_enable_monitor": flag})
        try:
            with trace.span("unit.outer", k=1):
                with trace.step_span("unit.step", 7, chunk=4):
                    pass
            evs = trace.events()
        finally:
            pt.set_flags({"FLAGS_enable_monitor": False})
            monitor.reset()
        assert annotations == [
            ("enter", "span", "unit.outer", {"k": 1}),
            ("enter", "step", "unit.step", {"step_num": 7, "chunk": 4}),
            ("exit", "step", "unit.step", {"step_num": 7, "chunk": 4}),
            ("exit", "span", "unit.outer", {"k": 1})]
        if flag:
            assert [(e["name"], e.get("args")) for e in evs] == [
                ("unit.step", {"chunk": 4}), ("unit.outer", {"k": 1})]
        else:
            assert evs == []

    def test_annotation_leaves_when_the_body_raises(self, annotations):
        with pytest.raises(KeyError):
            with trace.span("unit.raises"):
                raise KeyError("x")
        assert [a[0] for a in annotations] == ["enter", "exit"]

    def test_no_session_nothing_beyond_the_annotation(self):
        """With no profiler session and the flag off a span is the real
        ``jax.profiler`` annotation and nothing else: the classes are
        JAX's own (no second system), the ring stays empty, nothing is
        registered, and a span costs microseconds (a generous bound:
        an inactive TraceAnnotation measures ~1 us)."""
        import jax
        assert trace._TraceAnnotation is jax.profiler.TraceAnnotation
        assert trace._StepTraceAnnotation \
            is jax.profiler.StepTraceAnnotation
        monitor.reset()
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("unit.idle"):
                pass
        per_span = (time.perf_counter() - t0) / n
        assert per_span < 50e-6, per_span
        assert trace.events() == [] and trace.total_events() == 0
        assert monitor.snapshot() == {}
        from paddle_tpu.monitor import profile_capture
        assert not profile_capture.capturing()
        assert not hasattr(profile_capture, "annotate")


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_explicit_disarm_overrides_env(self, mon, monkeypatch,
                                           tmp_path):
        """set_flight_record_path(None) disarms even when the env var
        is set — the API always wins over the environment."""
        path = str(tmp_path / "fr.json")
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_RECORD", path)
        assert trace.flight_record_path() == path
        trace.set_flight_record_path(None)
        assert trace.flight_record_path() is None
        assert trace.dump_flight_record() is None
        assert not os.path.exists(path)

    def test_unarmed_dump_is_noop(self, mon, tmp_path):
        trace.set_flight_record_path(None)
        assert os.environ.get("PADDLE_TPU_FLIGHT_RECORD") is None
        assert trace.dump_flight_record() is None

    def test_manual_dump_payload(self, mon, tmp_path):
        path = str(tmp_path / "box.json")
        monitor.inc("manual.counter", 3)
        with trace.span("manual.span"):
            pass
        payload = trace.dump_flight_record(path, reason="manual-test")
        on_disk = json.load(open(path))
        assert on_disk == json.loads(json.dumps(payload))
        assert on_disk["kind"] == "paddle_tpu.flight_record"
        assert on_disk["reason"] == "manual-test"
        assert on_disk["metrics"]["counters"]["manual.counter"] == 3
        assert any(e["name"] == "manual.span" for e in on_disk["events"])

    def test_fault_raise_dumps_black_box(self, mon, tmp_path):
        """A firing raise-action fault point writes the armed flight
        record BEFORE unwinding, with the fault stamped in the ring."""
        path = str(tmp_path / "black_box.json")
        trace.set_flight_record_path(path)
        monitor.inc("pre.crash.work", 11)
        with trace.span("pre.crash.phase"):
            pass
        with faults.injected("checkpoint.write", action="raise"):
            with pytest.raises(faults.FaultInjected):
                faults.hit("checkpoint.write")
        rec = json.load(open(path))
        assert rec["reason"] == "fault:checkpoint.write:raise"
        fired = [e for e in rec["events"] if e["name"] == "fault.fired"]
        assert fired and fired[-1]["args"] == {
            "point": "checkpoint.write", "action": "raise"}
        assert any(e["name"] == "pre.crash.phase" for e in rec["events"])
        assert rec["metrics"]["counters"]["pre.crash.work"] == 11

    def test_preemption_hook_dumps_black_box(self, mon, tmp_path):
        """CheckpointManager.finalize_on_preemption (the SIGTERM hook
        body) writes the black box before finalizing anything."""
        from paddle_tpu.distributed.checkpoint.manager import \
            CheckpointManager
        path = str(tmp_path / "preempt_box.json")
        trace.set_flight_record_path(path)
        mgr = CheckpointManager(str(tmp_path / "root"))
        mgr.save(1, {"w": pt.to_tensor(np.ones((2,), "float32"))})
        trace.instant("about.to.die")
        mgr.finalize_on_preemption(timeout=2.0)
        rec = json.load(open(path))
        assert rec["reason"] == "fault:preemption.sigterm:preempt"
        assert any(e["name"] == "about.to.die" for e in rec["events"])
        assert rec["metrics"]["counters"]["ckpt.saves"] == 1

    def test_kill_fault_leaves_parseable_record(self, tmp_path):
        """Acceptance: a kill (os._exit, no atexit/flushes) at a fault
        point leaves a parseable flight record holding the final spans
        + the full metrics snapshot."""
        path = str(tmp_path / "kill_box.json")
        code = (
            "import paddle_tpu as pt\n"
            "from paddle_tpu import monitor\n"
            "from paddle_tpu.monitor import trace\n"
            "from paddle_tpu.testing import faults\n"
            "pt.set_flags({'FLAGS_enable_monitor': True})\n"
            "monitor.inc('crash.test.counter', 7)\n"
            "monitor.observe('crash.test.ms', 2.5)\n"
            "with trace.span('crash.test.phase', step=3):\n"
            "    trace.instant('crash.test.mark')\n"
            "faults.inject('checkpoint.write', action='kill')\n"
            "faults.hit('checkpoint.write')\n"
            "raise SystemExit('fault did not fire')\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PADDLE_TPU_FLIGHT_RECORD=path)
        env.pop("FLAGS_enable_monitor", None)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True,
                             timeout=300, cwd=REPO)
        assert out.returncode == faults.KILL_EXIT_CODE, out.stderr[-2000:]
        rec = json.load(open(path))       # parseable despite os._exit
        assert rec["reason"] == "fault:checkpoint.write:kill"
        names = [e["name"] for e in rec["events"]]
        assert "crash.test.phase" in names
        assert "crash.test.mark" in names
        assert "fault.fired" in names
        assert rec["metrics"]["counters"]["crash.test.counter"] == 7
        assert rec["metrics"]["histograms"]["crash.test.ms"]["count"] == 1


# ---------------------------------------------------------------------------
# chrome-trace export
# ---------------------------------------------------------------------------

class TestChromeExport:
    def test_export_shape(self, mon, tmp_path):
        with trace.span("phase.a", step=1):
            pass
        trace.instant("mark.b")
        path = str(tmp_path / "trace.json")
        trace.export_chrome_trace(path, include_profiler=False)
        evs = json.load(open(path))["traceEvents"]
        spans = [e for e in evs if e.get("name") == "phase.a"]
        marks = [e for e in evs if e.get("name") == "mark.b"]
        assert spans and spans[0]["ph"] == "X" and "dur" in spans[0]
        assert spans[0]["ts"] >= 0 and spans[0]["args"] == {"step": 1}
        assert marks and marks[0]["ph"] == "i"

    def test_merges_profiler_host_spans(self, mon, tmp_path):
        from paddle_tpu import profiler
        rec = profiler._get_recorder()
        rec.start()
        with profiler.RecordEvent("host.prof.span"):
            pass
        rec.stop()
        with trace.span("sched.span"):
            pass
        path = str(tmp_path / "merged.json")
        trace.export_chrome_trace(path)
        evs = json.load(open(path))["traceEvents"]
        own = [e for e in evs if e.get("name") == "sched.span"]
        prof = [e for e in evs if e.get("name") == "host.prof.span"]
        assert own and own[0]["pid"] == 0
        assert prof and prof[0]["pid"] == 1     # second process track
        # one timeline: both offsets computed from the shared t0
        assert prof[0]["ts"] >= 0 and own[0]["ts"] >= 0


# ---------------------------------------------------------------------------
# histogram quantiles
# ---------------------------------------------------------------------------

class TestQuantiles:
    def test_matches_numpy_on_uniform_data(self):
        rng = np.random.default_rng(7)
        data = rng.uniform(0.0, 100.0, size=2000)
        h = Histogram("h", buckets=tuple(float(b) for b in range(1, 101)))
        for v in data:
            h.observe(v)
        for q in (0.5, 0.9, 0.99):
            est = h.quantile(q)
            want = float(np.percentile(data, q * 100))
            # interpolation error is bounded by the bucket width (1.0)
            # plus the rank-definition delta; 1.5 covers both
            assert abs(est - want) < 1.5, (q, est, want)

    def test_snapshot_carries_quantiles(self, mon):
        for v in (1.0, 2.0, 3.0, 4.0):
            monitor.observe("q.h", v, buckets=(1.0, 2.0, 4.0, 8.0))
        s = monitor.snapshot()["histograms"]["q.h"]
        for key in ("p50", "p90", "p95", "p99"):
            assert s["min"] <= s[key] <= s["max"]
        assert s["p50"] <= s["p99"]

    def test_below_data_buckets_degrade_to_observed_max(self):
        """Buckets entirely below the data pile everything into +Inf;
        the degraded answer is the observed max — never inf/NaN."""
        h = Histogram("h", buckets=(0.001, 0.01))
        for v in (5.0, 10.0, 20.0):
            h.observe(v)
        for q in (0.01, 0.5, 0.99, 1.0):
            est = h.quantile(q)
            assert np.isfinite(est)
            assert est == 20.0
        snap = h.snapshot()
        assert np.isfinite(snap["p99"]) and snap["p99"] == 20.0

    def test_partial_overflow_clamps_to_observed_range(self):
        h = Histogram("h", buckets=(10.0,))
        h.observe(5.0)
        h.observe(50.0)
        assert h.quantile(0.99) == 50.0          # +Inf bucket -> max
        assert h.quantile(0.25) >= 5.0           # clamped to min
        assert np.isfinite(h.quantile(0.25))

    def test_empty_and_invalid(self):
        h = Histogram("h")
        assert h.quantile(0.5) is None
        assert h.quantiles() == {}
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_quantiles_dict_keys(self):
        h = Histogram("h", buckets=(1.0, 2.0))
        h.observe(1.5)
        qs = h.quantiles((0.5, 0.95))
        assert set(qs) == {"p50", "p95"}


# ---------------------------------------------------------------------------
# serving lifecycle -> latency histograms (real engine trace)
# ---------------------------------------------------------------------------

@pytest.mark.serving
class TestServingLatency:
    def _engine(self, **kw):
        import jax
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.models import llama as L
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(3))
        return ServingEngine(L, params, cfg, **kw), cfg

    def _reqs(self, cfg, rng, lens, new):
        from paddle_tpu.inference import Request
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            (n,)).astype(np.int32),
                        max_new_tokens=m)
                for i, (n, m) in enumerate(zip(lens, new))]

    def test_lifecycle_populates_slo_histograms(self, mon):
        eng, cfg = self._engine(num_slots=3, max_len=48, page_size=4,
                                decode_chunk=2)
        rng = np.random.default_rng(11)
        reqs = self._reqs(cfg, rng, lens=(3, 7, 5, 9, 4, 6),
                          new=(4, 3, 5, 2, 6, 3))
        outs = eng.run(reqs)
        assert sorted(outs) == [r.rid for r in reqs]
        n = len(reqs)
        reg = monitor.registry()
        ttft = reg.get("serving.latency.ttft_ms")
        e2e = reg.get("serving.latency.e2e_ms")
        qw = reg.get("serving.latency.queue_wait_ms")
        tpot = reg.get("serving.latency.tpot_ms")
        assert ttft.count == n          # one first token per request
        assert e2e.count == n           # one retirement per request
        assert qw.count == n            # one admission per request
        # every request generated >= 2 tokens -> has a decode phase
        assert tpot.count == n
        for h in (ttft, e2e, qw, tpot):
            s = h.snapshot()
            assert s["min"] >= 0 and np.isfinite(s["p99"])
            assert s["p50"] <= s["p99"]
        # e2e covers ttft by construction (same t0, later stamp)
        assert e2e.snapshot()["avg"] >= ttft.snapshot()["avg"]
        # lifecycle milestones landed in the trace ring per request
        names = [(e["name"], e.get("args", {}).get("rid"))
                 for e in trace.events()]
        for r in reqs:
            for ev in ("serving.enqueue", "serving.admit",
                       "serving.first_token", "serving.retire"):
                assert (ev, r.rid) in names
        # no preemption happened, so nothing was discarded and
        # generated == emitted (the easy half of the audit pin; the
        # preemption case below pins the hard half)
        s = eng.stats
        assert s.preempted == 0 and s.tokens_discarded == 0
        assert s.tokens_generated == \
            sum(len(outs[r.rid].tokens) for r in reqs)

    def test_token_invariant_drained_engine(self, mon):
        """generated - discarded == tokens emitted to clients, with
        and without preemption (the double-counting audit pin)."""
        eng, cfg = self._engine(num_slots=2, max_len=16, page_size=4,
                                num_pages=5, decode_chunk=2)
        rng = np.random.default_rng(5)
        reqs = self._reqs(cfg, rng, lens=(4, 4, 4), new=(8, 8, 8))
        outs = eng.run(reqs)
        s = eng.stats
        emitted = sum(len(outs[r.rid].tokens) for r in reqs)
        assert s.preempted >= 1            # tiny pool forces eviction
        assert s.tokens_discarded > 0
        assert s.tokens_generated - s.tokens_discarded == emitted
        # prefill counts the full prompt per ADMISSION (a preempted
        # request re-prefills); every prompt here is 4 tokens
        assert s.tokens_prefilled == s.admitted * 4
        # monitor counters agree with engine stats exactly
        c = monitor.snapshot()["counters"]
        assert c["serving.tokens.generated"] == s.tokens_generated
        assert c["serving.tokens.discarded"] == s.tokens_discarded
        assert c["serving.tokens.prefilled"] == s.tokens_prefilled
        # TTFT: exactly one sample per completed request even though
        # preempted requests prefilled more than once — a discarded
        # run's first token never lands in the histogram
        assert s.admitted > s.completed
        assert monitor.registry().get(
            "serving.latency.ttft_ms").count == s.completed

    def test_engine_off_path_registers_nothing(self):
        monitor.reset()
        assert not monitor.enabled()
        eng, cfg = self._engine(num_slots=2, max_len=32, page_size=4,
                                decode_chunk=2)
        rng = np.random.default_rng(2)
        eng.run(self._reqs(cfg, rng, lens=(3, 4), new=(3, 3)))
        assert monitor.snapshot() == {}
        assert trace.events() == []


@pytest.mark.serving
class TestServingSpans:
    """The span tree of ``ServingEngine.step()`` as a profiler session
    would record it (stubbed annotations, monitor off)."""

    STEP = {"serving.step.retire", "serving.step.compact",
            "serving.step.admit", "serving.step.reserve",
            "serving.decode_chunk", "serving.spec_chunk"}
    PHASES = ("build", "dispatch", "fetch", "emit")

    def _run(self, annotations, eos=None, **kw):
        import jax
        from paddle_tpu.inference import Request, ServingEngine
        from paddle_tpu.models import llama as L
        cfg = L.llama_tiny()
        params = L.init_params(cfg, jax.random.PRNGKey(3))
        eng = ServingEngine(L, params, cfg, num_slots=2, max_len=32,
                            page_size=4, decode_chunk=2, **kw)
        rng = np.random.default_rng(2)
        # two same-bucket prompts (one prefill group), then a third
        # that joins later through the same prefill program
        for rid, n in enumerate((3, 4, 3)):
            eng.submit(Request(
                rid=rid, prompt=rng.integers(0, cfg.vocab_size,
                                             (n,)).astype(np.int32),
                max_new_tokens=5, eos_token_id=eos))
        steps, self.raw = [], []
        while True:
            del annotations[:]
            busy = eng.step()
            steps.append(span_tree(annotations))
            self.raw += annotations
            if not busy:
                break
        assert sorted(eng.outputs) == [0, 1, 2]
        assert trace.events() == []          # monitor off: ring empty
        return steps

    def test_step_span_names_and_nesting(self, annotations):
        steps = self._run(annotations)
        first = steps[0]
        # every step is one serving.step root; its children are the
        # phases, in order
        for tree in steps:
            assert tree[0] == ("serving.step", 0)
            assert {n for n, d in tree if d == 1} <= self.STEP
            assert all(n.startswith("serving.") for n, _ in tree)
        assert [n for n, d in first if d == 1] == [
            "serving.step.retire", "serving.step.compact",
            "serving.step.admit", "serving.step.reserve",
            "serving.decode_chunk"]
        # the prefill of the admitted group sits inside admit, and the
        # first call compiles. No request names an EOS, so nothing before
        # the chunk's dispatch reads the first tokens: they go to their
        # slots on the device (the join's program compiles beside the
        # group's prefill, under its build), the chunk follows the
        # prefill at once, and the host reads them after its dispatch
        i = first.index(("serving.prefill", 2))
        assert first[i - 1] == ("serving.step.admit", 1)
        assert first[i + 1:i + 6] == [
            ("serving.prefill.build", 3), ("serving.compile", 4),
            ("serving.prefill.dispatch", 3), ("serving.compile", 4),
            ("serving.step.reserve", 1)]
        j = first.index(("serving.decode_chunk", 1))
        assert first[j + 1:] == [
            ("serving.decode_chunk.build", 2),
            ("serving.decode_chunk.dispatch", 2), ("serving.compile", 3),
            ("serving.prefill.fetch", 2), ("serving.prefill.emit", 2),
            ("serving.decode_chunk.fetch", 2),
            ("serving.decode_chunk.emit", 2)]

    def test_first_token_is_read_inside_the_prefill_when_it_may_end_a_request(
            self, annotations):
        # an EOS may end a request on its first token, which decides what
        # the scheduler does next: the download is not put off
        first = self._run(annotations, eos=10 ** 6)[0]
        i = first.index(("serving.prefill", 2))
        assert first[i + 1:i + 6] == [
            ("serving.prefill.build", 3), ("serving.prefill.dispatch", 3),
            ("serving.compile", 4), ("serving.prefill.fetch", 3),
            ("serving.prefill.emit", 3)]
        j = first.index(("serving.decode_chunk", 1))
        assert first[j + 1:j + 3] == [
            ("serving.decode_chunk.build", 2),
            ("serving.decode_chunk.dispatch", 2)]

    def test_compile_span_on_first_use_only(self, annotations):
        steps = self._run(annotations)
        flat = [n for tree in steps for n, _ in tree]
        chunks = flat.count("serving.decode_chunk")
        prefills = flat.count("serving.prefill")
        assert chunks >= 3 and prefills == 2
        # one program per (group, bucket) prefill shape and one decode
        # chunk program ran: each compiled once, on its first call; and
        # with each group size's first prefill, the join of its first
        # tokens (no request here names an EOS)
        programs = 2 + 1 + 2   # prefill g2 and g1, one chunk length, joins
        assert flat.count("serving.compile") == programs
        for phase in self.PHASES:
            assert flat.count(f"serving.decode_chunk.{phase}") == chunks
            assert flat.count(f"serving.prefill.{phase}") == prefills
        # a later step of the warm engine has no compile in it
        assert "serving.compile" not in [n for n, _ in steps[-2]]

    def test_decode_chunk_is_a_step_annotation(self, annotations):
        """The chunk carries the profiler's step number (the decode
        steps made so far), so a device trace groups by chunk."""
        self._run(annotations)
        chunks = [a for a in self.raw if a[0] == "enter"
                  and a[2] == "serving.decode_chunk"]
        assert len(chunks) >= 3
        nums = [kw["step_num"] for _, _, _, kw in chunks]
        assert nums == sorted(nums) and nums[0] == 0 and nums[1] == 2
        for _what, kind, _name, kw in chunks:
            assert kind == "step" and kw["chunk"] == 2


# ---------------------------------------------------------------------------
# StepTimer: phase split + goodput
# ---------------------------------------------------------------------------

class TestStepTimer:
    def test_phase_split_and_goodput(self, mon):
        st = StepTimer("unit")
        with st.data_wait():
            time.sleep(0.005)
        with st.compute():
            time.sleep(0.01)
        st.end_step(useful_tokens=1000)
        rep = st.report()
        assert rep["steps"] == 1 and rep["useful_tokens"] == 1000
        assert rep["compute_s"] >= 0.009
        assert rep["data_wait_s"] >= 0.004
        assert rep["goodput_tokens_per_sec"] > 0
        assert 0 < rep["compute_fraction"] <= 1.0
        s = monitor.snapshot()
        assert s["histograms"]["train.step.compute_ms"]["count"] == 1
        assert s["histograms"]["train.step.data_wait_ms"]["count"] == 1
        assert s["histograms"]["train.step.total_ms"]["count"] == 1
        assert s["counters"]["train.tokens.useful"] == 1000
        assert s["gauges"]["train.goodput.tokens_per_sec"] > 0
        assert 0 < s["gauges"]["train.goodput.compute_fraction"] <= 1.0
        # each phase left one span on the step timeline
        names = [e["name"] for e in trace.events()]
        assert "step.compute" in names and "step.data_wait" in names

    def test_iter_data_bills_data_wait(self, mon):
        st = StepTimer("loop")

        def slow_loader():
            for i in range(3):
                time.sleep(0.002)
                yield i

        seen = []
        for item in st.iter_data(slow_loader()):
            with st.compute():
                seen.append(item)
            st.end_step(useful_tokens=10)
        assert seen == [0, 1, 2]
        rep = st.report()
        assert rep["steps"] == 3
        assert rep["data_wait_s"] >= 0.005
        h = monitor.snapshot()["histograms"]["train.step.data_wait_ms"]
        # 3 yields + the StopIteration probe are each one next() wait
        assert h["count"] == 4

    def test_phase_exit_releases_ambient_target(self, mon):
        """A phase context restores the previous ambient target on
        exit: a completed loop's timer must not keep collecting
        ambient time (a checkpoint save after fit returns would bill
        to — and keep alive — a dead timer)."""
        from paddle_tpu.monitor import steptimer as st_mod
        st = StepTimer("loop")
        with st.compute():
            assert getattr(st_mod._ACTIVE, "timer", None) is st
        assert getattr(st_mod._ACTIVE, "timer", None) is not st
        outer = StepTimer("outer")
        with outer:                      # scoped activation nests...
            with st.compute():
                assert st_mod._ACTIVE.timer is st
            assert st_mod._ACTIVE.timer is outer
        # ...and releases when the scope closes
        assert getattr(st_mod._ACTIVE, "timer", None) is not outer

    def test_ambient_checkpoint_billing(self, mon, tmp_path):
        """CheckpointManager.save inside an active timer's scope bills
        its wall time to that timer's checkpoint bucket, without the
        loop threading the timer into the manager."""
        from paddle_tpu.distributed.checkpoint.manager import \
            CheckpointManager
        mgr = CheckpointManager(str(tmp_path / "root"))
        st = StepTimer("fit")
        with st:
            mgr.save(1, {"w": pt.to_tensor(np.ones((64,), "float32"))})
        st.end_step()
        rep = st.report()
        assert rep["checkpoint_s"] > 0
        h = monitor.snapshot()["histograms"]["train.step.checkpoint_ms"]
        assert h["count"] == 1

    def test_standalone_checkpoint_lands_in_histogram(self, mon,
                                                      tmp_path):
        from paddle_tpu.distributed.checkpoint.manager import \
            CheckpointManager
        mgr = CheckpointManager(str(tmp_path / "root"))
        mgr.save(1, {"w": pt.to_tensor(np.ones((8,), "float32"))})
        h = monitor.snapshot()["histograms"]["train.step.checkpoint_ms"]
        assert h["count"] == 1      # ambient orphan timer caught it

    def test_off_path_reports_empty(self):
        monitor.reset()
        assert not monitor.enabled()
        st = StepTimer("off")
        with st.data_wait():
            pass
        with st.compute():
            pass
        st.end_step(useful_tokens=5)
        assert st.report() == {}
        assert monitor.snapshot() == {}


# ---------------------------------------------------------------------------
# MFU accounting
# ---------------------------------------------------------------------------

class TestMFU:
    def test_peak_flops_env_override(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "123.5")
        assert mfu_mod.peak_flops() == 123.5

    def test_peak_flops_cpu_nominal(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
        import jax
        dev = jax.devices()[0]
        if dev.platform == "cpu":
            assert mfu_mod.peak_flops(dev) == 1e12

    def test_cost_analysis_flops_shapes(self):
        assert mfu_mod.cost_analysis_flops(None) == 0.0
        assert mfu_mod.cost_analysis_flops({"flops": 32.0}) == 32.0
        assert mfu_mod.cost_analysis_flops(
            [{"flops": 8.0}, {"flops": 4.0}]) == 12.0
        assert mfu_mod.cost_analysis_flops({"flops": -1}) == 0.0
        assert mfu_mod.cost_analysis_flops({"bytes": 9}) == 0.0

    def test_lowered_flops_nonzero_on_matmul(self):
        import jax
        f = jax.jit(lambda x: x @ x)
        x = np.ones((16, 16), np.float32)
        flops = mfu_mod.lowered_flops(f, x)
        assert flops > 0
        # a 16x16 matmul is 2*16^3 = 8192 MACs worth; cost analysis
        # should be in that ballpark, not wildly off
        assert flops >= 2 * 16 ** 3

    def test_mfu_math(self):
        assert mfu_mod.mfu(1e6, 10.0, peak=1e7) == pytest.approx(1.0)
        assert mfu_mod.mfu(0.0, 10.0, peak=1e7) == 0.0
        assert mfu_mod.mfu(1e6, 10.0, peak=0.0) == 0.0

    def test_jit_compile_seam_records_program_flops(self, mon):
        """A to_static cache miss records the compiled program's
        XLA-cost-analysis FLOPs into jit.program.flops."""
        from paddle_tpu import jit

        def f(x):
            return x @ x + 1.0

        sf = jit.to_static(f)
        x = pt.to_tensor(np.ones((8, 8), "float32"))
        sf(x)
        s = monitor.snapshot()
        assert s["counters"].get("jit.program.flops", 0) > 0
        assert s["gauges"].get("jit.program.last_flops", 0) > 0
        before = s["counters"]["jit.program.flops"]
        sf(x)                       # cache hit: no second capture
        after = monitor.snapshot()["counters"]["jit.program.flops"]
        assert after == before

    def test_training_program_counts_backward_flops(self, mon):
        """The grad-path capture lowers the executed vjp composition:
        a training call's recorded FLOPs must exceed the same model's
        forward-only program (backward included, not forward alone)."""
        from paddle_tpu import jit

        def f(x):
            return (x @ x).mean()

        with pt.no_grad():
            jit.to_static(f)(pt.to_tensor(np.ones((8, 8), "float32")))
        fwd = monitor.snapshot()["gauges"]["jit.program.last_flops"]
        assert fwd > 0

        x = pt.to_tensor(np.ones((8, 8), "float32"))
        x.stop_gradient = False
        jit.to_static(f)(x)
        train = monitor.snapshot()["gauges"]["jit.program.last_flops"]
        assert train > fwd


# ---------------------------------------------------------------------------
# docs drift check (tier-1 entry point for scripts/check_metrics_docs.py)
# ---------------------------------------------------------------------------

class TestMetricsDocsDrift:
    def _load(self):
        path = os.path.join(REPO, "scripts", "check_metrics_docs.py")
        spec = importlib.util.spec_from_file_location(
            "check_metrics_docs", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_every_registered_metric_is_documented(self):
        mod = self._load()
        names = mod.registered_names()
        # the scanner must actually find the instrumentation layer
        assert len(names) >= 30, sorted(names)
        assert "serving.latency.ttft_ms" in names
        assert "train.step.total_ms" in names
        assert "jit.program.flops" in names
        assert mod.undocumented() == []

    def test_doc_pattern_shorthands(self, tmp_path):
        mod = self._load()
        doc = tmp_path / "doc.md"
        doc.write_text("| `a.b.hit|miss` | `op.<name>.calls` |\n")
        pats = mod.doc_patterns(str(doc))
        covered = lambda n: any(p.match(n) for p in pats)  # noqa: E731
        assert covered("a.b.hit") and covered("a.b.miss")
        assert covered("op.matmul.calls")
        assert not covered("a.b.evictions")
        assert not covered("op.matmul.calls.extra")
