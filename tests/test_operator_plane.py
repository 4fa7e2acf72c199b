"""Operator plane (monitor/server.py + programs.py + memory.py +
fleet.py).

The load-bearing contracts:

- **Off path**: with both monitor flags unset, building/running an
  engine leaves ZERO server threads, sockets, and metric
  registrations — the operator plane is free until asked for.
- **Server lifecycle**: port-0 ephemeral bind, idempotent start,
  clean stop (socket released, thread joined), concurrent scrapes
  while a ServingEngine decodes on the main thread.
- **Liveness**: /healthz flips non-200 when a HangWatchdog deadline is
  blown and recovers on heartbeat; broken providers report but never
  fail liveness; dead (garbage-collected) owners self-prune.
- **Introspection**: a fresh to_static compile appears in /programs
  with signature/compile-ms/FLOPs and a lazily-analyzed XLA memory
  breakdown; serving programs register with their donation maps.
- **Exposition conformance**: expose_text emits strictly parseable
  Prometheus text format 0.0.4 (HELP/TYPE discipline, escaping,
  cumulative le buckets, _sum/_count consistency).
- **Fleet aggregation**: min/max/sum/per-host views + divergence, the
  same on every rank (2-process launch CLI, slow lane), served from
  rank 0's /metrics?scope=fleet without peers joining the scrape.
"""
import gc
import json
import math
import os
import re
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.monitor import exposition
from paddle_tpu.monitor import fleet
from paddle_tpu.monitor import memory as mon_memory
from paddle_tpu.monitor import programs
from paddle_tpu.monitor import server
from paddle_tpu.monitor.registry import StatRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def mon():
    """Monitor flag on, clean registry; server + flags torn down."""
    monitor.reset()
    server.stop_server()
    pt.set_flags({"FLAGS_enable_monitor": True})
    yield monitor
    server.stop_server()
    pt.set_flags({"FLAGS_enable_monitor": False,
                  "FLAGS_enable_monitor_server": False})
    monitor.reset()


def _get(url, timeout=10):
    """(status, body-bytes) — non-2xx does not raise."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _tiny_engine(num_slots=2, max_new=None):
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.models import llama as L
    cfg = L.llama_tiny()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    return ServingEngine(L, params, cfg, num_slots=num_slots,
                         max_len=32, page_size=4, decode_chunk=3), cfg


def _requests(cfg, n, max_new=4, seed=0):
    from paddle_tpu.inference import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, (5,))
                    .astype(np.int32),
                    max_new_tokens=max_new) for i in range(n)]


def _server_threads():
    return [t for t in threading.enumerate()
            if t.name == "paddle-tpu-monitor-server"]


# ---------------------------------------------------------------------------
# server lifecycle
# ---------------------------------------------------------------------------

def _collect_dead_engines():
    """An earlier test file's engine that died inside a reference cycle
    may have been frozen before the collector reached it (the engine
    calls ``gc.freeze()`` after each program's first call): its health
    provider would then outlive it into this file's counts, by which
    files xdist happens to hand this worker first."""
    gc.unfreeze()
    gc.collect()


class TestServerLifecycle:
    def test_flag_off_no_thread_no_socket_no_registrations(self):
        """The acceptance off-path: both flags unset -> building and
        running an engine starts nothing and registers nothing."""
        _collect_dead_engines()
        monitor.reset()
        server.stop_server()
        pt.set_flags({"FLAGS_enable_monitor": False,
                      "FLAGS_enable_monitor_server": False})
        assert server.maybe_start() is None
        eng, cfg = _tiny_engine()
        eng.run(_requests(cfg, 1))
        assert server.get_server() is None
        assert server.bound_port() is None
        assert _server_threads() == []
        assert monitor.snapshot() == {}
        assert programs.programs_snapshot() == []
        # ...and no health-provider entry either: a fully-off process
        # must not grow the provider map one entry per engine
        _, payload = server.health()
        assert not any(k.startswith("serving:")
                       for k in payload["providers"])

    def test_ephemeral_bind_scrape_and_stop(self, mon):
        srv = server.start_server(port=0)
        assert srv.port > 0
        assert server.bound_port() == srv.port
        monitor.inc("lifecycle.probe", 2, doc="probe")
        status, body = _get(f"{srv.url}/metrics")
        assert status == 200
        assert "lifecycle_probe 2" in body.decode()
        port = srv.port
        server.stop_server()
        assert server.get_server() is None
        # the socket is actually released
        with pytest.raises(OSError):
            socket.create_connection(("127.0.0.1", port), timeout=0.5)
        time.sleep(0.05)
        assert _server_threads() == []

    def test_start_idempotent_and_maybe_start_gated(self, mon):
        srv = server.start_server(port=0)
        assert server.start_server() is srv
        # flag still off -> maybe_start returns the RUNNING server?
        # no: maybe_start is the flag-gated seam; with the flag off it
        # must stay a no-op branch even while a manual server runs
        assert server.maybe_start() is None
        pt.set_flags({"FLAGS_enable_monitor_server": True})
        assert server.maybe_start() is srv

    def test_engine_entrypoint_starts_server(self, mon):
        pt.set_flags({"FLAGS_enable_monitor_server": True})
        eng, cfg = _tiny_engine()
        srv = server.get_server()
        assert srv is not None, "ServingEngine did not start the server"
        status, body = _get(f"{srv.url}/healthz")
        assert status == 200
        providers = json.loads(body)["providers"]
        assert any(k.startswith("serving:") for k in providers)

    def test_root_index_and_404(self, mon):
        srv = server.start_server(port=0)
        status, body = _get(f"{srv.url}/")
        assert status == 200
        assert "/metrics" in json.loads(body)["routes"]
        status, _ = _get(f"{srv.url}/nope")
        assert status == 404

    def test_flight_endpoint_live_record(self, mon):
        from paddle_tpu.monitor import trace
        srv = server.start_server(port=0)
        with trace.span("op.test", tag=1):
            pass
        status, body = _get(f"{srv.url}/flight")
        assert status == 200
        payload = json.loads(body)
        assert payload["kind"] == "paddle_tpu.flight_record"
        assert payload["reason"] == "operator_scrape"
        assert any(e["name"] == "op.test" for e in payload["events"])
        assert "metrics" in payload


class TestConcurrentScrapes:
    @pytest.mark.slow  # tier-1 budget (ISSUE 19 rebalance): concurrency stress; ephemeral_bind_scrape +
    # engine_entrypoint keep the scrape seam fast
    def test_scrapes_during_live_engine_run(self, mon):
        """The acceptance scenario: while the engine decodes, /metrics
        returns conformant text carrying the serving SLO histograms and
        jit.program.* FLOPs, and concurrent scrapers never error."""
        srv = server.start_server(port=0)
        eng, cfg = _tiny_engine()
        for r in _requests(cfg, 4, max_new=8):
            eng.submit(r)
        results = []
        stop = threading.Event()

        def scraper(route):
            while not stop.is_set():
                status, body = _get(f"{srv.url}{route}")
                results.append((route, status))
                if status != 200:
                    return

        threads = [threading.Thread(target=scraper, args=(route,))
                   for route in ("/metrics", "/healthz", "/programs")
                   for _ in range(2)]
        for t in threads:
            t.start()
        try:
            outs = eng.run()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
        assert len(outs) == 4
        assert results, "scrapers never ran"
        assert all(status == 200 for _, status in results), \
            [r for r in results if r[1] != 200]
        status, body = _get(f"{srv.url}/metrics")
        text = body.decode()
        families = parse_prometheus(text)   # conformant under load
        assert "serving_latency_ttft_ms" in families
        assert families["serving_latency_ttft_ms"]["type"] == "histogram"
        assert "jit_program_flops" in families
        assert families["jit_program_flops"]["samples"][0][2] > 0
        assert "serving_tokens_generated" in families


# ---------------------------------------------------------------------------
# /healthz
# ---------------------------------------------------------------------------

class TestHealthz:
    def test_watchdog_stall_flips_503_and_recovers(self, mon):
        from paddle_tpu.training.sentinel import HangWatchdog
        srv = server.start_server(port=0)
        wd = HangWatchdog(deadline_s=0.2, poll_s=0.05, name="hz")
        with wd:
            status, body = _get(f"{srv.url}/healthz")
            assert status == 200
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                status, body = _get(f"{srv.url}/healthz")
                if status == 503:
                    break
                time.sleep(0.05)
            assert status == 503, "healthz never flipped on the stall"
            payload = json.loads(body)
            assert payload["status"] == "unhealthy"
            rep = next(v for k, v in payload["providers"].items()
                       if k.startswith("watchdog:hz:"))
            assert rep["ok"] is False
            assert rep["last_heartbeat_age_s"] > 0.2
            # recovery: a heartbeat re-arms liveness on the next probe
            wd.heartbeat()
            status, body = _get(f"{srv.url}/healthz")
            assert status == 200
            assert json.loads(body)["status"] == "ok"
        # stop() unregisters exactly this instance's provider
        ok, payload = server.health()
        assert not any(k.startswith("watchdog:hz")
                       for k in payload["providers"])

    def test_broken_provider_reports_but_keeps_liveness(self, mon):
        def boom():
            raise RuntimeError("telemetry hook crashed")
        server.register_health_provider("boom", boom)
        try:
            ok, payload = server.health()
            assert ok
            assert "telemetry hook crashed" in \
                payload["providers"]["boom"]["error"]
        finally:
            server.unregister_health_provider("boom")

    def test_dead_owner_self_prunes_and_engines_coexist(self, mon):
        _collect_dead_engines()
        eng, cfg = _tiny_engine()
        eng2, _ = _tiny_engine(num_slots=1)
        ok, payload = server.health()
        serving = {k: v for k, v in payload["providers"].items()
                   if k.startswith("serving:")}
        # two live engines = two providers (neither evicts the other)
        assert len(serving) == 2
        assert {v["num_slots"] for v in serving.values()} == {1, 2}
        del eng, eng2
        _collect_dead_engines()
        ok, payload = server.health()
        assert not any(k.startswith("serving:")
                       for k in payload["providers"])

    def test_sentinel_loop_ladder_state(self, mon):
        from paddle_tpu.training.sentinel import (AnomalySentinel,
                                                  SentinelConfig,
                                                  SentinelLoop)
        sent = AnomalySentinel(SentinelConfig(agree=False, name="hzt"))
        loop = SentinelLoop(lambda *a: None, {}, {},
                            lambda: iter(()), sentinel=sent)
        ok, payload = server.health()
        key, rep = next((k, v) for k, v in payload["providers"].items()
                        if k.startswith("sentinel:"))
        assert ok and rep["ok"] and rep["rollbacks"] == 0
        # a loop that burned its rollback budget is alive but cannot
        # recover itself -> unhealthy (supervisor should replace it)
        sent.rollbacks = sent.config.max_rollbacks
        ok, payload = server.health()
        assert not ok
        assert payload["providers"][key]["ok"] is False
        del loop


# ---------------------------------------------------------------------------
# /programs + /memory introspection
# ---------------------------------------------------------------------------

class TestPrograms:
    def test_fresh_compile_lands_in_programs_endpoint(self, mon):
        import paddle_tpu.jit as jit
        import paddle_tpu.nn as nn
        srv = server.start_server(port=0)
        net = nn.Linear(4, 2)
        sf = jit.to_static(net.forward)
        x = pt.to_tensor(np.ones((3, 4), "float32"))
        with pt.no_grad():
            sf(x)
            sf(x)
        status, body = _get(f"{srv.url}/programs")
        assert status == 200
        recs = json.loads(body)["programs"]
        rec = next(r for r in recs if r["name"] == "forward")
        assert "float32[3,4]" in rec["signature"]
        assert rec["compile_ms"] > 0
        assert rec["flops"] > 0
        assert rec["hits"] == 1
        # the endpoint resolved the lazy XLA memory analysis
        assert rec["memory"] is not None
        for k in ("argument_bytes", "output_bytes", "temp_bytes"):
            assert k in rec["memory"]
        # ...and the byte gauges now exist for /metrics
        gauges = monitor.snapshot()["gauges"]
        assert "jit.program.last_argument_bytes" in gauges
        assert gauges["jit.program.count"] >= 1

    @pytest.mark.slow
    def test_serving_programs_carry_donation_map(self, mon):
        # engine-construction-heavy; the concurrent-scrape acceptance
        # test already proves serving programs register with FLOPs, so
        # the donation-map pin rides the slow lane
        eng, cfg = _tiny_engine()
        eng.run(_requests(cfg, 2))
        recs = programs.programs_snapshot()
        by_name = {r["name"]: r for r in recs}
        chunk = next(v for k, v in by_name.items()
                     if k.startswith("serving.decode_chunk"))
        assert chunk["donated_args"] == [1, 2]     # the KV pools
        prefill = next(v for k, v in by_name.items()
                       if k.startswith("serving.prefill"))
        assert prefill["donated_args"] == [2, 3]
        assert chunk["flops"] > 0

    @pytest.mark.slow
    def test_monitor_reset_recovers_serving_registration(self, mon):
        """The registry is the dedup: after monitor.reset() mid-run, a
        live engine's next dispatch re-registers its programs (an
        engine-local seen-set would leave /programs and the headroom
        temp reservation empty forever). Engine-construction-heavy ->
        slow lane."""
        eng, cfg = _tiny_engine()
        eng.run(_requests(cfg, 1))
        assert programs.programs_snapshot()
        monitor.reset()
        assert programs.programs_snapshot() == []
        eng.run(_requests(cfg, 1, seed=1))
        names = [r["name"] for r in programs.programs_snapshot()]
        assert any(n.startswith("serving.") for n in names), names

    def test_registry_bounded_fifo(self, mon):
        for i in range(300):
            programs.record_program(("t", i), f"p{i}", source="test")
        snap = programs.programs_snapshot()
        assert len(snap) == 256
        assert programs.evicted_count() == 44
        assert snap[0]["name"] == "p299"           # newest first
        assert all(r["name"] != "p0" for r in snap)

    def test_monitor_off_registers_nothing(self):
        monitor.reset()
        pt.set_flags({"FLAGS_enable_monitor": False})
        import paddle_tpu.jit as jit
        import paddle_tpu.nn as nn
        sf = jit.to_static(nn.Linear(3, 3).forward)
        with pt.no_grad():
            sf(pt.to_tensor(np.ones((2, 3), "float32")))
        assert programs.programs_snapshot() == []
        assert monitor.snapshot() == {}

    def test_dead_owner_analyzer_reports_not_raises(self, mon):
        import paddle_tpu.jit as jit
        import paddle_tpu.nn as nn
        net = nn.Linear(4, 2)
        sf = jit.to_static(net.forward)
        with pt.no_grad():
            sf(pt.to_tensor(np.ones((2, 4), "float32")))
        del sf, net
        import gc
        gc.collect()
        programs.analyze_pending()
        rec = programs.programs_snapshot()[0]
        assert rec["memory"] is None
        assert "ReferenceError" in rec.get("analyze_error", "") or \
            rec.get("analyze_error")


class TestMemoryIntrospection:
    def test_device_helper_backend_safe(self):
        from paddle_tpu.device.memory import memory_stats

        class NoneDev:
            def memory_stats(self):
                return None

        class RaisingDev:
            def memory_stats(self):
                raise RuntimeError("backend says no")

        class PartialDev:
            def memory_stats(self):
                return {"bytes_in_use": 5}

        assert memory_stats(NoneDev()) == {}
        assert memory_stats(RaisingDev()) == {}
        assert memory_stats(PartialDev()) == {"bytes_in_use": 5}

    def test_cuda_parity_path_uses_helper(self):
        # CPU backend reports nothing -> the paddle-parity queries
        # answer 0 without raising (the old behavior, now via the
        # shared helper)
        from paddle_tpu.device import cuda
        assert cuda.memory_allocated() == 0
        assert cuda.max_memory_allocated() == 0
        assert cuda.get_device_properties().total_memory == 0

    def test_no_fake_gauges_on_silent_backend(self, mon):
        stats = mon_memory.update_hbm_gauges(stats_fn=lambda: [{}, {}])
        assert stats["totals"] == {}
        gauges = monitor.snapshot().get("gauges", {})
        assert not any(k.startswith("device.hbm") for k in gauges)

    def test_hbm_gauges_sum_reporting_devices(self, mon):
        fake = [{"bytes_in_use": 10, "bytes_limit": 100,
                 "peak_bytes_in_use": 40},
                {},                                  # silent device
                {"bytes_in_use": 30, "bytes_limit": 100}]
        stats = mon_memory.update_hbm_gauges(stats_fn=lambda: fake)
        assert stats["devices_reporting"] == 2
        g = monitor.snapshot()["gauges"]
        assert g["device.hbm.bytes_in_use"] == 40
        assert g["device.hbm.bytes_limit"] == 200
        assert g["device.hbm.peak_bytes_in_use"] == 40
        assert g["device.hbm.headroom_bytes"] == 160

    def test_headroom_composes_pages_and_program_temps(self, mon):
        monitor.set_gauge("serving.pages.total", 20)
        monitor.set_gauge("serving.pages.in_use", 5)
        programs.record_program(
            ("hr", 0), "big", source="test",
            analyzer=lambda: {"temp_bytes": 30})
        programs.analyze_pending()
        fake = [{"bytes_in_use": 10, "bytes_limit": 110}]
        hr = mon_memory.headroom(stats_fn=lambda: fake)
        assert hr["pages_total"] == 20
        assert hr["pages_free_fraction"] == 0.75
        assert hr["program_temp_bytes_max"] == 30
        assert hr["hbm_free_bytes"] == 100
        assert hr["est_admittable_bytes"] == 70
        g = monitor.snapshot()["gauges"]
        assert g["serving.headroom.pages_free_fraction"] == 0.75

    def test_memory_endpoint(self, mon):
        srv = server.start_server(port=0)
        status, body = _get(f"{srv.url}/memory")
        assert status == 200
        payload = json.loads(body)
        assert "hbm" in payload and "headroom" in payload
        # CPU backend: nothing reported, nothing fabricated
        assert payload["hbm"]["totals"] == {}
        assert payload["headroom"]["hbm_free_bytes"] is None


# ---------------------------------------------------------------------------
# Prometheus exposition conformance (strict format)
# ---------------------------------------------------------------------------

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_SAMPLE_RE = re.compile(
    rf"^({_NAME})(\{{(?:[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\.)*\""
    rf"(?:,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\.)*\")*)?\}})? "
    r"(-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?|\+Inf|-Inf|NaN)$")
_LABEL_RE = re.compile(r"([a-zA-Z_][a-zA-Z0-9_]*)=\"((?:[^\"\\\n]|\\.)*)\"")


def _unescape_label(v: str) -> str:
    return (v.replace("\\n", "\n").replace('\\"', '"')
            .replace("\\\\", "\\"))


def _parse_value(s: str) -> float:
    return {"+Inf": math.inf, "-Inf": -math.inf,
            "NaN": math.nan}.get(s, None) if s in ("+Inf", "-Inf", "NaN") \
        else float(s)


def parse_prometheus(text: str) -> dict:
    """Strict 0.0.4 parser. Raises AssertionError on any violation:
    unknown line shape, sample before its TYPE, duplicate TYPE, help
    after samples started. Returns {family: {"type", "help",
    "samples": [(name, labels-dict, value)]}}."""
    families: dict = {}
    assert text.endswith("\n") or text == "", "missing trailing newline"
    for line in text.splitlines():
        assert line == line.strip("\r"), f"stray CR in {line!r}"
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, help_text = rest.partition(" ")
            assert re.fullmatch(_NAME, name), f"bad HELP name {name!r}"
            fam = families.setdefault(
                name, {"type": None, "help": None, "samples": []})
            assert not fam["samples"], f"HELP after samples for {name}"
            fam["help"] = help_text
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, kind = rest.partition(" ")
            assert re.fullmatch(_NAME, name), f"bad TYPE name {name!r}"
            assert kind in ("counter", "gauge", "histogram", "summary",
                            "untyped"), f"bad kind {kind!r}"
            fam = families.setdefault(
                name, {"type": None, "help": None, "samples": []})
            assert fam["type"] is None, f"duplicate TYPE for {name}"
            fam["type"] = kind
            continue
        assert not line.startswith("#"), f"unparseable comment {line!r}"
        if not line:
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line {line!r}"
        sname, labels_blob, value = m.group(1), m.group(2), m.group(3)
        labels = {}
        if labels_blob:
            labels = {k: _unescape_label(v)
                      for k, v in _LABEL_RE.findall(labels_blob)}
        # a histogram's series attach to the base family
        base = sname
        for suffix in ("_bucket", "_sum", "_count"):
            if sname.endswith(suffix) and sname[:-len(suffix)] \
                    in families and families[sname[:-len(suffix)]][
                        "type"] == "histogram":
                base = sname[:-len(suffix)]
        fam = families.get(base)
        assert fam is not None and fam["type"] is not None, \
            f"sample {sname!r} before its TYPE line"
        fam["samples"].append((sname, labels, _parse_value(value)))
    return families


class TestExpositionConformance:
    def _nasty_registry(self):
        r = StatRegistry()
        r.counter("ops.total",
                  'line1\nline2 "quoted" and \\backslash').incr(3)
        g = r.gauge("queue.depth", "plain doc")
        g.set(7)
        h = r.histogram("lat.ms", "latency", buckets=(1.0, 5.0, 25.0))
        for v in (0.5, 3.0, 4.0, 100.0):
            h.observe(v)
        return r

    def test_strict_parse_and_histogram_consistency(self):
        text = exposition.expose_text(self._nasty_registry())
        fams = parse_prometheus(text)
        assert fams["ops_total"]["type"] == "counter"
        assert fams["ops_total"]["samples"] == [("ops_total", {}, 3)]
        # HELP escaping: the raw newline/quote/backslash survive the
        # round trip as escapes, not as format-breaking bytes
        assert "\n" not in fams["ops_total"]["help"]
        assert fams["ops_total"]["help"] == \
            'line1\\nline2 "quoted" and \\\\backslash'
        hist = fams["lat_ms"]
        assert hist["type"] == "histogram"
        buckets = [(s[1]["le"], s[2]) for s in hist["samples"]
                   if s[0] == "lat_ms_bucket"]
        # le ascending, counts cumulative (nondecreasing), +Inf last
        les = [float("inf") if le == "+Inf" else float(le)
               for le, _ in buckets]
        assert les == sorted(les) and les[-1] == float("inf")
        counts = [c for _, c in buckets]
        assert counts == sorted(counts)
        assert buckets == [("1", 1), ("5", 3), ("25", 3), ("+Inf", 4)]
        count = next(s[2] for s in hist["samples"]
                     if s[0] == "lat_ms_count")
        total = next(s[2] for s in hist["samples"]
                     if s[0] == "lat_ms_sum")
        assert count == 4 == counts[-1]
        assert total == pytest.approx(107.5)

    def test_canonical_pin(self):
        """Exact output pin for a minimal registry — scrapers parse
        bytes, so the format is a contract, not a style."""
        r = StatRegistry()
        r.counter("a.count", "doc A").incr(2)
        r.gauge("b.val").set(1.5)
        assert exposition.expose_text(r) == (
            "# HELP a_count doc A\n"
            "# TYPE a_count counter\n"
            "a_count 2\n"
            "# TYPE b_val gauge\n"
            "b_val 1.5\n")

    def test_label_value_escaping_round_trip(self):
        nasty = 'a\\b"c\nd'
        line = exposition.render_sample("m.x", {"host": nasty}, 1)
        m = _SAMPLE_RE.match(line)
        assert m, f"escaped sample does not parse: {line!r}"
        (k, v), = _LABEL_RE.findall(m.group(2))
        assert k == "host"
        assert _unescape_label(v) == nasty

    @pytest.mark.slow
    def test_live_registry_scrape_is_conformant(self, mon):
        # the real registry under a busy engine, via HTTP — redundant
        # with the strict parse inside test_scrapes_during_live_engine_
        # run (same scrape, same parser), so it rides the slow lane
        srv = server.start_server(port=0)
        eng, cfg = _tiny_engine()
        eng.run(_requests(cfg, 2))
        status, body = _get(f"{srv.url}/metrics")
        assert status == 200
        fams = parse_prometheus(body.decode())
        for fam in fams.values():
            assert fam["type"] in ("counter", "gauge", "histogram")


# ---------------------------------------------------------------------------
# fleet aggregation
# ---------------------------------------------------------------------------

class TestFleetAggregation:
    def test_single_process_aggregate(self, mon):
        monitor.set_gauge("fa.gauge", 12.5, doc="g")
        monitor.inc("fa.count", 4, doc="c")
        monitor.observe("fa.lat", 3.0, doc="h")
        agg = fleet.aggregated_snapshot(name="t1")
        assert agg["world_size"] == 1
        s = agg["aggregate"]["scalars"]["fa.gauge"]
        assert s["min"] == s["max"] == s["sum"] == 12.5
        assert s["hosts"] == [12.5]
        h = agg["aggregate"]["histograms"]["fa.lat"]
        assert h["count"] == 1 and h["sum"] == 3.0
        assert agg["divergence"] == []          # one host: no spread
        assert fleet.last_aggregate() is agg

    def test_aggregate_hosts_math_and_divergence(self):
        snaps = [
            {"gauges": {"g.ema": 1.0, "g.only0": 5},
             "counters": {"c.tok": 100}},
            {"gauges": {"g.ema": 1.1}, "counters": {"c.tok": 100}},
            {"gauges": {"g.ema": 9.0}, "counters": {"c.tok": 100}},
        ]
        agg = fleet.aggregate_hosts(snaps)
        ema = agg["scalars"]["g.ema"]
        assert ema["min"] == 1.0 and ema["max"] == 9.0
        assert ema["sum"] == pytest.approx(11.1)
        assert agg["scalars"]["g.only0"]["hosts"] == [5, None, None]
        div = fleet.divergence(agg)
        # the drifting EMA dominates; the identical counter is absent
        assert div[0]["metric"] == "g.ema"
        assert all(d["metric"] != "c.tok" for d in div)
        # a gauge straddling zero (mean ~0) must not blow the ratio up
        # to ~1e9 and bury real divergence — bounded by 2 via |max|
        agg0 = fleet.aggregate_hosts([{"gauges": {"z": 1.0}},
                                      {"gauges": {"z": -1.0}}])
        d0 = fleet.divergence(agg0)
        assert d0[0]["relative_spread"] == pytest.approx(2.0)

    def test_fleet_scope_endpoint_single_process(self, mon):
        srv = server.start_server(port=0)
        monitor.set_gauge("fa.scrape", 3, doc="g")
        status, body = _get(f"{srv.url}/metrics?scope=fleet")
        assert status == 200
        text = body.decode()
        assert 'fa_scrape{agg="sum"} 3' in text
        assert 'fa_scrape{host="0"} 3' in text
        assert "paddle_fleet_world_size 1" in text
        # single-host fleet view is computed FRESH per scrape — a
        # cached payload would freeze the view at its first value
        monitor.set_gauge("fa.scrape", 9, doc="g")
        _, body = _get(f"{srv.url}/metrics?scope=fleet")
        assert 'fa_scrape{agg="sum"} 9' in body.decode()

    def test_fleet_text_of_synthetic_aggregate_parses(self):
        payload = {
            "world_size": 2,
            "aggregate": fleet.aggregate_hosts([
                {"gauges": {"x.y": 1}}, {"gauges": {"x.y": 3}}]),
        }
        fams = parse_prometheus(fleet.expose_fleet_text(payload))
        samples = {(s[0], tuple(sorted(s[1].items()))): s[2]
                   for s in fams["x_y"]["samples"]}
        assert samples[("x_y", (("agg", "min"),))] == 1
        assert samples[("x_y", (("agg", "max"),))] == 3
        assert samples[("x_y", (("agg", "sum"),))] == 4
        assert samples[("x_y", (("host", "1"),))] == 3

    @pytest.mark.slow
    @pytest.mark.slow  # tier-1 budget (ISSUE 19 rebalance): subprocess launch; single-process aggregate +
    # synthetic-aggregate parse pin the math fast
    def test_two_process_launch_agreement(self, tmp_path):
        """Cross-host gather via the launch CLI (KV-store transport —
        no compiled collectives, so it runs on the jax-0.4.37 CPU
        backend where cross-process XLA collectives do not)."""
        worker = os.path.join(REPO, "tests", "_fleet_agg_worker.py")
        log_dir = str(tmp_path / "logs")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--log_dir", log_dir, worker],
            capture_output=True, text=True, timeout=420,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
        logs = {}
        for rank in range(2):
            p = os.path.join(log_dir, f"workerlog.{rank}")
            logs[rank] = open(p).read() if os.path.exists(p) else ""
        blob = logs[0] + logs[1]
        assert r.returncode == 0, blob[-4000:]
        for rank in range(2):
            assert (f"AGG rank={rank} min=10.0 max=20.0 sum=30.0 "
                    "hosts=[10.0, 20.0]") in blob, blob[-4000:]
            assert f"SHARED rank={rank} min=7 max=7 sum=14" in blob
            assert f"HIST rank={rank} count=2 sum=11.0" in blob
            assert f"DIVERGENT rank={rank} yes" in blob
        # rank 0 served the cached aggregate over HTTP with labels
        assert "FLEETSCRAPE rank=0 min=ok host1=ok" in blob, blob[-4000:]
        # both ranks computed the byte-identical aggregate
        digests = sorted(l.split()[-1] for l in blob.splitlines()
                         if l.startswith("DIGEST"))
        assert len(digests) == 2 and digests[0] == digests[1]
