"""The reduction from trace events to numbers, on hand-written events
with known answers and on one small capture from the chip. No test
starts ``jax.profiler``."""
import gzip
import json
import os

import pytest

from benchmark.harness import trace_reduce as T
from benchmark.harness.manifest import BENCH_DIR

D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
MS = 1e6  # ns


def ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


def hand_written():
    """A 100 ms window on two devices.

    Device 0 ops: a loop 10-50 holding fusion.1 10-30 and kernel_a 30-50;
    all-gather.1 60-70; all-reduce.2 70-80 with fusion.2 72-78 inside.
    So busy = 40 + 20 = 60, idle 40: gaps 0-10, 50-60, 80-100.
    Device 1 ops: fusion.1 0-90: idle 10.
    Host: bench.engine_step 0-55 holding serving.prefill 5-8;
    bench.stamp 55-58; nothing after 58.
    """
    return [
        ev(HOST, "main", T.WINDOW_SPAN, 0, 100),
        ev(HOST, "main", "bench.engine_step", 0, 55),
        ev(HOST, "main", "serving.prefill", 5, 3),
        ev(HOST, "main", "bench.stamp", 55, 3),
        ev(D0, T.OPS, "while.3", 10, 40),
        ev(D0, T.OPS, "fusion.1", 10, 20),
        ev(D0, T.OPS, "kernel_a", 30, 20),
        ev(D0, T.OPS, "all-gather.1", 60, 10),
        ev(D0, T.OPS, "all-reduce.2", 70, 10),
        ev(D0, T.OPS, "fusion.2", 72, 6),
        ev(D0, T.MODULES, "jit_step(1)", 10, 40),
        ev(D0, T.MODULES, "jit_step(1)", 60, 20),
        ev(D0, T.MODULES, "jit__pf(2)", 95, 10),   # half outside
        ev(D1, T.OPS, "fusion.1", 0, 90),
    ]


@pytest.fixture(scope="module")
def trace():
    return T.Trace(hand_written())


def test_window_and_planes(trace):
    assert trace.planes == [D0, D1]
    assert trace.window_s == pytest.approx(0.100)


def test_busy_union_and_idle_share(trace):
    assert T.total(trace.busy(D0)) / MS == pytest.approx(60)
    assert T.total(trace.busy(D1)) / MS == pytest.approx(90)
    assert trace.busy_s() == pytest.approx(0.075)       # mean of devices
    assert trace.idle_share() == pytest.approx(0.40)    # worst device


def test_gaps_go_to_the_innermost_host_span(trace):
    idle = trace.idle_by_host_span(D0)
    # 0-10: engine_step holds it but for prefill's 5-8; 50-55 engine_step;
    # 55-58 stamp; 58-60 and 80-100 under no span
    assert idle["bench.engine_step"] == pytest.approx(0.012)
    assert idle["serving.prefill"] == pytest.approx(0.003)
    assert idle["bench.stamp"] == pytest.approx(0.003)
    assert idle["(no span)"] == pytest.approx(0.022)
    assert sum(idle.values()) == pytest.approx(0.040)


def test_module_and_pattern_sums(trace):
    secs, n = trace.matching(T.MODULES, "jit_step")
    assert (secs, n) == (pytest.approx(0.060), 2)
    assert trace.matching(T.MODULES, "_pf")[0] == pytest.approx(0.005)
    # a loop's own event does not count its body twice
    assert trace.matching(T.OPS, "while")[0] == pytest.approx(0.0)
    assert trace.matching(T.OPS, "fusion")[0] == pytest.approx(0.026)
    assert trace.matching(T.OPS, "kernel_a") == (pytest.approx(0.020), 1)
    assert trace.matching(T.OPS, "no such op") == (0.0, 0)


def test_top_ops_are_self_times(trace):
    top = dict(trace.top(T.OPS))
    assert top["fusion.1"] == pytest.approx(0.020)
    assert top.get("while.3", 0.0) == pytest.approx(0.0)
    assert trace.top(T.MODULES, 1)[0][0] == "jit_step(1)"


def test_an_event_the_window_cuts_counts_as_its_share_inside(trace):
    # jit__pf 95-105 against a window that ends at 100: half its seconds
    # and half an event, so seconds over events is still its 10 ms
    secs, n = trace.matching(T.MODULES, "_pf")
    assert n == pytest.approx(0.5) and secs / n == pytest.approx(0.010)


def steps_cut_by_the_profiler():
    """A host that runs ahead of the device: the profiler starts inside
    one 8 ms step and stops inside another, and records of each only the
    part it saw (2 ms, 3 ms). Three whole steps lie between, each with
    one 2 ms kernel; the first step's kernel was over before the start
    and the last step's is cut to 1 ms."""
    evs = [ev(HOST, "main", T.WINDOW_SPAN, 0, 30),
           ev(D0, T.MODULES, "jit_step(1)", 1, 2),        # 6 ms lost
           ev(D0, T.OPS, "fusion.9", 1, 2)]
    for i in range(3):
        evs += [ev(D0, T.MODULES, "jit_step(1)", 3 + 8 * i, 8),
                ev(D0, T.OPS, "fusion.9", 3 + 8 * i, 6),
                ev(D0, T.OPS, "kernel_f", 9 + 8 * i, 2)]
    return evs + [ev(D0, T.MODULES, "jit_step(1)", 27, 3),  # 5 ms lost
                  ev(D0, T.OPS, "fusion.9", 27, 2),
                  ev(D0, T.OPS, "kernel_f", 29, 1)]


def test_events_the_profiler_cut_short_are_not_whole():
    t = T.Trace(steps_cut_by_the_profiler())
    # every event: 29 ms over 5 launches would say a step takes 5.8 ms
    secs, n = t.matching(T.MODULES, "^jit_step")
    assert (secs, n) == (pytest.approx(0.029), pytest.approx(5))
    # whole ones: the three steps of 8 ms, and their three kernels
    secs, n = t.matching(T.MODULES, "^jit_step", whole=True)
    assert (secs, n) == (pytest.approx(0.024), 3)
    assert t.matching(T.OPS, "kernel_f", whole=True) \
        == (pytest.approx(0.006), 3)
    assert t.matching(T.OPS, "kernel_f")[0] == pytest.approx(0.007)


def test_trace_ops_reader_takes_a_step_from_whole_events(capsys):
    from benchmark.readers import trace_ops

    ctx = {"trace": T.Trace(steps_cut_by_the_profiler()), "counters": {},
           "config": {"num_attention_heads": 1, "hidden_size": 2},
           "mix": {"batch": 1, "seq_len": 4}, "peaks": {"flops": 32e3}}
    step = {"line": T.MODULES, "pattern": "^jit_step", "per": "events",
            "scale": 1000.0}
    assert trace_ops.read(step, ctx) == pytest.approx(8.0)
    # 3 whole forward calls x 2 products x 32 FLOPs at a peak of 32 kFLOP/s
    # are 6 ms of work at best, and took 6 ms: 100%, not the 7 calls over
    # 7 ms or 4 calls over 7 ms that a cut event would make of it
    roof = {"line": T.OPS, "pattern": "kernel_", "roofline": {
        "work": "flash_flops", "calls": {"fwd": "kernel_f"}}}
    assert trace_ops.read(roof, ctx) == pytest.approx(100.0)
    # a pattern that matches nothing reads nothing, and says so
    assert trace_ops.read({**step, "pattern": "^jit_renamed"}, ctx) is None
    assert "NOTHING" in capsys.readouterr().out


def test_breakdown_shape(trace):
    b = trace.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "module jit_step(1)"
    assert all(isinstance(s, float) for _, s in b["device_ops"])


def test_no_window_span_falls_back_to_the_device_events():
    t = T.Trace([e for e in hand_written() if e[2] != T.WINDOW_SPAN])
    assert (t.lo / MS, t.hi / MS) == (0, 105)


def test_flatten_gives_self_time():
    pieces = T.flatten([(0, 10, "outer"), (2, 4, "inner"), (4, 6, "inner")])
    by = {}
    for lo, hi, name in pieces:
        by[name] = by.get(name, 0) + hi - lo
    assert by == {"outer": 6, "inner": 4}


def test_a_capture_from_the_chip():
    """A slice of a traced run of a cell on the TPU v5e, stored in the
    reduced form, with the numbers it must give (written when the capture
    was made, PR 23)."""
    path = os.path.join(BENCH_DIR, "testdata", "chip_capture.json.gz")
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    t = T.Trace(doc["events"])
    want = doc["expect"]
    assert t.planes == want["planes"]
    assert t.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert t.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    assert t.idle_share() == pytest.approx(want["idle_share"], rel=1e-9)
    for line, pattern, secs, n in want["matching"]:
        got = t.matching(line, pattern)
        assert got == (pytest.approx(secs, rel=1e-9), n), (line, pattern)
    idle = t.idle_by_host_span()
    assert idle == pytest.approx(want["idle_by_host_span"], rel=1e-9)
    # one chunk of 4 decode steps through 16 layers: 64 paged-kernel calls
    assert want["matching"][1][3] == 64
    assert os.path.getsize(path) < 200 * 1024
