"""The readers PR 24 added (``trace_host``: the program's own host spans;
``trace_scope``: device time by named scope) on hand-written events with
known answers, and on a capture from the chip with the numbers it gave."""
import gzip
import json
import os

import pytest

from benchmark.harness import trace_reduce as T
from benchmark.harness.manifest import BENCH_DIR, Manifest
from benchmark.readers import trace_host, trace_scope

MS = 1e6            # hand-written events are in milliseconds
HOST, D0 = "/host:CPU", "/device:TPU:0"


def host(name, start, end):
    return (HOST, "python", name, start * MS, (end - start) * MS)


def host_events():
    """Two engine steps, the second cut by the window's end at 100."""
    return [
        host(T.WINDOW_SPAN, 0, 100),
        host("bench.engine_step", 10, 60),
        host("serving.step", 11, 59),
        host("serving.step.admit", 12, 20),
        host("serving.prefill", 13, 19),
        host("serving.prefill.fetch", 15, 18),
        host("serving.decode_chunk", 20, 58),
        host("serving.decode_chunk.build", 20, 22),
        host("serving.decode_chunk.dispatch", 22, 25),
        host("serving.compile", 23, 25),
        host("serving.decode_chunk.fetch", 25, 55),
        host("serving.decode_chunk.emit", 55, 58),
        host("bench.stamp", 60, 62),
        host("bench.engine_step", 90, 120),
        host("serving.step", 91, 119),
        host("serving.decode_chunk.fetch", 95, 118),
        # the device was busy but for 58-62 and 90-95
        (D0, T.OPS, "%fusion.1 = f32[8]{0} fusion(...)", 0, 58 * MS),
        (D0, T.OPS, "%fusion.1 = f32[8]{0} fusion(...)", 62 * MS, 28 * MS),
        (D0, T.OPS, "%fusion.1 = f32[8]{0} fusion(...)", 95 * MS, 25 * MS),
    ]


def test_host_self_seconds_go_to_the_innermost_span():
    by = trace_host.self_seconds(T.Trace(host_events()))
    ms = {k: round(v * 1e3, 9) for k, v in by.items()}
    assert ms == {
        "bench.engine_step": 3, "bench.stamp": 2, "serving.step": 6,
        "serving.step.admit": 2, "serving.prefill": 3,
        "serving.prefill.fetch": 3, "serving.decode_chunk.build": 2,
        "serving.decode_chunk.dispatch": 1, "serving.compile": 2,
        "serving.decode_chunk.fetch": 35, "serving.decode_chunk.emit": 3}


def test_host_ms_per_step_leaves_out_the_waiting(capsys):
    spec = Manifest().layer_metric("sched.host_ms_per_step")
    ctx = {"trace": T.Trace(host_events()),
           "counters": {"traced_decode_steps": 4}}
    # all serving.* but the two *.fetch: 6 + 2 + 3 + 2 + 1 + 2 + 3 = 19 ms
    assert trace_host.read(spec["params"], ctx) == pytest.approx(19 / 4)
    out = capsys.readouterr().out
    # 57 ms under serving.*, 3 ms of bench.engine_step beside them
    assert "cover 95.00% of bench.engine_step" in out
    # and the idle gaps go to the leaves, not to bench.engine_step
    idle = ctx["trace"].idle_by_host_span()
    assert idle == pytest.approx({
        "serving.step": 0.001 + 0.004, "bench.engine_step": 0.001 + 0.001,
        "bench.stamp": 0.002})


def test_host_reader_reads_nothing_without_the_programs_spans(capsys):
    """The parent commit's trace: ``bench.*`` spans only."""
    evs = [e for e in host_events() if not e[2].startswith("serving.")]
    spec = Manifest().layer_metric("sched.host_ms_per_step")
    ctx = {"trace": T.Trace(evs), "counters": {"traced_decode_steps": 4}}
    assert trace_host.read(spec["params"], ctx) is None
    assert "NOTHING" in capsys.readouterr().out
    assert trace_host.read(spec["params"], {"trace": None,
                                            "counters": {}}) is None


# -- trace_scope ---------------------------------------------------------

STEP = [  # (name, start, end, op_name) of one 40 ms train step from 0
    ("%fusion.1 = bf16[8]{0} fusion(...)", 0, 10,
     "jit(step)/jvp()/while/body/closed_call/attn.proj/dot_general"),
    ("%while.1 = (s32[], bf16[8]{0}) while(...)", 10, 30,
     "jit(step)/transpose(jvp())/while"),
    ("%flash_fwd.2 = bf16[8]{0} custom-call(...)", 12, 20,
     "jit(step)/transpose(jvp())/while/body/closed_call/"
     "rematted_computation/attn.kernel/pallas_call"),
    ("%fusion.2 = bf16[8]{0} fusion(...)", 20, 28,
     "jit(step)/transpose(jvp())/while/body/closed_call/"
     "rematted_computation/moe.experts/ecd,edf->ecf/dot_general"),
    ("%fusion.3 = f32[8]{0} fusion(...)", 30, 36,
     "jit(step)/transpose(jvp(ce))/ce/while/body/closed_call/mul"),
    ("%copy.1 = bf16[8]{0} copy(...)", 36, 38, ""),
    ("%fusion.4 = bf16[8]{0} fusion(...)", 38, 40,
     "jit(step)/optim/mul;jit(step)/optim/add"),
]


def scope_events():
    """A step cut by the profiler's start, two whole steps, one cut by
    its stop; the window is the device's own extent, 0-105."""
    mods, ops = [], []
    for at, length in ((0, 5), (10, 40), (60, 40), (102, 3)):
        mods.append((D0, T.MODULES, "jit_step(7)", at * MS, length * MS))
        if length == 40:
            ops += [[D0, n, (at + s) * MS, (e - s) * MS, op]
                    for n, s, e, op in STEP]
        else:
            ops.append([D0, "%fusion.9 = bf16[8]{0} fusion(...)", at * MS,
                        length * MS, "jit(step)/jvp()/mlp/dot_general"])
    mods.append((D0, T.MODULES, "jit__pf(3)", 52 * MS, 4 * MS))
    ops.append([D0, "%fusion.5 = bf16[8]{0} fusion(...)", 52 * MS, 4 * MS,
                "jit(_pf)/head/dot_general"])
    events = mods + [(p, T.OPS, n, s, d) for p, n, s, d, _ in ops]
    return T.Trace(events), ops


def test_scope_of_takes_the_innermost_component():
    assert trace_scope.scope_of(STEP[0][3]) == "attn.proj"
    assert trace_scope.scope_of(STEP[2][3]) == "attn.kernel"
    assert trace_scope.scope_of(STEP[4][3]) == "ce"
    assert trace_scope.scope_of(STEP[6][3]) == "optim"
    assert trace_scope.scope_of("jit(step)/jvp()/while/body/add") \
        == trace_scope.UNSCOPED
    assert trace_scope.scope_of("") == trace_scope.UNSCOPED
    # a scope inside a scope: the inner one; a look-alike is no scope
    assert trace_scope.scope_of("jit(f)/mlp/attn.proj/mul") == "attn.proj"
    assert trace_scope.scope_of("jit(f)/mlp_extra/mul") \
        == trace_scope.UNSCOPED


def test_split_of_whole_steps_adds_up_to_the_step():
    trace, ops = scope_events()
    got = trace_scope.split(trace, ops, {"program": "^jit_step",
                                         "per": "events"})
    assert got["events"] == 2              # the two cut steps are left out
    ms = {k: round(v * 1e3, 9) for k, v in got["by"].items()}
    # the loop's own 4 ms and the copy's 2 carry no scope
    assert ms == {"attn.proj": 20, "attn.kernel": 16, "moe.experts": 16,
                  "ce": 12, "optim": 4, trace_scope.UNSCOPED: 12}
    assert sum(ms.values()) == 2 * 40
    # and it agrees with what trace_ops reads for the program
    secs, n = trace.matching(T.MODULES, "^jit_step", whole=True)
    assert (secs, n) == (pytest.approx(0.080), 2)
    assert got["top_unscoped"][0][0].startswith("%while.1")


def test_scope_metrics_read_from_their_files(capsys):
    trace, ops = scope_events()
    man = Manifest()
    ctx = {"trace": trace, "counters": {}}
    want = {"prog.train.attn_ms": 18, "prog.train.moe_ms": 8,
            "prog.train.ce_ms": 6, "prog.train.optim_ms": 2,
            "prog.train.unscoped_ms": 6, "prog.train.recompute_ms": 16}
    for name, ms in want.items():
        spec = man.layer_metric(name)
        assert spec["reader"] == "trace_scope"
        assert trace_scope.read(spec["params"], ctx, ops) \
            == pytest.approx(ms), name
    # the summands are the whole step (prog.train_step_ms, 40 ms)
    assert sum(v for k, v in want.items()
               if k != "prog.train.recompute_ms") == 40
    assert "seconds by scope" in capsys.readouterr().out


def test_split_over_a_counter_takes_the_cut_events_too():
    trace, ops = scope_events()
    params = {"program": "^jit_step", "per": "counter",
              "counter": "steps", "sum": ["mlp"], "scale": 1000.0}
    ctx = {"trace": trace, "counters": {"steps": 4}}
    # the cut steps' 5 + 3 ms of mlp, over the counter
    assert trace_scope.read(params, ctx, ops) == pytest.approx(8 / 4)
    assert trace_scope.read({**params, "counter": "absent"}, ctx, ops) \
        is None


def test_scope_reader_reads_nothing_without_names_or_scopes(capsys):
    """The parent commit (no scope in any name) and a trace whose op
    events hold no name at all: left out, said so, never raised."""
    trace, ops = scope_events()
    spec = Manifest().layer_metric("prog.train.moe_ms")
    ctx = {"trace": trace, "counters": {}}
    bare = [o[:4] + ["jit(step)/jvp()/dot_general"] for o in ops]
    assert trace_scope.read(spec["params"], ctx, bare) is None
    # (said once for a run's trace and program, not once a metric)
    assert trace_scope.read(spec["params"], ctx,
                            [o[:4] + [""] for o in ops]) is None
    out = capsys.readouterr().out
    assert out.count("NOTHING") == 1 and "0 scopes found" in out
    # a program the trace does not hold
    assert trace_scope.read({**spec["params"], "program": "^jit_absent"},
                            ctx, ops) is None
    assert "0 events of the program" in capsys.readouterr().out
    assert trace_scope.read(spec["params"], {"trace": None}) is None


# -- the .xplane.pb as trace_scope reads it -------------------------------

def _vi(n):
    out = b""
    while True:
        n, b = n >> 7, n & 0x7F
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _f(no, val):
    """One protobuf field: a varint for an int, else length-delimited."""
    if isinstance(val, int):
        return _vi(no << 3) + _vi(val)
    val = val.encode() if isinstance(val, str) else val
    return _vi(no << 3 | 2) + _vi(len(val)) + val


def _entry(key, msg):
    return _f(1, key) + _f(2, msg)


def _xspace():
    """A device plane with two ops (one named by a string statistic, one
    by a reference to a statistic's name, one event of a third with no
    name), beside a modules line, another statistic, a fixed64 field and
    a host plane with a line of the ops line's name."""
    stat_meta = (_f(5, _entry(1, _f(1, 1) + _f(2, "tf_op")))
                 + _f(5, _entry(2, _f(1, 2) + _f(2, "flops")))
                 + _f(5, _entry(9, _f(1, 9) + _f(2, "jit(f)/mlp/dot:"))))
    double = _vi(2 << 3 | 1) + b"\x00" * 8             # XStat.double_value
    event_meta = (
        _f(4, _entry(11, _f(1, 11) + _f(2, "%fusion.1 = f32[8]{0} fusion()")
                     + _f(5, _f(1, 2) + _f(3, 77))
                     + _f(5, _f(1, 1) + _f(5, "jit(f)/optim/mul:"))))
        + _f(4, _entry(12, _f(1, 12) + _f(2, "%fusion.2 = f32[8]{0} fusion()")
                       + _f(5, _f(1, 2) + double)
                       + _f(5, _f(1, 1) + _f(7, 9))))
        + _f(4, _entry(13, _f(1, 13) + _f(2, "%copy.3 = f32[8]{0} copy()"))))
    ops = (_f(2, T.OPS) + _f(3, 1000)
           + _f(4, _f(1, 11) + _f(2, 5_000) + _f(3, 2_000_000))
           + _f(4, _f(1, 12) + _f(2, 3_000_000) + _f(3, 500))
           + _f(4, _f(1, 13) + _f(3, 250)))           # offset 0 is left out
    mods = _f(2, T.MODULES) + _f(4, _f(1, 11) + _f(3, 9))
    device = _f(1, 7) + _f(2, D0) + _f(3, mods) + _f(3, ops) \
        + event_meta + stat_meta
    cpu = _f(2, HOST) + _f(3, _f(2, T.OPS) + _f(4, _f(1, 11) + _f(3, 9))) \
        + event_meta + stat_meta
    return _f(1, cpu) + _f(1, device) + _f(4, "a-hostname")


def test_ops_and_their_names_come_from_the_xplane_file(tmp_path):
    path = tmp_path / "run.xplane.pb"
    path.write_bytes(_xspace())
    assert trace_scope.load_ops(str(path)) == [
        [D0, "%fusion.1 = f32[8]{0} fusion()", 1005.0, 2000.0,
         "jit(f)/optim/mul:"],
        [D0, "%fusion.2 = f32[8]{0} fusion()", 4000.0, 0.5,
         "jit(f)/mlp/dot:"],
        [D0, "%copy.3 = f32[8]{0} copy()", 1000.0, 0.25, ""]]
    assert trace_scope.scope_of("jit(f)/optim:") == "optim"
    # the newest trace under a root, as the reader finds the run's
    older = tmp_path / "a" / "b" / "old.xplane.pb"
    older.parent.mkdir(parents=True)
    older.write_bytes(b"")
    os.utime(older, (1, 1))
    assert trace_scope.newest_xplane(str(tmp_path)) == str(path)
    assert trace_scope.newest_xplane(str(tmp_path / "a" / "b")) \
        == str(older)
    assert trace_scope.newest_xplane(str(tmp_path / "absent")) is None


# -- a capture from the chip ----------------------------------------------

@pytest.fixture(scope="module")
def capture():
    path = os.path.join(BENCH_DIR, "testdata",
                        "chip_capture_named.json.gz")
    assert os.path.getsize(path) < 200 * 1024
    with gzip.open(path, "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("part,program,step_metric", [
    ("serve", "^jit_decode_chunk", None),
    ("train", "^jit_step", "prog.train_step_ms")])
def test_a_named_capture_from_the_chip(capture, part, program, step_metric):
    """A scheduler step of ``decode-sat`` and a train step of
    ``train-4k`` from the TPU v5e (PR 24), with the numbers the readers
    gave when the capture was made, and what ISSUE 24 accepts by."""
    doc = capture[part]
    ops, want = doc["ops"], doc["expect"]
    trace = T.Trace(doc["events"] + [[p, T.OPS, n, s, d]
                                     for p, n, s, d, _ in ops])
    ctx = {"trace": trace, "counters": doc["counters"]}
    man = Manifest()
    assert trace.window_s == pytest.approx(want["window_s"], rel=1e-9)
    for name, value in want["metrics"].items():
        spec = man.layer_metric(name)
        got = trace_host.read(spec["params"], ctx) \
            if spec["reader"] == "trace_host" \
            else trace_scope.read(spec["params"], ctx, ops)
        assert got == pytest.approx(value, rel=1e-9), name
    found = {}
    for line, pattern, secs, n, whole in want["matching"]:
        found[pattern] = trace.matching(line, pattern, whole=whole)
        assert found[pattern] == (pytest.approx(secs, rel=1e-9),
                                  pytest.approx(n)), pattern
    # every kernel's instruction carries the name the kernel set
    for _p, name, *_ in ops:
        if 'custom_call_target="tpu_custom_call"' in name:
            assert name.startswith(("%flash_fwd.", "%flash_bwd_dq.",
                                    "%flash_bwd_dkv.",
                                    "%paged_decode_attn.")), name
    # the scopes and (unscoped) add up to the program's time
    by = trace_scope.split(trace, ops, {
        "program": program, "per": "events" if step_metric else "counter"})
    assert by["by"] == pytest.approx(want["by_scope"], rel=1e-9)
    assert sum(by["by"].values()) == pytest.approx(
        found[program][0], rel=1e-4)
    if part == "serve":
        # found by name, the paged kernel is the events its shape found
        assert found["^%paged_decode_attn"] == found[
            r"= bf16\[\d+,\d+,\d+,\d+\]\S* custom-call\(s32\["]
        assert found["^%paged_decode_attn"][1] == pytest.approx(4 * 16)
        # (the part starts in the last instants of the chunk before)
        assert found[program][1] == pytest.approx(1, abs=1e-3)
        # the device's idle instants go to leaves of the engine's spans
        idle = trace.idle_by_host_span()
        assert idle == pytest.approx(want["idle_by_host_span"], rel=1e-6)
        led = sorted(idle, key=idle.get, reverse=True)[:4]
        assert led == ["serving.decode_chunk.fetch", "serving.prefill.build",
                       "serving.prefill.fetch",
                       "serving.decode_chunk.build"]
        assert idle["bench.engine_step"] < 0.001 * sum(idle.values())
        own = trace_host.self_seconds(trace)
        inside = sum(v for k, v in own.items() if k.startswith("serving."))
        assert inside / (inside + own["bench.engine_step"]) > 0.95
    else:
        assert found["^%flash_"] \
            == found['custom_call_target="tpu_custom_call"']
        # attention's scopes hold at least the three flash kernels
        attn = want["metrics"]["prog.train.attn_ms"]
        assert attn >= found["^%flash_"][0] * 1e3
        step = 1e3 * found[program][0] / found[program][1]
        assert step == pytest.approx(725.3, rel=1e-3)
        summands = sum(v for k, v in want["metrics"].items()
                       if k != "prog.train.recompute_ms")
        others = sum(v for k, v in by["by"].items()
                     if k in ("embed", "head")) * 1e3
        assert summands + others == pytest.approx(step, rel=1e-4)
