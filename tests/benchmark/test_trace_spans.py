"""``readers/trace_spans`` (PR 35) on hand-written events with known
answers: spans and programs paired first in, first out, what the slice's
edges leave out of both sums; and the attrs of a REAL ``.xplane.pb``,
written by ``jax.profiler`` on the CPU in a process of its own (after
``test_device_plugin``'s fake plugin the in-process profiler segfaults)."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import trace_reduce as T
from benchmark.harness.manifest import ROOT, Manifest
from benchmark.readers import trace_spans

MS = 1e6            # hand-written events are in milliseconds
HOST, D0 = "/host:CPU", "/device:TPU:0"
DISPATCH = "serving.prefill.dispatch"
SPEC = Manifest().layer_metric("prog.prefill_tok_s")


def span(start, end, name=DISPATCH, **attrs):
    return (name, start * MS, (end - start) * MS, attrs)


def module(name, start, end):
    return (D0, T.MODULES, name, start * MS, (end - start) * MS)


def trace_of(modules, window=(0, 100)):
    """The modules, a decode chunk before and after them that marks the
    device's first and last instant, and the window."""
    return T.Trace([
        (HOST, "python", T.WINDOW_SPAN, window[0] * MS,
         (window[1] - window[0]) * MS),
        module("jit_decode_chunk(1)", window[0] - 5, window[0] + 1),
        *modules,
        module("jit_decode_chunk(1)", window[1] - 2, window[1] + 5)])


def read(spans, modules, **kw):
    ctx = {"trace": trace_of(modules, **kw), "counters": {}}
    return trace_spans.read(SPEC["params"], ctx, spans=spans)


# two groups dispatched back to back (the second while the first runs),
# then a chunk; a third group a step later
BACK_TO_BACK = [span(10, 11, rows=2, width=256, tokens=300),
                span(11, 12, rows=1, width=512, tokens=500),
                span(40, 41, rows=2, width=256, tokens=400)]
THEIR_MODULES = [module("jit__pf(7)", 10.5, 14), module("jit__pf(9)", 14, 19),
                 module("jit__join_first(3)", 19, 19.1),
                 module("jit_decode_chunk(1)", 20, 38),
                 module("jit__pf(7)", 40.5, 44.5)]


def test_two_groups_back_to_back_pair_in_order(capsys):
    pairs = trace_spans.pair(trace_of(THEIR_MODULES), BACK_TO_BACK,
                             SPEC["params"])
    assert [(s[3]["tokens"], m.name, m.dur / MS) for s, m in pairs] == [
        (300, "jit__pf(7)", 3.5), (500, "jit__pf(9)", 5.0),
        (400, "jit__pf(7)", 4.0)]
    # 1,200 real tokens over 12.5 ms of the programs that prefilled them
    assert read(BACK_TO_BACK, THEIR_MODULES) == pytest.approx(1200 / 12.5e-3)
    out = capsys.readouterr().out
    assert "3 pairs" in out and "tokens 1200 over 0.012500 s" in out
    # the split by shape: calls, seconds, tokens, distinct programs (a
    # shape is one compiled program: 1 where the pairing is right)
    assert "'2x256': [2, 0.0075, 700.0, 1]" in out
    assert "'1x512': [1, 0.005, 500.0, 1]" in out
    assert "begins 0.500 ms after its span at the least" in out


@pytest.mark.parametrize("spans,modules,tokens,ms", [
    # a module whose dispatch preceded the slice: no span began before it
    (BACK_TO_BACK,
     [module("jit__pf(5)", 2, 6)] + THEIR_MODULES, 1200, 12.5),
    # ... and it does not take the first span, which began long after it
    ([span(30, 31, rows=1, width=128, tokens=100)],
     [module("jit__pf(5)", 2, 6), module("jit__pf(5)", 31, 33)], 100, 2.0),
    # a module the slice's end cut takes its span, and the pair is left
    # out of BOTH sums: the last group's 400 tokens are not counted
    (BACK_TO_BACK, THEIR_MODULES[:-1] + [module("jit__pf(7)", 96, 103)],
     800, 8.5),
    # a span dispatched as the slice ended: no module, in neither sum
    (BACK_TO_BACK + [span(97, 97.5, rows=1, width=128, tokens=90)],
     THEIR_MODULES, 1200, 12.5),
    # another program's modules are not a prefill's
    (BACK_TO_BACK[:1], [module("jit__join_first(3)", 10.2, 10.4),
                        module("jit__pf(7)", 10.5, 14)], 300, 3.5),
], ids=["dispatch_before_slice", "no_span_began_before", "cut_by_the_end",
        "span_without_module", "other_programs"])
def test_what_the_slices_edges_leave_out(spans, modules, tokens, ms):
    trace = trace_of(modules)
    pairs = trace_spans.pair(trace, spans, SPEC["params"])
    assert sum(s[3]["tokens"] for s, _ in pairs) == tokens
    assert sum(m.dur for _, m in pairs) / MS == pytest.approx(ms)
    assert all(trace.whole(m) and s[1] < m.start for s, m in pairs)


def test_a_device_clock_laid_early_on_the_hosts_keeps_its_pairs(capsys):
    """What the chip showed (PERF.md section 6, PR 35): in the first traced
    process on a machine every module reads 1.3 ms early against the
    host's spans, so a prefill 'starts' before its own dispatch began.
    With the metric's slack the pairs stay; the bare rule takes every
    module for the one dispatched before it, and the split shows it (a
    shape paired with two programs)."""
    early = [(p, line, n, start - 1.3 * MS, dur)
             for p, line, n, start, dur in THEIR_MODULES]
    assert SPEC["params"]["slack_ms"] == 5.0
    assert read(BACK_TO_BACK, early) == pytest.approx(1200 / 12.5e-3)
    out = capsys.readouterr().out
    assert "begins -0.800 ms after its span" in out
    assert "'2x256': [2, 0.0075, 700.0, 1]" in out
    bare = {**SPEC["params"], "slack_ms": 0.0}
    pairs = trace_spans.pair(trace_of(early), BACK_TO_BACK, bare)
    assert [(s[3]["width"], m.name) for s, m in pairs] == [
        (256, "jit__pf(9)"), (512, "jit__pf(7)")]


def test_a_module_cut_by_the_last_device_event_is_not_whole():
    """Without the chunk after it the last prefill touches the device's
    last instant: the profiler may have stopped inside it."""
    trace = T.Trace([
        (HOST, "python", T.WINDOW_SPAN, 0, 100 * MS),
        module("jit_decode_chunk(1)", -5, 1), module("jit__pf(7)", 10, 14),
        module("jit__pf(7)", 50, 60)])
    pairs = trace_spans.pair(trace, [
        span(9, 9.5, rows=1, width=128, tokens=100),
        span(49, 49.5, rows=1, width=128, tokens=120)], SPEC["params"])
    assert [s[3]["tokens"] for s, _ in pairs] == [100]


def test_spans_that_say_nothing_read_nothing(capsys):
    """The parent's program: the span is there, its attrs are not."""
    assert read([span(10, 11), span(11, 12)], THEIR_MODULES) is None
    assert "NOTHING among the 'serving.prefill.dispatch' spans (2 of " \
        "them) carries 'tokens'" in capsys.readouterr().out
    assert read([span(10, 11, name="serving.prefill", group=2, s_pad=256)],
                THEIR_MODULES) is None


def test_no_pair_and_no_trace_read_nothing():
    # every module cut or unmatched: no seconds to divide by
    assert read(BACK_TO_BACK[:1], [module("jit__pf(7)", 2, 6)]) is None
    assert trace_spans.read(SPEC["params"],
                            {"trace": None, "counters": {}}) is None


def test_the_metrics_file_names_the_span_the_attr_and_the_program():
    assert SPEC["reader"] == "trace_spans"
    assert SPEC["params"] == {"span": DISPATCH, "attr": "tokens",
                              "program": "^jit__pf", "slack_ms": 5.0}


# -- a real .xplane.pb -----------------------------------------------------

WRITER = """
import json, sys
import jax, jax.numpy as jnp
from paddle_tpu.monitor import trace
from benchmark.readers import trace_spans, trace_scope
f = jax.jit(lambda x: x @ x)
x = jnp.ones((64, 64)); f(x).block_until_ready()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level, opts.host_tracer_level = 0, 1
jax.profiler.start_trace(sys.argv[1], profiler_options=opts)
with trace.span("serving.prefill", group=3, s_pad=512):
    with trace.span("serving.prefill.dispatch", rows=4, width=512,
                    tokens=1100):
        f(x).block_until_ready()
with trace.step_span("serving.decode_chunk", 7, chunk=16, live=64):
    f(x).block_until_ready()
with trace.span("other.span", rows=1):
    pass
jax.profiler.stop_trace()
spans = trace_spans.load_spans(trace_scope.newest_xplane(sys.argv[1]))
print(json.dumps([[n, s, d, a] for n, s, d, a in spans]))
"""


def test_attrs_of_a_real_xplane_written_on_the_cpu(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    p = subprocess.run([sys.executable, "-c", WRITER, str(tmp_path)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    spans = json.loads(p.stdout.strip().splitlines()[-1])
    by = {n: (s, d, a) for n, s, d, a in spans}
    # the harness's prefixes alone, each with what it said it did
    assert set(by) == {"serving.prefill", DISPATCH, "serving.decode_chunk"}
    assert by["serving.prefill"][2] == {"group": 3, "s_pad": 512}
    assert by[DISPATCH][2] == {"rows": 4, "width": 512, "tokens": 1100}
    chunk = by["serving.decode_chunk"][2]
    assert (chunk["step_num"], chunk["chunk"], chunk["live"]) == (7, 16, 64)
    # on one clock: the dispatch lies inside its prefill
    (s0, d0, _), (s1, d1, _) = by["serving.prefill"], by[DISPATCH]
    assert s0 <= s1 and s1 + d1 <= s0 + d0 and d1 > 0
