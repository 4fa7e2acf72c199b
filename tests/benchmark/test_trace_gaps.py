"""``readers/trace_gaps`` (PR 35) on hand-written events with known
answers: the device's idle time inside the host's ``*.fetch`` spans split
by what ended each gap (``queued``: the device resumed by itself while
the host still waited; ``return``: only after the host had come back),
the two against ``Trace.idle_by_host_span``'s ``.fetch`` rows, and the
closure line of a traced decode step."""
import pytest

from benchmark.harness import trace_reduce as T
from benchmark.harness.manifest import Manifest
from benchmark.readers import trace_gaps

MS = 1e6            # hand-written events are in milliseconds
HOST, D0 = "/host:CPU", "/device:TPU:0"
CHUNK_FETCH, PF_FETCH = "serving.decode_chunk.fetch", "serving.prefill.fetch"
MAN = Manifest()
QUEUED = MAN.layer_metric("dev.launch_gap_ms_per_step")
RETURN = MAN.layer_metric("sched.return_wait_ms_per_step")


def host(name, start, end):
    return (HOST, "python", name, start * MS, (end - start) * MS)


def op(start, end, name="%fusion.1 = f32[8]{0} fusion(...)"):
    return (D0, T.OPS, name, start * MS, (end - start) * MS)


def module(name, start, end):
    return (D0, T.MODULES, name, start * MS, (end - start) * MS)


def trace_of(*events):
    return T.Trace([host(T.WINDOW_SPAN, 0, 100), *events])


def fetch_rows(trace):
    return sum(v for k, v in trace.idle_by_host_span().items()
               if k.endswith(".fetch"))


# one engine step: a prefill, the join of its first tokens and a chunk
# run from one queue while the host waits for the chunk; the next step's
# chunk starts only after the host has come back and dispatched it
ONE_STEP = [
    host("serving.step", 5, 64),
    host("serving.prefill.dispatch", 9, 10),
    host("serving.decode_chunk.dispatch", 18, 20),
    host(CHUNK_FETCH, 20, 60),
    host("serving.decode_chunk.emit", 60, 63),
    op(0, 5), op(10, 25), op(27, 28), op(30, 55), op(65, 100),
    module("jit_decode_chunk(1)", -4, 5), module("jit__pf(7)", 10, 25),
    module("jit__join_first(3)", 27, 28),
    module("jit_decode_chunk(1)", 30, 55),
    module("jit_decode_chunk(1)", 65, 104)]


def test_prefill_join_chunk_under_one_fetch():
    """25-27 and 28-30 end inside the fetch (queued: 4 ms); 55-65 ends
    after it (return: its 5 ms inside the fetch; the 5 after it are the
    emit's and the next dispatch's); 5-10 touches no fetch."""
    trace = trace_of(*ONE_STEP)
    got = trace_gaps.split(trace, ".fetch")
    assert got["queued"] == pytest.approx(4e-3)
    assert got["return"] == pytest.approx(5e-3)
    assert got["queued"] + got["return"] == pytest.approx(fetch_rows(trace))
    # both queued gaps lie between two programs, not inside one
    assert got["queued.in_program"] == 0.0


@pytest.mark.parametrize("events,queued,ret", [
    # a gap that begins in a prefill's fetch and ends in the chunk's
    # fetch after it: queued, both parts (25-30 and 32-35), not the emit
    ([host(PF_FETCH, 20, 30), host("serving.prefill.emit", 30, 32),
      host(CHUNK_FETCH, 32, 60), op(0, 25), op(35, 100)], 8, 0),
    # a gap that straddles the span's start: its seconds inside alone
    ([host("serving.decode_chunk.build", 10, 20), host(CHUNK_FETCH, 20, 60),
      op(0, 15), op(25, 100)], 5, 0),
    # a gap that lasts exactly to the span's end did not end inside it
    ([host(CHUNK_FETCH, 20, 60), op(0, 50), op(60, 100)], 0, 10),
    # between two operations of ONE program: queued all the same
    ([host(CHUNK_FETCH, 20, 60), op(0, 40), op(41, 100),
      module("jit_decode_chunk(1)", 10, 100)], 1, 0),
    # idle, and none of it while the host waits
    ([host("serving.step.admit", 20, 60), op(0, 30), op(40, 100)], 0, 0),
    # no gap at all
    ([host(CHUNK_FETCH, 20, 60), op(-1, 101)], 0, 0),
], ids=["two_fetches", "straddles_the_start", "lasts_to_the_end",
        "inside_one_program", "no_fetch", "no_gap"])
def test_a_gap_at_a_time(events, queued, ret):
    trace = trace_of(*events)
    got = trace_gaps.split(trace, ".fetch")
    assert got["queued"] == pytest.approx(queued * 1e-3)
    assert got["return"] == pytest.approx(ret * 1e-3)
    assert got["queued"] + got["return"] == pytest.approx(fetch_rows(trace))
    assert got["queued.in_program"] == pytest.approx(
        1e-3 if any(e[1] == T.MODULES for e in events) else 0.0)


def test_the_two_metrics_over_the_traced_steps(capsys):
    trace_gaps._SAID.clear()
    ctx = {"trace": trace_of(*ONE_STEP),
           "counters": {"traced_decode_steps": 4}}
    assert trace_gaps.read(QUEUED["params"], ctx) == pytest.approx(4 / 4)
    assert trace_gaps.read(RETURN["params"], ctx) == pytest.approx(5 / 4)
    out = capsys.readouterr().out
    # said once a run, by whichever metric reads first
    assert out.count("trace_gaps: idle inside *.fetch spans") == 1
    assert "together 0.009000 = the idle_gaps rows' 0.009000" in out
    assert out.count("trace_gaps: closure") == 1


def test_no_gap_reads_zero_not_nothing():
    """A listed metric that a run leaves out reads ``null`` in the
    ledger, which is read as "an accepted PR did away with it"."""
    trace_gaps._SAID.clear()
    ctx = {"trace": trace_of(host(CHUNK_FETCH, 20, 60), op(-1, 101)),
           "counters": {"traced_decode_steps": 4}}
    for spec in (QUEUED, RETURN):
        value = trace_gaps.read(spec["params"], ctx)
        assert value == 0.0 and value is not None


@pytest.mark.parametrize("ctx", [
    {"trace": None, "counters": {"traced_decode_steps": 4}},
    {"trace": trace_of(*ONE_STEP), "counters": {}},
    {"trace": trace_of(*ONE_STEP), "counters": {"traced_decode_steps": 0}},
    {"trace": T.Trace([host(CHUNK_FETCH, 20, 60)]),
     "counters": {"traced_decode_steps": 4}},
], ids=["rehearsal", "no_counter", "no_steps", "no_device"])
def test_nothing_to_divide_or_to_read_reads_nothing(ctx):
    assert trace_gaps.read(QUEUED["params"], ctx) is None


def test_the_closure_line_sums_to_the_wall(capsys):
    """100 ms over 4 steps: the chunks 5 + 25 + 35 ms inside the window,
    the prefill 15, the join 1, idle 5 + 2 + 2 + 10; nothing is left."""
    trace = trace_of(*ONE_STEP)
    c = trace_gaps.closure(trace, QUEUED["params"]["closure"], 4)
    assert c == pytest.approx({
        "wall": 25.0, "decode": 65 / 4, "prefill": 15 / 4, "other": 1 / 4,
        "idle": 19 / 4, "residue": 0.0}, abs=1e-9)
    assert list(c) == ["wall", "decode", "prefill", "other", "idle",
                       "residue"]
    # a program nobody named is in ``other``; time no part holds (here a
    # module's event longer than its operations) shows as the residue
    trace = trace_of(*ONE_STEP[:-1], module("jit_something_else(2)", 60, 104))
    c = trace_gaps.closure(trace, QUEUED["params"]["closure"], 4)
    assert c["other"] == pytest.approx(41 / 4)
    assert c["decode"] == pytest.approx(30 / 4)
    assert c["residue"] == pytest.approx(-5 / 4)
    trace_gaps._SAID.clear()
    trace_gaps.read(QUEUED["params"], {
        "trace": trace, "counters": {"traced_decode_steps": 4}})
    assert "wall 25.0000, decode 7.5000, prefill 3.7500, other 10.2500, " \
        "idle 4.7500, residue -1.2500 (-5.00% of the wall)" \
        in capsys.readouterr().out


def test_both_files_name_one_reader_and_the_two_kinds():
    assert QUEUED["reader"] == RETURN["reader"] == "trace_gaps"
    assert (QUEUED["params"]["kind"], RETURN["params"]["kind"]) \
        == trace_gaps.KINDS
    for spec in (QUEUED, RETURN):
        rest = {k: v for k, v in spec["params"].items() if k != "kind"}
        assert rest == {"suffix": ".fetch", "scale": 1000.0,
                        "counter": "traced_decode_steps",
                        "closure": {"decode": "^jit_decode_chunk",
                                    "prefill": "^jit__pf"}}
