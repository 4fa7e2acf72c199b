"""What PR 35 added to the benchmark, found BY NAME and not by position,
so that the next PR that appends breaks nothing here: five per-layer
metrics (their files, readers, cells and ``moves``), two readers, no cell
and no configuration; every entry the parent's ``BENCHMARK.json`` had is
there unchanged and in order (a list of ``workloads`` may have grown at
its end), and no file the parent had under the benchmark's paths differs.
This replaces the two cases of ``test_zaya_cell.py`` that hold PR 33's
entries to be ``per_layer``'s last (``tests/conftest.py``)."""
import hashlib
import json
import os
import subprocess

import pytest

from benchmark.harness.manifest import ROOT, Manifest, plugin

MAN = Manifest()
DOC = MAN.doc
PARENT = "e2eaf61"          # PR 34
DECODE_SAT = ["mistral-7b-v0.3.decode-sat", "falcon-h1-34b.decode-sat"]
SERVING = DECODE_SAT + ["phi-4-mini-flash.reason-sat", "zaya1-8b.reason-sat"]
# name: (unit, better, source, layer, reader, the cells it began with)
NEW = {
    "prog.prefill_ms_per_step": ("ms", "lower", "device_trace", "programs",
                                 "trace_ops", DECODE_SAT),
    "prog.prefill_tok_s": ("tokens/s", "higher", "program_span", "programs",
                           "trace_spans", DECODE_SAT),
    "sched.prefill_fill_pct": ("%", "higher", "program_counter", "scheduler",
                               "counter", SERVING),
    "dev.launch_gap_ms_per_step": ("ms", "lower", "device_trace", "device",
                                   "trace_gaps", SERVING),
    "sched.return_wait_ms_per_step": ("ms", "lower", "program_span",
                                      "scheduler", "trace_gaps", SERVING),
}
# per_layer as PR 34 left it, in its order
BEFORE = [
    "sched.occupancy_pct", "prog.train_step_ms", "dev.idle_pct.serve_sat",
    "dev.idle_pct.train", "prog.decode_chunk_step_ms",
    "kern.paged_attn_named_roofline", "kern.flash_named_roofline",
    "sched.host_ms_per_step", "prog.decode.dense_ms",
    "prog.decode.kv_write_ms", "prog.decode.unscoped_ms",
    "prog.train.attn_ms", "prog.train.moe_ms", "prog.train.ce_ms",
    "prog.train.optim_ms", "prog.train.unscoped_ms",
    "prog.train.recompute_ms", "prog.mfu.train", "prog.mfu.serve",
    "prog.decode.ssm_ms", "kern.ssm_update_roofline",
    "prog.decode.shared_attn_ms", "prog.decode.window_ms",
    "kern.window_attn_roofline", "prog.decode.moe_ms", "prog.decode.cca_ms",
    "kern.moe_experts_roofline"]


def entry(name):
    return next(m for m in DOC["per_layer"] if m["name"] == name)


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry_its_file_its_reader_and_its_cells(name):
    unit, better, source, layer, reader, cells = NEW[name]
    e = entry(name)
    assert {k: e[k] for k in ("unit", "better", "source", "layer", "moves")} \
        == {"unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "serve_tok_s"}
    assert e["workloads"][:len(cells)] == cells       # a later cell appends
    spec = MAN.layer_metric(name)
    assert spec["reader"] == reader and len(spec["what"]) > 60
    assert callable(plugin("readers", reader).read)
    served = next(m for m in DOC["end_to_end"] if m["name"] == "serve_tok_s")
    assert set(e["workloads"]) <= set(served["workloads"])


def test_the_entries_before_are_there_in_their_order():
    names = [m["name"] for m in DOC["per_layer"]]
    assert [n for n in names if n in BEFORE] == BEFORE
    at = [names.index(n) for n in NEW]
    assert at == sorted(at) and min(at) > max(names.index(n) for n in BEFORE)
    # PR 35 brought no cell and no configuration: the five PR 33 left
    assert [w["name"] for w in DOC["workloads"]][:5] == [
        "deepseek-moe-16b.train-4k"] + DECODE_SAT + SERVING[2:]
    assert [c["name"] for c in DOC["configs"]][:5] == [
        "mistral-7b-v0.3", "deepseek-moe-16b", "falcon-h1-34b",
        "phi-4-mini-flash", "zaya1-8b"]


def test_what_the_five_read():
    p = {n: MAN.layer_metric(n)["params"] for n in NEW}
    assert p["prog.prefill_ms_per_step"] == {
        "line": "XLA Modules", "pattern": "^jit__pf", "per": "counter",
        "counter": "traced_decode_steps", "scale": 1000.0}
    assert p["sched.prefill_fill_pct"] == {
        "counter": "engine.tokens_prefilled",
        "over": "engine.prefill_grid_tokens", "scale": 100.0}
    # the program's side of the two names the benchmark reads
    from paddle_tpu.inference.engine import EngineStats
    assert {"tokens_prefilled", "prefill_grid_tokens"} <= set(vars(
        EngineStats()))
    assert p["prog.prefill_tok_s"]["span"] == "serving.prefill.dispatch"
    # not a second share of a peak beside prog.mfu.serve
    assert not any("mfu" in n or "roofline" in n for n in NEW)


def test_a_parent_without_the_counter_leaves_the_ratio_out():
    """The driver lays these files over the parent's checkout, whose
    ``EngineStats`` has no ``prefill_grid_tokens``: nothing, no raise."""
    counter = plugin("readers", "counter")
    spec = MAN.layer_metric("sched.prefill_fill_pct")["params"]
    assert counter.read(spec, {"counters": {
        "engine.tokens_prefilled": 900}}) is None
    assert counter.read(spec, {"counters": {
        "engine.tokens_prefilled": 900,
        "engine.prefill_grid_tokens": 1200}}) == pytest.approx(75.0)


# -- against the parent's tree ------------------------------------------------

def _git(*args) -> bytes:
    try:
        return subprocess.run(["git", *args], cwd=ROOT, check=True,
                              capture_output=True).stdout
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here to read the parent's tree from")


def test_every_entry_the_parent_had_is_there_unchanged_and_in_order():
    was = json.loads(_git("show", f"{PARENT}:BENCHMARK.json"))
    assert {k: DOC[k] for k in ("command", "paths", "run_seconds")} \
        == {k: was[k] for k in ("command", "paths", "run_seconds")}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        now = {e["name"]: e for e in DOC[group]}
        names = [e["name"] for e in was[group]]
        assert [n for n in now if n in names] == names, group
        for old in was[group]:
            new = dict(now[old["name"]])
            if "workloads" in old:      # a later cell joins at the end
                n = len(old["workloads"])
                assert new["workloads"][:n] == old["workloads"]
                new["workloads"] = new["workloads"][:n]
            assert new == old, old["name"]
    added = [e["name"] for e in DOC["per_layer"]
             if e["name"] not in {m["name"] for m in was["per_layer"]}]
    assert added[:5] == list(NEW)


def test_no_file_the_parent_had_differs():
    listed = _git("ls-tree", "-r", "--name-only", PARENT, "benchmark",
                  "tests/benchmark").decode().split()
    assert len(listed) > 70
    for path in listed:
        with open(os.path.join(ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).digest() == hashlib.sha256(
                _git("show", f"{PARENT}:{path}")).digest(), path
