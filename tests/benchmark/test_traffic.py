"""The traffic generator: the same seed gives the same inputs, another
seed gives other inputs and the same work, lengths stay inside the clips;
and each driver, rehearsed end to end on the CPU, prints a well-formed
last line with no device metric in it."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import traffic as T
from benchmark.harness.manifest import ROOT, Manifest

BIG = 3_000_000_019          # wider than 32 signed bits, as the driver's
MAN = Manifest()
MIXES = sorted(f[:-len(".json")]
               for f in os.listdir(os.path.join(MAN.bench_dir, "traffic")))


def _bytes(sched: dict) -> bytes:
    return b"".join(np.asarray(sched[k]).tobytes() for k in sorted(sched))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_bytes_other_seed_same_work(name):
    mix = MAN.traffic(name)
    if mix["kind"] == "serve_closed":
        def make(seed):
            sched = T.closed_schedule(mix, 64)
            ids = T.prompt_ids(sched["prompt_len"], 32768, seed)
            return {**sched, "ids": np.concatenate(ids)}
    else:
        def make(seed):
            return {"ids": next(T.train_batches(mix, 1000, seed))}
    a, b, c = make(BIG), make(BIG), make(BIG + 1)
    assert _bytes(a) == _bytes(b)
    assert a["ids"].tobytes() != c["ids"].tobytes()
    if mix["kind"] == "serve_closed":
        # how much there is to do, and what meets what, is the mix's own:
        # a window meets only the head of the list, so the order is too
        for k in ("prompt_len", "out_len"):
            assert a[k].tobytes() == c[k].tobytes()
        other = T.closed_schedule({**mix, "order_seed": 1}, 64)
        assert other["out_len"].tobytes() != a["out_len"].tobytes()
        assert sorted(other["out_len"][64:]) == sorted(a["out_len"][64:])


@pytest.mark.parametrize("name", [m for m in MIXES
                                  if "prompt" in MAN.traffic(m)])
def test_lengths_stay_inside_the_clips(name):
    mix = MAN.traffic(name)
    for key in ("prompt", "output"):
        x = T.lognormal_quantiles(mix[key], 500)
        assert x.min() >= mix[key]["min"] and x.max() <= mix[key]["max"]
        assert abs(np.median(x) - mix[key]["median"]) <= 0.05 * mix[key]["median"]
    closed = T.closed_schedule({**mix, "requests_per_client": 4}, 64)
    assert closed["out_len"].min() >= 2
    top = mix["prompt"]["max"] + mix["output"]["max"] - 2
    assert closed["prompt_len"].max() <= top


def test_the_first_round_is_met_part_way_through():
    """Phases spread the first round's requests over every age: what is
    left of them is of every length, not a wave that ends together."""
    mix = MAN.traffic("decode-sat")
    s = T.closed_schedule(mix, 64)
    full = np.sort(T.lognormal_quantiles(mix["output"], 64))
    left = np.sort(s["out_len"][:64])
    assert left.min() >= 2 and np.all(left <= full[-1])
    assert left.sum() == pytest.approx(full.sum() / 2, rel=0.1)
    # prompt + answer of a request is unchanged by where it is met
    assert (s["prompt_len"][:64] + s["out_len"][:64]).sum() == (
        T.lognormal_quantiles(mix["prompt"], 64).sum() + full.sum())


def test_prompt_ids_are_split_by_request():
    ids = T.prompt_ids(np.array([3, 5, 2]), 50, BIG)
    assert [len(x) for x in ids] == [3, 5, 2]
    assert all(0 <= t < 50 for x in ids for t in x)


def rehearse(cell: str, trace: int, root: str = None, timeout: int = 300):
    """One rehearsal in a process of its own (it sets its environment
    before JAX is imported). Returns (every line, the last line parsed)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}
    cmd = [sys.executable, "-m", "benchmark.run", "--workload", cell,
           "--seed", str(BIG), "--seconds", "2", "--trace", str(trace),
           "--rehearse"] + (["--root", root] if root else [])
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=timeout)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    return lines, json.loads(lines[-1])


def check_last_line(last: dict, manifest, cell: str, trace: int):
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu" and last["rehearsal"] is True
    group = "per_layer" if trace else "end_to_end"
    real = {m["name"] for g in ("end_to_end", "per_layer")
            for m in manifest.doc[g]}
    assert last["metrics"], "no metric at all"
    for name, m in last["metrics"].items():
        # no number of a CPU run goes by a device metric's name
        assert name.startswith("rehearse.") and name not in real
        assert name[len("rehearse."):] in {
            x["name"] for x in manifest.metrics_of(cell, group)}
        assert isinstance(m["value"], float) and m["unit"]
    if not trace:
        assert "rehearse.setup_s" in last["metrics"]
    assert "breakdown" not in last and "busy_s" not in last["device"]
    # what ``correct`` was decided by, each number beside its limit, last
    assert list(last)[-1] == "compared" and len(last["compared"]) >= 3
    for c in last["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


@pytest.mark.parametrize("cell,trace", [
    # (a rehearsal has no profiler, and a training cell's per-layer metrics
    # all come from the trace: it is rehearsed end to end only)
    (w["name"], int(MAN.traffic(w["traffic"])["kind"] != "train"))
    for w in MAN.doc["workloads"]])
def test_rehearsal_of_each_driver(cell, trace):
    lines, last = rehearse(cell, trace)
    check_last_line(last, MAN, cell, trace)
    conf = MAN.config(MAN.cell(cell)["config"])
    assert f"(architecture {conf['architecture']}: " in lines[0]
    window = [x for x in lines if "INSIDE THE WINDOW" in x]
    assert window and "compiled 0, from the cache 0" in window[0]


def test_off_the_chip_there_is_no_result():
    """Without --rehearse and without a TPU: a non-zero exit, no line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         MAN.doc["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert not p.stdout.strip()
