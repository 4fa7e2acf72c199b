"""``BENCHMARK.json`` against the files it names and the contract's
limits, each configuration against what its architecture's module counts,
and the proof that the harness is driven by data: one more configuration,
mix, cell and per-layer metric, added as files and appended entries in a
copy, run (one more architecture: ``test_architectures.py``)."""
import importlib
import json
import os
import re
import shutil

import jax
import numpy as np
import pytest

from benchmark.harness import work
from benchmark.harness.manifest import ROOT, Manifest, build_config

MAN = Manifest()
DOC = MAN.doc
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in DOC["workloads"]]
CONFIGS = [c["name"] for c in DOC["configs"]]
LAYER = [m["name"] for m in DOC["per_layer"]]
# (the driver's check also refused ``norm_topk_prob`` in ``reduced``, a
# key about the experts a token takes: chiprun's pre-check, PR 23)
WIDTH = re.compile(r"(_dim|_rank)$|hidden_size|intermediate_size|head_dim|"
                   r"num_experts_per_tok|top_?k|expand")


def test_top_level_keys_and_limits():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= DOC["run_seconds"] <= 51
    assert len(json.dumps(DOC)) < 64 * 1024
    assert 1 <= len(DOC["workloads"]) <= 24 and len(DOC["configs"]) <= 24
    four = sum(w["chips"] == 4 for w in DOC["workloads"])
    assert four <= max(1, len(DOC["workloads"]) // 4)
    for word in DOC["command"]:
        assert not word.startswith("/") and ".." not in word
    for p in DOC["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in DOC[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            for key in ("why", "layer", "source"):
                if key in e and group != "end_to_end" \
                        and not (group == "per_layer" and key == "source"):
                    assert 1 <= len(e[key]) <= 200, (e["name"], key)
                    assert "\n" not in e[key] and "\t" not in e[key]
    assert len(names) == len(set(names))
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in DOC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in DOC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in DOC["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_and_reports(cell):
    w = MAN.cell(cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4)
    conf, mix = MAN.config(w["config"]), MAN.traffic(w["traffic"])
    importlib.import_module(f"benchmark.drivers.{mix['kind']}")
    assert ("serve" if mix["kind"].startswith("serve") else "train") in conf
    e2e = {m["name"] for m in MAN.metrics_of(cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert MAN.metrics_of(cell, "per_layer")


def test_every_pair_of_config_and_traffic_once():
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in DOC["workloads"]} == set(CONFIGS)


@pytest.mark.parametrize("name", LAYER)
def test_layer_metric_resolves_and_its_moves_is_reported(name):
    entry = next(m for m in DOC["per_layer"] if m["name"] == name)
    spec = MAN.layer_metric(name)
    for key in ("name", "unit", "layer", "moves"):
        assert spec[key] == entry[key]
    # which cells report it is BENCHMARK.json's to say, and its alone: a
    # new cell joins a metric by an appended name, with no file edited
    assert "workloads" not in spec
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    assert callable(reader.read)
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    assert entry["moves"] in e2e
    for cell in entry.get("workloads", CELLS):
        assert cell in CELLS
        assert cell in e2e[entry["moves"]].get("workloads", CELLS), \
            f"{name} moves {entry['moves']}, which {cell} does not report"
    same_layer = {m["layer"] for m in DOC["per_layer"]}
    assert entry["layer"] in same_layer and "\n" not in entry["layer"]


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_builds_and_counts(name):
    entry = next(c for c in DOC["configs"] if c["name"] == name)
    conf = MAN.config(name)
    assert entry["file"].startswith(tuple(DOC["paths"]))
    assert entry["source"] == conf["source"]
    assert entry["reduced"] == conf["reduced"] and len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
        assert conf["source_values"][key] != conf[key]
    assert set(conf["source_values"]) == set(entry["reduced"])
    block = "serve" if "serve" in conf else "train"
    family, cfg = build_config(conf, block)
    # the program's own parameter tree at these fields, by shape only
    tree = jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    counted = int(sum(np.prod(x.shape) for x in jax.tree.leaves(tree)))
    # ... against the count of the architecture the file names
    arch = MAN.architecture(conf)
    assert arch.__name__ == f"benchmark.architectures.{conf['architecture']}"
    assert counted == conf["param_count"] == arch.param_count(conf)
    assert conf["kv_bytes_per_token"] == arch.kv_bytes_per_token(conf)
    assert conf.get("state_bytes_per_slot", 0) \
        == arch.state_bytes_per_slot(conf)
    mf = conf["model_flops_per_token"]
    assert mf["flops"] == arch.model_flops_per_token(conf, mf["seq_len"])
    if "active_param_count" in conf:
        assert conf["active_param_count"] == arch.param_count(conf, True)
    assert work.head_dim(conf) == cfg.head_dim
    # the rehearsal's tiny widths build too
    from benchmark.run import rehearsal_of

    build_config(rehearsal_of(conf), block)


def test_peaks_table_is_keyed_by_device_kind():
    assert work.peaks("TPU v5 lite")["flops"] == 197e12
    assert work.peaks("TPU v5 lite")["hbm_bytes"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9")


def test_kernel_work_from_shapes():
    # one causal product at B=1, H=1, S=4, d=2 is 2*4*4*2/2 = 32 FLOPs
    assert work.flash_unit_flops(1, 1, 4, 2) == 32
    assert work.flash_flops({"fwd": 2, "bwd": 1}, batch=1, heads=1, seq=4,
                            head_dim=2) == (2 * 2 + 5) * 32
    c = {"hidden_size": 8, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 3}
    assert work.kv_bytes_per_token(c) == 2 * 3 * 2 * 2 * 2
    # q K^T and p V, two FLOPs a multiply-add, 3 layers x 4 heads of 2
    assert work.decode_attn_flops(10, c) == 4 * 10 * 3 * 4 * 2


def copy_of_the_data(root: str) -> str:
    bench = os.path.join(root, "benchmark")
    for sub in ("configs", "traffic", "layer_metrics", "architectures"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub),
                        os.path.join(bench, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    return bench


def test_every_data_file_is_registered():
    """Nothing is parked under the benchmark's directories: every mix,
    per-layer metric, configuration, reader, driver and architecture
    there is one that a registered cell runs."""
    mixes = {w["traffic"] for w in DOC["workloads"]}
    readers = {MAN.layer_metric(m)["reader"] for m in LAYER} | {"__init__"}
    drivers = {MAN.traffic(m)["kind"] for m in mixes} | {"__init__"}
    archs = {MAN.config(c)["architecture"] for c in CONFIGS} | {"__init__"}
    for kind, names, ext in (("traffic", mixes, ".json"),
                             ("layer_metrics", set(LAYER), ".json"),
                             ("configs", set(CONFIGS), ".json"),
                             ("readers", readers, ".py"),
                             ("drivers", drivers, ".py"),
                             ("architectures", archs, ".py")):
        files = {f[:-len(ext)]
                 for f in os.listdir(os.path.join(MAN.bench_dir, kind))
                 if f.endswith(ext)}
        assert files == names, kind


def one_more_of_each(root: str, architecture: tuple = None) -> tuple:
    """In a copy of the data under ``root``: a configuration, a mix, a
    cell, a ``counter`` metric over an attribute of ``engine.stats`` that
    no file lists, and the cell's name appended to ``serve_tok_s`` and to
    an existing per-layer metric; with ``architecture`` (name, source)
    also that module, named by the configuration. Returns (the cell's
    name, the files that were there with their modification times)."""
    bench = copy_of_the_data(root)
    before = {os.path.join(b, f): os.path.getmtime(os.path.join(b, f))
              for b, _, fs in os.walk(bench) for f in fs}

    def add(kind, name, doc):
        with open(os.path.join(bench, kind, name + ".json"), "w") as f:
            json.dump(doc, f)

    conf = MAN.config("mistral-7b-v0.3")
    conf["rehearse"]["override"]["num_hidden_layers"] = 3
    # (float32 on the CPU agrees to 3e-7; at the rehearsal's width scores
    # spread little, and a position fault moves the logits by ~1e-3)
    conf["rehearse"]["serve"]["check"].update(tolerance=1e-5,
                                              rms_tolerance=1e-5)
    if architecture:
        conf["architecture"], source = architecture
        with open(os.path.join(bench, "architectures",
                               conf["architecture"] + ".py"), "w") as f:
            f.write(source)
    add("configs", "another-dense", conf)
    mix = MAN.traffic("decode-sat")
    mix["rehearse"]["output"] = {"median": 8, "sigma": 0.3, "min": 4,
                                 "max": 12}
    add("traffic", "short-answers", mix)
    cell = {"name": "another-dense.short-answers", "config": "another-dense",
            "traffic": "short-answers", "chips": 1, "why": "a test's cell"}
    metric = {"name": "sched.shed", "unit": "requests", "layer": "scheduler",
              "moves": "serve_tok_s", "reader": "counter",
              "params": {"counter": "engine.shed"}}
    add("layer_metrics", metric["name"], metric)

    doc = json.loads(json.dumps(DOC))
    doc["configs"].append({**DOC["configs"][0], "name": "another-dense",
                           "file": "benchmark/configs/another-dense.json"})
    doc["workloads"].append(cell)
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m["name"] in ("serve_tok_s", "sched.occupancy_pct"):
            m["workloads"] = m["workloads"] + [cell["name"]]
    doc["per_layer"].append({k: metric[k] for k in (
        "name", "unit", "layer", "moves")}
        | {"better": "lower", "source": "program_counter",
           "workloads": [cell["name"]]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return cell["name"], before


def rehearsed_both_ways(root: str, cell: str, before: dict) -> list:
    """The copy's new cell rehearsed with ``--trace 1`` and ``0``, and no
    file that was there edited. Returns the traced run's lines."""
    from test_traffic import check_last_line, rehearse

    lines, last = rehearse(cell, 1, root=root)
    check_last_line(last, Manifest(root), cell, 1)
    # the new counter metric, and the existing one the cell joined
    assert last["metrics"]["rehearse.sched.shed"]["value"] == 0.0
    assert 0 < last["metrics"]["rehearse.sched.occupancy_pct"]["value"] <= 100
    _, last = rehearse(cell, 0, root=root)
    assert set(last["metrics"]) == {"rehearse.serve_tok_s",
                                    "rehearse.setup_s"}
    assert last["correct"] is True
    after = {p: os.path.getmtime(p) for p in before}
    assert after == before, "a file that was there was edited"
    return lines


def test_one_more_of_each_is_files_and_appended_entries(tmp_path):
    """A configuration, a traffic mix, a cell and a per-layer metric added
    to a copy of the benchmark, and an existing per-layer metric joined,
    without editing any file that was there (only appending to
    BENCHMARK.json), then rehearsed."""
    cell, before = one_more_of_each(str(tmp_path))
    lines = rehearsed_both_ways(str(tmp_path), cell, before)
    assert "(architecture dense_decoder: " in lines[0]
