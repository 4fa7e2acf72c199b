"""The cell ``command-a-plus-05-2026.longdoc-sat`` and what came with it: the
file's stated counts against the architecture's module and the program's
own parameter tree and cache, the plain reference against the program
through the cache (``check.serve_check``: a prefill past the window and
the ring's wrap that ends inside a page, then decode steps that cross
into the next), the controls that show the seeded weights and the rule
that follows a near-tie hide no fault (each term dropped or bent in the
reference in turn, weights in int8 and fp8), the work functions on a
synthetic trace, the entries by name (what the two cases of
``test_axk1_cell.py`` that hold A.X K1's entries to be the lists' last
held of them), and that nothing the benchmark had was edited."""
import copy
import hashlib
import importlib.util
import inspect
import json
import os
import subprocess

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check
from benchmark.harness.manifest import ROOT, Manifest, build_config
from benchmark.run import rehearsal_of

MAN = Manifest()
NAME, CELL = "command-a-plus-05-2026", "command-a-plus-05-2026.longdoc-sat"
CONF = MAN.config(NAME)
ARCH = MAN.architecture(CONF)
CATALOG = os.environ.get("MODEL_CATALOG", "")  # architectures.jsonl
PARENT = "0cf37fa"
AXK1 = "ax-k1.reason-sat"


# -- the counts ------------------------------------------------------------

def test_the_counts_are_the_rows_arithmetic():
    """A layer outside its routed experts 344,461,312 (the four attention
    products 142.6 M, the four shared experts 201.3 M, the router over 128,
    the norm), an expert 50.3 M, 16 held; the 1/8 table once, tied: 4,733 M
    parameters, 8.82 GiB; the pool keeps one full layer's keys and values
    a token, the rings three window layers' 4,112 a slot."""
    c = CONF
    assert ARCH.layer_params(c) - 16 * ARCH.expert_params(c) == 344_461_312
    assert ARCH.expert_params(c) == 50_331_648
    assert c["param_count"] == ARCH.param_count(c) == 4_733_292_544
    assert round(c["param_count"] * 2 / 2 ** 30, 2) == 8.82
    assert ARCH.held_picks_a_token(c) == 1.0
    assert c["active_param_count"] == 4 * (344_461_312 + 50_331_648) \
        + 32768 * 4096 + 4096
    assert ARCH.layer_counts(c) == {"window": 3, "full": 1}
    assert c["kv_bytes_per_token"] == 2 * 8 * 128 * 2 == 4096
    assert c["state_bytes_per_slot"] == 3 * (4096 + 16) * 4096


def test_the_cache_the_program_keeps_is_the_cache_the_file_counts():
    from paddle_tpu.inference.paged import init_pool

    family, cfg = build_config(CONF, "serve")
    blk = CONF["serve"]
    cache = jax.eval_shape(lambda: init_pool(
        cfg, 8, 64, state_shapes=family.state_shapes(cfg),
        state_rows=blk["num_slots"], pool_layout=family.pool_layout(cfg)))
    assert cache["k"].shape == cache["v"].shape == (1, 8, 8, 64, 128)
    ring = cache["state"]["ring_k"]
    assert ring.shape == (3, blk["num_slots"] + 1, 257, 8, 16, 128)
    assert {a.dtype for a in jax.tree.leaves(cache)} == {
        jnp.dtype(jnp.bfloat16)}
    assert 2 * int(np.prod(cache["k"].shape)) * 2 \
        == 8 * 64 * CONF["kv_bytes_per_token"]
    assert 2 * int(np.prod(ring.shape)) * 2 \
        == (blk["num_slots"] + 1) * CONF["state_bytes_per_slot"]
    assert (blk["num_slots"], blk["max_len"]) == (56, 16400)
    assert blk["pool_tokens"] % 64 == 0


def test_every_key_is_the_catalogs_and_three_are_cut():
    if not CATALOG or not os.path.exists(CATALOG):
        pytest.skip("MODEL_CATALOG names no catalog file")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == NAME)
    assert CONF["source"] == row["source_url"]
    cut = {"num_hidden_layers": (32, 4), "num_experts": (128, 16),
           "vocab_size": (262144, 32768)}
    assert CONF["reduced"] == list(cut)
    assert CONF["source_values"] == {k: v[0] for k, v in cut.items()}
    for key, value in row["config"].items():
        assert CONF[key] == (cut[key][1] if key in cut else value), key
    assert CONF["n_router_outputs"] == 128 and CONF["first_held_expert"] == 0
    entry = next(c for c in MAN.doc["configs"] if c["name"] == NAME)
    assert entry["reduced"] == list(cut)
    assert entry["source"] == row["source_url"]


# -- the reference against the program ----------------------------------------

def tiny(**chk):
    conf = copy.deepcopy(rehearsal_of(CONF))
    conf["serve"]["check"].update(chk)
    family, cfg = build_config(conf, "serve")
    return conf, family, cfg, loud(jax.jit(
        lambda k: family.init_params(cfg, k))(jax.random.PRNGKey(0)))


def loud(params):
    """The gains that ``init_params`` leaves at one, off one, so that a
    norm's forgotten weight shows."""
    def one(path, a):
        if not jnp.issubdtype(a.dtype, jnp.floating) or not bool(
                jnp.all(a == 1)):
            return a
        k = jax.random.PRNGKey(len(jax.tree_util.keystr(path)))
        return a * (1 + 0.3 * jax.random.normal(k, a.shape, a.dtype))
    return jax.tree_util.tree_map_with_path(one, params)


def test_prefill_then_decode_through_the_cache_matches_the_reference():
    """The engine's own programs: 14 prompt tokens (past the window of 8
    and the ring's wrap at 12) in pages of 16 then 6 decode steps; and at
    pages of 4, crossing pages."""
    conf, family, cfg, params = tiny()
    for page in (16, 4):
        out = check.serve_check(ARCH, family, cfg, conf, params, page, seed=7)
        assert out["ok"] and out["rms_err_over_rms"] < 1e-5, out
        assert out["logit_err_over_max"] < 1e-5


def test_the_programs_picks_are_kept_under_the_prompts_ids():
    conf, family, cfg, params = tiny()
    ARCH._RECORD.clear()
    check.serve_check(ARCH, family, cfg, conf, params, 16, seed=7)
    chk = conf["serve"]["check"]
    assert len(ARCH._RECORD) == chk["prompts"]
    for parts in ARCH._RECORD.values():
        got = np.concatenate(parts, 1)
        assert got.shape == (4, chk["prompt_len"] + chk["decode_steps"], 4)
    assert ARCH._picked(np.arange(5, dtype=np.int32)) is None


# One thing wrong in the reference, by a rewrite of its source: (what the
# reference's text has, what the faulty one has instead).
FAULTS = {
    "no_rope_on_the_window_layers": (
        "    if window:\n        q, k = rotary(", "    if False:\n"
        "        q, k = rotary("),
    "rope_on_the_full_layer": (
        "    if window:\n        q, k = rotary(", "    if True:\n"
        "        q, k = rotary("),
    "no_window_bound": (
        'seen = (age >= 0) & (age < c["sliding_window"]) if window else '
        "age >= 0", "seen = age >= 0"),
    "the_block_made_sequential": (
        "    a = attention(h, w, c, window)\n",
        "    a = attention(h, w, c, window)\n    x, a = x + a, 0.0\n"
        '    h = layer_norm(x, w["ln"], c["layer_norm_eps"])\n'),
    "no_centring_in_layernorm": ("    mu = jnp.mean(x, -1, keepdims=True)\n",
                                 "    mu = 0.0\n"),
    "no_shared_experts": ("return x + a + (routed + shared) / 2",
                          "return x + a + routed / 2"),
    "no_average": ("return x + a + (routed + shared) / 2",
                   "return x + a + (routed + shared)"),
    "no_norm_of_the_picks": (
        "weight = top / top.sum(-1, keepdims=True)", "weight = top"),
    "a_held_expert_missing": (
        "wi = jnp.sum(jnp.where(picks == first + i, weight, 0.0), -1)",
        "wi = (i > 0) * jnp.sum(jnp.where(picks == first + i, weight, "
        "0.0), -1)"),
    "follows_whatever_the_program_picked": (
        "picks = jnp.where((gap <= margin)[:, None], follow, own)",
        "picks = follow"),
}


def faulty_reference(fault: str):
    """The architecture's module with one piece of its text replaced."""
    text = inspect.getsource(ARCH)
    old, new = FAULTS[fault]
    assert text.count(old) == 1, (fault, text.count(old))
    text = text.replace(old, new)
    name = f"benchmark.architectures._faulty_command_a_{fault}"
    spec = importlib.util.spec_from_loader(name, loader=None)
    module = importlib.util.module_from_spec(spec)
    module.__package__ = "benchmark.architectures"
    exec(compile(text, name, "exec"), module.__dict__)
    return module


@pytest.mark.parametrize("fault", sorted(
    set(FAULTS) - {"follows_whatever_the_program_picked"}))
def test_seeded_weights_hide_no_dropped_term(fault):
    """Each term of the layer dropped or bent in the reference in turn
    moves the logits by more than one and a half times the cell's bands on
    the chip, so ``correct`` would be false."""
    conf, family, cfg, params = tiny(
        route_margin=CONF["serve"]["check"]["route_margin"], prompts=4,
        prompt_len=40)
    band = CONF["serve"]["check"]
    out = check.serve_check(faulty_reference(fault), family, cfg, conf,
                            params, 16, seed=7)
    assert not out["ok"]
    assert out["rms_err_over_rms"] > 1.5 * band["rms_tolerance"], out


def test_a_reference_that_followed_every_pick_would_hide_a_wrong_router():
    """Why the margin: a program whose router is another (its columns
    shuffled) passes a reference that takes whatever the program picked;
    the module's rule, the same program, fails it."""
    conf, family, cfg, params = tiny(route_margin=CONF["serve"]["check"][
        "route_margin"], prompts=4, prompt_len=40)
    wrong = jax.tree.map(lambda a: a, params)
    for kind in ("window", "full"):
        wrong[kind] = {**params[kind],
                       "router": params[kind]["router"][..., ::-1]}
    blind = faulty_reference("follows_whatever_the_program_picked")
    assert check.serve_check(blind, family, cfg, conf, wrong, 16, seed=7,
                             reference_params=jax.tree.map(
                                 lambda a: a, wrong))["ok"]
    out = check.serve_check(ARCH, family, cfg, conf, wrong, 16, seed=7,
                            reference_params=params)
    assert not out["ok"] and out["rms_err_over_rms"] > 0.05


@pytest.mark.parametrize("lower", ["int8", "fp8"])
def test_a_band_a_quarter_over_bf16_fails_weights_a_precision_below(lower):
    """At a width the CPU can run, bands 1.25 times what the bf16 program
    measures there fail weights rounded to int8 (a scale a matrix's output
    channel) and to fp8."""
    conf = copy.deepcopy(rehearsal_of(CONF))
    conf.update(hidden_size=256, intermediate_size=128, num_attention_heads=8,
                num_key_value_heads=2, head_dim=64, vocab_size=512)
    conf["serve"]["set"]["dtype"] = "bfloat16"
    conf["serve"]["check"].update(prompts=2, prompt_len=48, decode_steps=4,
                                  tolerance=1.0, rms_tolerance=1.0,
                                  route_margin=1.0)
    family, cfg = build_config(conf, "serve")
    params = loud(jax.jit(lambda k: family.init_params(cfg, k))(
        jax.random.PRNGKey(3)))
    bf16 = check.serve_check(ARCH, family, cfg, conf, params, 16, seed=7)
    assert 1e-3 < bf16["rms_err_over_rms"] < 0.1
    conf["serve"]["check"].update(
        tolerance=1.25 * bf16["logit_err_over_max"],
        rms_tolerance=1.25 * bf16["rms_err_over_rms"], route_margin=0.05)
    assert check.serve_check(ARCH, family, cfg, conf, params, 16, seed=7)["ok"]

    def rounded(w):
        if w.ndim < 2 or w.dtype != jnp.bfloat16:
            return w
        if lower == "fp8":
            return w.astype(jnp.float8_e4m3fn).astype(w.dtype)
        wf = w.astype(jnp.float32)
        s = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / 127.0
        return (jnp.round(wf / jnp.maximum(s, 1e-12)) * s).astype(w.dtype)

    out = check.serve_check(ARCH, family, cfg, conf,
                            jax.tree.map(rounded, params), 16, seed=7,
                            reference_params=params)
    assert not out["ok"]
    assert out["rms_err_over_rms"] > 1.5 * bf16["rms_err_over_rms"]


# -- the cell, its metrics, the work functions --------------------------------

JOINED = {"sched.occupancy_pct", "dev.idle_pct.serve_sat",
          "prog.decode_chunk_step_ms", "kern.paged_attn_named_roofline",
          "sched.host_ms_per_step", "prog.decode.dense_ms",
          "prog.decode.kv_write_ms", "prog.decode.unscoped_ms",
          "prog.mfu.serve", "prog.decode.window_ms",
          "kern.window_attn_roofline", "prog.decode.moe_ms",
          "kern.moe_experts_roofline", "prog.prefill_ms_per_step",
          "prog.prefill_tok_s", "sched.prefill_fill_pct",
          "dev.launch_gap_ms_per_step", "sched.return_wait_ms_per_step"}


def test_the_cell_its_metric_and_the_metrics_it_joined():
    doc = MAN.doc
    cell = MAN.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "longdoc-sat", 1)
    entry = next(m for m in doc["per_layer"]
                 if m["name"] == "prog.decode.full_attn_ms")
    assert entry["workloads"] == [CELL] and entry["moves"] == "serve_tok_s"
    joined = {m["name"] for m in doc["per_layer"]
              if CELL in m.get("workloads", []) and m["workloads"] != [CELL]}
    assert joined == JOINED
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL          # appended, not put in
    assert {m["name"] for m in MAN.metrics_of(CELL, "end_to_end")} \
        == {"serve_tok_s", "setup_s"}
    spec = MAN.layer_metric("prog.decode.full_attn_ms")
    assert spec["params"]["through"] == "attn.full" and "sum" not in spec[
        "params"]
    mix = MAN.traffic("longdoc-sat")
    assert (mix["prompt"]["median"], mix["output"]["median"]) == (4096, 4096)
    assert (mix["max_queue_requests"], mix["max_queue_tokens"]) == (2, 16384)


def test_a_xk1s_entries_are_there_by_name():
    """What ``test_axk1_cell.py``'s two stale cases hold of A.X K1's
    entries, found by name: its configuration, its cell, its two metrics
    with it alone, and the thirteen it joined."""
    doc = MAN.doc
    assert [w["name"] for w in doc["workloads"]].index(AXK1) == 5
    assert [c["name"] for c in doc["configs"]].index("ax-k1") == 5
    for name in ("prog.decode.mla_ms", "kern.mla_decode_roofline"):
        m = next(m for m in doc["per_layer"] if m["name"] == name)
        assert m["workloads"] == [AXK1]
    joined = {m["name"] for m in doc["per_layer"]
              if AXK1 in m.get("workloads", []) and m["workloads"] != [AXK1]}
    assert joined == {
        "sched.occupancy_pct", "dev.idle_pct.serve_sat",
        "prog.decode_chunk_step_ms", "sched.host_ms_per_step",
        "prog.decode.dense_ms", "prog.decode.kv_write_ms",
        "prog.decode.unscoped_ms", "prog.mfu.serve", "prog.decode.moe_ms",
        "kern.moe_experts_roofline", "sched.prefill_fill_pct",
        "dev.launch_gap_ms_per_step", "sched.return_wait_ms_per_step"}


def _trace():
    from benchmark.harness import trace_reduce as T

    def ev(line, name, start, dur):
        return T.Event("/device:TPU:0", line, name, start, dur)

    return T.Trace([
        ev(T.MODULES, "jit_decode_chunk(1)", 0, 30_000_000),
        ev(T.OPS, "%paged_decode_attn_window.3 = custom-call", 0, 4_000_000),
        ev(T.OPS, "%paged_decode_attn.4 = custom-call", 5_000_000,
           2_000_000),
        ev(T.OPS, "%moe_expert_mlp_decode.3 = custom-call", 12_000_000,
           9_000_000)])


CTX = {"config": CONF, "peaks": {"hbm_bytes": 819e9, "flops": 197e12},
       "counters": {"kv_token_steps": 100 * 56 * 7000.0,
                    "traced_decode_steps": 100,
                    "traced_tokens_decoded": 100 * 56,
                    "engine.expert_reads": 800 * 4 * 15.7,
                    "engine.decode_steps": 800}}


def test_the_window_kernels_work_is_the_window_a_token():
    spec = MAN.layer_metric("kern.window_attn_roofline")["params"]
    least, secs = ARCH.window_attn_bytes(spec, CTX, _trace())
    assert secs == pytest.approx(4e-3)
    assert least == pytest.approx(3 * 4096 * 4096 * 100 * 56 / 819e9)
    spec = MAN.layer_metric("kern.paged_attn_named_roofline")["params"]
    least, secs = ARCH.paged_attn_bytes(spec, CTX, _trace())
    assert secs == pytest.approx(6e-3)
    assert least == pytest.approx((4096 * 100 * 56 * 7000
                                   + 3 * 4096 * 4096 * 100 * 56) / 819e9)


def test_the_expert_work_counts_held_experts_read():
    spec = MAN.layer_metric("kern.moe_experts_roofline")["params"]
    least, secs = ARCH.moe_expert_bytes(spec, CTX, _trace())
    assert secs == pytest.approx(9e-3)
    assert least == pytest.approx(100 * 4 * 15.7 * 50_331_648 * 2 / 819e9)


def test_the_decode_steps_flops_count_the_active_parameters():
    spec = MAN.layer_metric("prog.mfu.serve")["params"]
    least, secs = ARCH.decode_step_flops(spec, CTX, _trace())
    assert secs == pytest.approx(30e-3)
    attn = 4 * 128 * 128 * (100 * 56 * 7000 + 3 * 4096 * 100 * 56)
    assert least == pytest.approx(
        (2 * CONF["active_param_count"] * 100 * 56 + attn) / 197e12)


def test_a_parent_without_the_family_fails_at_once(tmp_path):
    """The parent's checkout with this benchmark laid over it: the
    configuration's module is not there, and a rehearsal stops at once
    with the import's message instead of hanging."""
    try:
        tree = subprocess.run(["git", "archive", PARENT], cwd=ROOT,
                              check=True, capture_output=True).stdout
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here to read the parent's tree from")
    subprocess.run(["tar", "-x", "-C", str(tmp_path)], input=tree, check=True)
    for path in ("BENCHMARK.json", "benchmark", "tests/benchmark"):
        subprocess.run(["cp", "-r", os.path.join(ROOT, path),
                        os.path.dirname(tmp_path / path)], check=True)
    p = subprocess.run(
        ["python3", "-m", "benchmark.run", "--workload", CELL, "--seed", "5",
         "--seconds", "2", "--trace", "0", "--rehearse"], cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0 and not p.stdout.strip().endswith("}")
    assert "paddle_tpu.models.command_a" in p.stderr


# -- files added, entries appended, nothing edited ----------------------------

def _parent(path):
    try:
        return subprocess.run(["git", "show", f"{PARENT}:{path}"], cwd=ROOT,
                              check=True, capture_output=True).stdout
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here to read the parent's file from")


def test_no_file_that_was_there_changed():
    try:
        listed = subprocess.run(
            ["git", "ls-tree", "-r", "--name-only", PARENT, "benchmark",
             "tests/benchmark"], cwd=ROOT, check=True, capture_output=True,
            text=True).stdout.split()
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here to read the parent's tree from")
    assert len(listed) > 70
    for path in listed:
        with open(os.path.join(ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).digest() \
                == hashlib.sha256(_parent(path)).digest(), path


def test_benchmark_json_only_gained_entries():
    """Every entry the parent's ``BENCHMARK.json`` had is there unchanged
    and in order, but for this cell's name appended to lists of
    ``workloads``; one configuration, one cell and one metric added."""
    was = json.loads(_parent("BENCHMARK.json"))
    now = MAN.doc
    assert {k: now[k] for k in ("command", "paths", "run_seconds")} \
        == {k: was[k] for k in ("command", "paths", "run_seconds")}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(was[group], now[group]):
            if new.get("workloads", [])[-1:] == [CELL]:
                new = {**new, "workloads": new["workloads"][:-1]}
            assert old == new, old["name"]
    assert len(now["workloads"]) == len(was["workloads"]) + 1
    assert len(now["configs"]) == len(was["configs"]) + 1
    assert len(now["per_layer"]) == len(was["per_layer"]) + 1
