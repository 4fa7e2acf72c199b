"""The cell ``ax-k1.reason-sat`` and what came with it: the file's stated
counts against the architecture's module and the program's own parameter
tree and latent pool, the plain reference against the program through
the cache (``check.serve_check``: a prefill that ends inside a page, then
decode steps that cross into the next, absorbed), the shares of the
deployment adding up to the uncut layer, the controls that show the
seeded weights and the rule that follows a near-tie hide no fault (each
term dropped or bent in the reference in turn, a program with another
group rule, weights in int8 and fp8), the work functions on a synthetic
trace, and that PR 37 added files and appended entries and edited no file
the benchmark had."""
import copy
import dataclasses
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
from benchmark.harness import reference as R
from benchmark.harness.manifest import ROOT, Manifest, build_config
from benchmark.run import rehearsal_of

MAN = Manifest()
NAME, CELL = "ax-k1", "ax-k1.reason-sat"
CONF = MAN.config(NAME)
ARCH = MAN.architecture(CONF)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARENT = "ca25dec"


# -- the counts ------------------------------------------------------------

def test_the_counts_are_issue_37s_arithmetic():
    """MLA 101.1 M a layer (and its four norms), the dense layer 497.5 M,
    an expert 44.04 M, an expert layer 146.6 M beside its 12 held experts,
    the 1/8 vocabulary's table and head 293.6 M: 3,491 M, 6.50 GiB; a
    token's rows 1,280 B a layer (576 values padded to 640)."""
    c = CONF
    assert ARCH.attn_params(c) == 101_122_048 + 2 * 7168 + 1536 + 512
    assert ARCH.layer_params(c, True) == 497_500_160
    assert ARCH.expert_params(c) == 44_040_192
    assert ARCH.layer_params(c, False) - 12 * 44_040_192 \
        == ARCH.attn_params(c) + 44_040_192 + 7168 * 192
    assert c["param_count"] == ARCH.param_count(c) == 3_491_257_344
    assert round(c["param_count"] * 2 / 2 ** 30, 2) == 6.50
    assert ARCH.held_picks_a_token(c) == 0.5
    assert c["active_param_count"] == (
        ARCH.layer_params(c, True) + 4 * (ARCH.layer_params(c, False)
                                          - 12 * 44_040_192 + 22_020_096)
        + 20480 * 7168 + 7168)
    assert c["kv_bytes_per_token"] == 5 * 640 * 2 == 6400
    assert ARCH.state_bytes_per_slot(c) == 0


def test_the_pool_the_program_keeps_is_the_pool_the_file_counts():
    from paddle_tpu.inference.paged import init_pool

    family, cfg = build_config(CONF, "serve")
    cache = jax.eval_shape(lambda: init_pool(
        cfg, 8, 128, pool_layout=family.pool_layout(cfg)))
    assert set(cache) == {"latent"}
    assert cache["latent"].shape == (5, 8, 1, 128, 640)
    assert cache["latent"].dtype == jnp.bfloat16
    assert int(np.prod(cache["latent"].shape)) * 2 \
        == 8 * 128 * CONF["kv_bytes_per_token"]
    blk = CONF["serve"]
    assert (blk["num_slots"], blk["max_len"], blk["pool_tokens"]) \
        == (256, 16400, 930944)
    assert blk["pool_tokens"] % 128 == 0
    total = 2 * CONF["param_count"] + blk["pool_tokens"] * 6400
    assert 0.25 * 15.75 < total / 2 ** 30 < 15.45


def test_every_key_is_the_catalogs_and_three_are_cut():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "A.X-K1")
    assert CONF["source"] == row["source_url"]
    cut = {"num_hidden_layers": (61, 5), "n_routed_experts": (192, 12),
           "vocab_size": (163840, 20480)}
    assert CONF["reduced"] == list(cut)
    assert CONF["source_values"] == {k: v[0] for k, v in cut.items()}
    for key, value in row["config"].items():
        assert CONF[key] == (cut[key][1] if key in cut else value), key
    assert CONF["n_router_outputs"] == 192 and CONF["first_held_expert"] == 0
    entry = next(c for c in MAN.doc["configs"] if c["name"] == NAME)
    assert entry["reduced"] == list(cut)
    assert entry["source"] == row["source_url"]


# -- the reference against the program ----------------------------------------

def tiny(**chk):
    conf = copy.deepcopy(rehearsal_of(CONF))
    conf["serve"]["check"].update(chk)
    family, cfg = build_config(conf, "serve")
    return conf, family, cfg, loud(family.init_params(
        cfg, jax.random.PRNGKey(0)))


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


def test_forward_matches_the_reference():
    conf, family, cfg, params = tiny()
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 40))
    want, routes = family.forward(params, jnp.asarray(ids), cfg,
                                  with_routes=True)
    for b in range(2):
        got = ARCH.logits_at(params, jnp.asarray(ids[b]), conf,
                             np.arange(40))
        np.testing.assert_allclose(np.asarray(got), want[b], atol=2e-5)
    # and a layer's own picks are the program's
    x = params["embed"][jnp.asarray(ids[0])]
    x, _ = ARCH.layer(x, ARCH._layer_weights(params, 0, conf), conf)
    _, (e, own, gap) = ARCH.layer(x, ARCH._layer_weights(params, 1, conf),
                                  conf)
    np.testing.assert_array_equal(np.sort(e, -1), np.sort(routes[0, 0], -1))
    assert float(jnp.max(gap)) == 0.0


def test_prefill_then_decode_through_the_cache_matches_the_reference():
    """The engine's own programs: 14 prompt tokens in pages of 16, then 3
    absorbed decode steps that cross into the next page; and at pages of
    4, three boundaries."""
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
    for key, parts in ARCH._RECORD.items():
        got = np.concatenate(parts, 1)
        assert got.shape == (2, chk["prompt_len"] + chk["decode_steps"], 4)
    assert ARCH._picked(np.arange(5, dtype=np.int32)) is None


def test_the_shares_add_up_to_the_uncut_layer():
    """Every share of the experts (4 chips of 4 of 16 in the rehearsal's
    widths), the shared expert counted once: the held parts add up to the
    layer with all 16 held."""
    conf, family, cfg, params = tiny()
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, 24))
    x = params["embed"][ids].astype(R.F32)
    x, _ = ARCH.layer(x, ARCH._layer_weights(params, 0, conf), conf)
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    whole = {k: jax.random.normal(kk, (16, 32, 64)) * 0.2
             for k, kk in zip(("gate", "up", "down"), ks)}
    w = ARCH._layer_weights(params, 1, conf)
    full, _ = ARCH.layer(x, {**w, "experts": whole},
                         {**conf, "first_held_expert": 0,
                          "n_routed_experts": 16})
    shared = ARCH.layer(x, {**w, "experts": jax.tree.map(
        lambda a: a[:0], whole)}, conf)[0]
    parts = [ARCH.layer(x, {**w, "experts": jax.tree.map(
        lambda a: a[4 * i:4 * i + 4], whole)}, {**conf,
        "first_held_expert": 4 * i})[0] - shared for i in range(4)]
    np.testing.assert_allclose(shared + sum(parts), full, atol=1e-4)
    assert float(jnp.max(jnp.abs(parts[1]))) > 1e-2


# One thing wrong in the reference, by a rewrite of its source: (what the
# reference's text has, what the faulty one has instead).
FAULTS = {
    "no_q_norm": ('q = (R.rms_norm(h @ w["wq_a"], w["q_norm"], eps) @ '
                  'w["wq_b"])', 'q = ((h @ w["wq_a"]) @ w["wq_b"])'),
    "no_kv_norm": ('lat = R.rms_norm(ckv[:, :r], w["kv_norm"], eps)',
                   "lat = ckv[:, :r]"),
    "no_k_pe": ('+ jnp.einsum("qhd,kd->hqk", q_pe, k_pe)) * softmax_scale(c)',
                ") * softmax_scale(c)"),
    "no_yarn_scale": ('return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])'
                      ' ** -0.5 * m * m',
                      'return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])'
                      ' ** -0.5'),
    "no_group_rule": [("choice = jnp.where(jnp.repeat(keep, grouped.shape[-1], "
                       "-1), s, -jnp.inf)", "choice = s"),
                      ('n, kg = c["n_group"], c["topk_group"]',
                       'n, kg = c["n_group"], c["n_group"]')],
    "no_routed_scaling": (
        'weight = top / top.sum(-1, keepdims=True) * c["routed_scaling_factor"]',
        "weight = top / top.sum(-1, keepdims=True)"),
    "no_norm_of_the_picks": (
        'weight = top / top.sum(-1, keepdims=True) * c["routed_scaling_factor"]',
        'weight = top * c["routed_scaling_factor"]'),
    "no_shared_expert": ("y = swiglu(g, w) + experts(", "y = experts("),
    "a_held_expert_missing": ('for i in range(ex["gate"].shape[0]):',
                              'for i in range(1, ex["gate"].shape[0]):'),
    "follows_whatever_the_program_picked": (
        "picks = jnp.where((gap <= margin)[:, None], follow, own)",
        "picks = follow"),
}


def faulty_reference(fault: str):
    """The architecture's module with one piece of its text replaced (or
    the pieces of one fault: the group rule is both the router's and the
    rule that judges the program's picks)."""
    text = inspect.getsource(ARCH)
    pairs = FAULTS[fault]
    for old, new in pairs if isinstance(pairs, list) else [pairs]:
        assert text.count(old) == 1, (fault, text.count(old))
        text = text.replace(old, new)
    name = f"benchmark.architectures._faulty_axk1_{fault}"
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
    the chip, so ``correct`` would be false; the program's picks, followed
    inside ``route_margin`` alone, carry no wrong router through."""
    conf, family, cfg, params = tiny(
        route_margin=CONF["serve"]["check"]["route_margin"], prompts=4,
        prompt_len=40)
    band = CONF["serve"]["check"]
    out = check.serve_check(faulty_reference(fault), family, cfg, conf,
                            params, 16, seed=7)
    assert not out["ok"]
    assert out["rms_err_over_rms"] > 1.5 * band["rms_tolerance"], out


def test_a_reference_that_followed_every_pick_would_hide_a_wrong_rule():
    """Why the margin: a program without the group rule (every expert a
    candidate) passes a reference that takes whatever the program picked;
    the module's rule, the same program, fails it."""
    conf, family, cfg, params = tiny(route_margin=CONF["serve"]["check"][
        "route_margin"], prompts=4, prompt_len=40)
    wrong = dataclasses.replace(cfg, topk_group=cfg.n_group)
    blind = faulty_reference("follows_whatever_the_program_picked")
    assert check.serve_check(blind, family, wrong, conf, params, 16,
                             seed=7)["ok"]
    out = check.serve_check(ARCH, family, wrong, conf, params, 16, seed=7)
    assert not out["ok"] and out["rms_err_over_rms"] > 0.05


def test_a_near_tie_decided_the_other_way_passes(capsys):
    """The program's router nudged a little (what bf16 does to it on the
    chip; it moves the weights a little too): some pick sets change, among
    them near-ties between two groups, the reference follows every one,
    and the check passes under bands of 0.01; with a margin of zero the
    same program is ten times as far off and fails."""
    conf, family, cfg, params = tiny(route_margin=0.02, prompt_len=40,
                                     prompts=4, tolerance=0.01,
                                     rms_tolerance=0.01)
    nudge = jax.random.normal(jax.random.PRNGKey(5),
                              params["moe"]["router"].shape) * 0.002
    nudged = jax.tree.map(lambda a: a, params)
    nudged["moe"] = {**params["moe"],
                     "router": params["moe"]["router"] + nudge}
    del ARCH.FOLLOWED[:]
    out = check.serve_check(ARCH, family, cfg, conf, nudged, 16, seed=7,
                            reference_params=params)
    assert "differed from the reference's own on" in capsys.readouterr().out
    flipped = sum(f["differed"] for f in ARCH.FOLLOWED)
    assert flipped > 0 and flipped == sum(f["followed"]
                                          for f in ARCH.FOLLOWED)
    assert max(f["largest_gap"] for f in ARCH.FOLLOWED) < 0.02
    assert out["ok"] and out["rms_err_over_rms"] < 0.005, out
    conf["serve"]["check"]["route_margin"] = 0.0
    out = check.serve_check(ARCH, family, cfg, conf, nudged, 16, seed=7,
                            reference_params=params)
    assert not out["ok"] and out["rms_err_over_rms"] > 0.01, out


@pytest.mark.parametrize("lower", ["int8", "fp8"])
def test_a_band_a_quarter_over_bf16_fails_weights_a_precision_below(lower):
    """At a width the CPU can run, bands 1.25 times what the bf16 program
    measures there fail weights rounded to int8 (a scale a matrix's
    output channel) and to fp8."""
    conf = copy.deepcopy(rehearsal_of(CONF))
    conf.update(hidden_size=256, intermediate_size=256,
                moe_intermediate_size=128, q_lora_rank=128, kv_lora_rank=128,
                num_attention_heads=8, vocab_size=512)
    conf["serve"]["set"]["dtype"] = "bfloat16"
    conf["serve"]["check"].update(prompts=2, prompt_len=48, decode_steps=4,
                                  tolerance=1.0, rms_tolerance=1.0,
                                  route_margin=1.0)
    family, cfg = build_config(conf, "serve")
    params = loud(family.init_params(cfg, jax.random.PRNGKey(3)))
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


# -- the cell, its metrics, the work functions -----------------------------------

NEW = ["prog.decode.mla_ms", "kern.mla_decode_roofline"]
JOINED = {"sched.occupancy_pct", "dev.idle_pct.serve_sat",
          "prog.decode_chunk_step_ms", "sched.host_ms_per_step",
          "prog.decode.dense_ms", "prog.decode.kv_write_ms",
          "prog.decode.unscoped_ms", "prog.mfu.serve", "prog.decode.moe_ms",
          "kern.moe_experts_roofline", "sched.prefill_fill_pct",
          "dev.launch_gap_ms_per_step", "sched.return_wait_ms_per_step"}


def test_the_cell_its_metrics_and_the_metrics_it_joined():
    doc = MAN.doc
    assert doc["workloads"][-1]["name"] == CELL
    assert doc["configs"][-1]["name"] == NAME
    assert [m["name"] for m in doc["per_layer"][-2:]] == NEW
    for m in doc["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    joined = {m["name"] for m in doc["per_layer"]
              if CELL in m.get("workloads", []) and m["workloads"] != [CELL]}
    assert joined == JOINED
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL          # appended, not put in
    cell = MAN.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "reason-sat", 1)
    assert {m["name"] for m in MAN.metrics_of(CELL, "end_to_end")} \
        == {"serve_tok_s", "setup_s"}
    spec = MAN.layer_metric("kern.mla_decode_roofline")
    assert spec["params"]["pattern"] == "^%mla_decode"
    assert spec["params"]["roofline"]["work"] == "mla_decode_work"
    spec = MAN.layer_metric("prog.decode.mla_ms")
    assert spec["params"]["through"] == "attn.mla" and "sum" not in spec[
        "params"]
    assert not hasattr(ARCH, "paged_attn_bytes")


def _trace():
    from benchmark.harness import trace_reduce as T

    def ev(line, name, start, dur):
        return T.Event("/device:TPU:0", line, name, start, dur)

    return T.Trace([
        ev(T.MODULES, "jit_decode_chunk(1)", 0, 20_000_000),
        ev(T.OPS, "%mla_decode.3 = custom-call", 0, 5_000_000),
        ev(T.OPS, "%mla_decode.3 = custom-call", 6_000_000, 5_000_000),
        ev(T.OPS, "%moe_expert_mlp_decode.3 = custom-call", 12_000_000,
           4_000_000)])


def test_the_latent_kernels_work_is_its_live_rows_and_bound_by_bytes():
    ctx = {"config": CONF, "peaks": {"hbm_bytes": 819e9, "flops": 197e12},
           "counters": {"kv_token_steps": 100 * 256 * 2700.0,
                        "traced_decode_steps": 100}}
    spec = MAN.layer_metric("kern.mla_decode_roofline")["params"]
    least, secs = ARCH.mla_decode_work(spec, ctx, _trace())
    assert secs == pytest.approx(10e-3)
    rows = 100 * 256 * 2700 * 5 * 1152
    qo = 100 * 5 * 256 * 64 * (576 + 512) * 2
    assert least == pytest.approx((rows + qo) / 819e9)
    flops = 2 * 64 * (576 + 512) * 100 * 256 * 2700 * 5
    assert flops / 197e12 < least < 1.1 * rows / 819e9     # bytes bound it


def test_the_expert_work_counts_held_experts_read():
    ctx = {"config": CONF, "peaks": {"hbm_bytes": 819e9, "flops": 197e12},
           "counters": {"engine.expert_reads": 800 * 4 * 11.5,
                        "engine.decode_steps": 800,
                        "traced_decode_steps": 100}}
    spec = MAN.layer_metric("kern.moe_experts_roofline")["params"]
    least, secs = ARCH.moe_expert_bytes(spec, ctx, _trace())
    assert secs == pytest.approx(4e-3)
    assert least == pytest.approx(100 * 4 * 11.5 * 44_040_192 * 2 / 819e9)


# -- files added, entries appended, nothing edited -------------------------------

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
    assert len(listed) > 50
    for path in listed:
        with open(os.path.join(ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).digest() \
                == hashlib.sha256(_parent(path)).digest(), path


def test_benchmark_json_only_gained_entries():
    """Every entry the parent's ``BENCHMARK.json`` had is there unchanged,
    but for this cell's name appended to lists of ``workloads``."""
    was = json.loads(_parent("BENCHMARK.json"))
    now = MAN.doc
    assert {k: now[k] for k in ("command", "paths", "run_seconds")} \
        == {k: was[k] for k in ("command", "paths", "run_seconds")}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(was[group], now[group]):
            if CELL in new.get("workloads", []):
                new = {**new, "workloads": [w for w in new["workloads"]
                                            if w != CELL]}
            assert old == new, old["name"]
    assert len(now["workloads"]) == len(was["workloads"]) + 1
    assert len(now["configs"]) == len(was["configs"]) + 1
    assert len(now["per_layer"]) == len(was["per_layer"]) + 2
