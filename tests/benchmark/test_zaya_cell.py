"""The cell ``zaya1-8b.reason-sat`` and what came with it: the file's stated
counts against the architecture's module and the program's own parameter
tree and cache, the plain reference against the program through the cache
(``check.serve_check``: the tails crossing from prefill to decode, a page
boundary), the controls that show the seeded weights and the rule that
follows a near-tie hide no fault (each term of the layer dropped or bent
in the reference in turn, a pick taken from the wrong expert, a program
that drops an over-capacity row, weights in int8 and fp8), the expert
layer under total imbalance beside ``models/moe.py``'s capacity path, the
work function on a synthetic trace, and that PR 33 added files and
appended entries and edited no file the benchmark had."""
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
NAME, CELL = "zaya1-8b", "zaya1-8b.reason-sat"
CONF = MAN.config(NAME)
ARCH = MAN.architecture(CONF)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# -- the counts ------------------------------------------------------------

def test_the_counts_are_issue_33s_arithmetic():
    """A layer multiplies 18.8 M parameters a token and holds 207.6 M; 20
    layers and the tied table are 4.688 B; a token's keys and values are
    1,024 B a layer; a sequence's row 2,688 values a layer."""
    c = CONF
    assert ARCH.attn_params(c) == 5_575_680 + 2            # and the two tau
    assert ARCH.expert_params(c) * 16 == 201_326_592
    assert ARCH.router_params(c) == 659_472 + 512      # gamma, router norm
    assert ARCH.layer_params(c) == 207_574_546
    assert ARCH.layer_params(c, active=True) == 18_830_866
    assert c["param_count"] == 20 * 207_574_546 + 537_133_056 + 2048 \
        == 4_688_626_024
    assert c["active_param_count"] == 20 * 18_830_866 + 537_133_056 + 2048
    assert c["kv_bytes_per_token"] == 20 * 1024 == 20_480
    assert c["state_bytes_per_slot"] == 20 * 2688 * 2 == 107_520
    # the whole model by the same count: 40 layers, 8.84 B held, 0.75 B
    # multiplied a token beside the table (published: 8.3 B without the
    # table's 0.54 B; 760 M active)
    whole = {**c, "num_hidden_layers": 40}
    assert round(ARCH.param_count(whole) / 1e9, 2) == 8.84
    assert round(40 * ARCH.layer_params(c, True) / 1e6) == 753


def test_the_cache_the_program_keeps_is_the_cache_the_file_counts():
    from paddle_tpu.inference.paged import init_pool

    family, cfg = build_config(CONF, "serve")
    rows = 3
    cache = jax.eval_shape(lambda: init_pool(
        cfg, 8, 64, state_shapes=family.state_shapes(cfg), state_rows=rows))
    assert cache["k"].shape == (20, 8, 2, 64, 128)
    pool = sum(int(np.prod(cache[h].shape)) * cache[h].dtype.itemsize
               for h in "kv")
    assert pool == 8 * 64 * CONF["kv_bytes_per_token"]
    leaf = cache["state"]["cca"]
    assert leaf.shape == (20, rows + 1, 2688) and leaf.dtype == jnp.bfloat16
    assert int(np.prod(leaf.shape)) * 2 \
        == (rows + 1) * CONF["state_bytes_per_slot"]
    blk = CONF["serve"]
    assert (blk["num_slots"], blk["max_len"], blk["pool_tokens"]) \
        == (64, 16400, 245760)
    total = (2 * CONF["param_count"] + blk["pool_tokens"] * 20_480
             + 65 * 107_520)
    assert 0.75 * 15.75 < total / 2 ** 30 < 15.45     # 13.4 GiB: 85%


def test_every_key_is_the_catalogs_and_depth_alone_is_cut():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
    assert CONF["source"] == row["source_url"]
    assert CONF["reduced"] == ["num_hidden_layers"]
    assert CONF["source_values"] == {"num_hidden_layers": 40}
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (value, CONF[key]) == (40, 20)
        else:
            assert CONF[key] == value, key
    assert "zaya_use_mod" not in CONF and "skip expert" in " ".join(
        CONF["assumed"])
    entry = next(c for c in MAN.doc["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == row["source_url"]


# -- the reference against the program -----------------------------------------

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
    assert abs(float(ARCH.loss(params, jnp.asarray(ids[0]), conf))
               - np.log(cfg.vocab_size)) < 0.2
    # and a layer's own picks are the program's
    x = params["embed"][jnp.asarray(ids[0])]
    _, _, (e, own, gap) = ARCH.layer(x, ARCH._layer_weights(params, 0), conf)
    np.testing.assert_array_equal(e, routes[0, 0])
    assert float(jnp.max(gap)) == 0.0


def test_prefill_then_decode_through_the_cache_matches_the_reference():
    """The engine's own programs: 14 prompt tokens in pages of 16 (the
    prompt ends inside a page, so its padding is masked), then 3 decode
    steps that read the prefill's tails and cross into the next page; and
    at pages of 4, three boundaries."""
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
        assert len(key) == 4 * chk["prompt_len"]
        got = np.concatenate(parts, 1)
        assert got.shape == (cfg.num_hidden_layers,
                             chk["prompt_len"] + chk["decode_steps"])
        ids = np.frombuffer(key, np.int32)
        np.testing.assert_array_equal(ARCH._picked(ids)[:, :len(ids)],
                                      parts[0])
    assert ARCH._picked(np.arange(5, dtype=np.int32)) is None


# One thing wrong in the reference, by a rewrite of its source: (what the
# reference's text has, what the faulty one has instead).
FAULTS = {
    "no_depthwise_convolution": (
        'a = w["conv1_w"][0] * _before(u) + w["conv1_w"][1] * u '
        '+ w["conv1_b"]', "a = u"),
    "depthwise_without_the_token_before": (
        'a = w["conv1_w"][0] * _before(u) + ', "a = "),
    "grouped_without_the_token_before": (
        'cc = (jnp.einsum("sgi,gio->sgo", ap, w["conv2_w"][0])',
        'cc = (0.0 * jnp.einsum("sgi,gio->sgo", ap, w["conv2_w"][0])'),
    "no_grouped_convolution": ("q = cc[:, :nh] + (", "q = 0 * cc[:, :nh] + ("),
    "no_mean_on_the_queries": (
        "q = cc[:, :nh] + (qt + jnp.repeat(kt, g, axis=1)) / 2",
        "q = cc[:, :nh]"),
    "no_mean_on_the_keys": (
        "k = cc[:, nh:] + (qt.reshape(s, nkv, g, d).mean(2) + kt) / 2",
        "k = cc[:, nh:]"),
    "no_norm": ("return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)"
                " + eps)", "return t"),
    "no_temperature": ('unit(k) * w["tau"][:, None]', "unit(k)"),
    "no_value_shift": ('_before(h @ w["wv2"])', 'h @ w["wv2"]'),
    "rotary_on_the_whole_head": (
        'n = int(d * c["partial_rotary_factor"])', "n = d"),
    "no_rotary_on_the_queries": ("q = _rotary_part(unit(q), theta, n)",
                                 "q = unit(q)"),
    "no_gamma": (' + w["gamma"] * r_prev', ""),
    "a_router_layer_short": (
        '    z = jax.nn.gelu(z @ w["w2"], approximate=False)\n', ""),
    "no_router_norm": ('z = R.rms_norm(r, w["rnorm"], c["rms_norm_eps"])',
                       "z = r"),
    "no_selection_bias": ('return r, s, s + w["rbias"]', "return r, s, s"),
    "no_pick_probability": (
        "y = jnp.take_along_axis(s, e[:, None], -1) * experts(g, e, ex)",
        "y = experts(g, e, ex)"),
    "no_alpha": ('x = w["a_alpha"] * x + w["a_beta"] * attention(',
                 'x = x + w["a_beta"] * attention('),
    "no_beta": ('return w["m_alpha"] * x + w["m_beta"] * y, r,',
                'return w["m_alpha"] * x + y, r,'),
    "the_pick_from_the_wrong_expert": (
        "e = jnp.where(gap <= margin, follow, own)",
        "e = (jnp.where(gap <= margin, follow, own) + 1) % s.shape[-1]"),
    "follows_whatever_the_program_picked": (
        "e = jnp.where(gap <= margin, follow, own)", "e = follow"),
}


def faulty_reference(fault: str):
    """The architecture's module with one piece of its text replaced."""
    old, new = FAULTS[fault]
    text = inspect.getsource(ARCH)
    assert text.count(old) == 1, (fault, text.count(old))
    name = f"benchmark.architectures._faulty_{fault}"
    spec = importlib.util.spec_from_loader(name, loader=None)
    module = importlib.util.module_from_spec(spec)
    module.__package__ = "benchmark.architectures"
    exec(compile(text.replace(old, new), name, "exec"), module.__dict__)
    return module


@pytest.mark.parametrize("fault", sorted(
    set(FAULTS) - {"follows_whatever_the_program_picked"}))
def test_seeded_weights_hide_no_dropped_term(fault):
    """With ``init_params``' deviations every term of the layer is a
    visible share of the logits: each dropped or bent in the reference in
    turn moves them by more than one and a half times the cell's bands on
    the chip, so ``correct`` would be false; the program's picks, followed
    inside ``route_margin`` alone, do not carry a wrong router through."""
    conf, family, cfg, params = tiny(
        route_margin=CONF["serve"]["check"]["route_margin"], prompts=4,
        prompt_len=40)
    band = CONF["serve"]["check"]
    out = check.serve_check(faulty_reference(fault), family, cfg, conf,
                            params, 16, seed=7)
    assert not out["ok"]
    assert out["rms_err_over_rms"] > 1.5 * band["rms_tolerance"], out
    assert out["logit_err_over_max"] > 1.3 * band["tolerance"], out


def test_a_reference_that_followed_every_pick_would_hide_a_wrong_router():
    """Why the margin: a reference that took whatever the program picked
    would pass a program whose selection bias is wrong (the two agree on
    every pick by construction, and the bias weighs nothing); the module's
    rule, the same program, fails it."""
    conf, family, cfg, params = tiny(route_margin=CONF["serve"]["check"][
        "route_margin"])
    wrong = jax.tree.map(lambda a: a, params)
    wrong["layers"] = {**params["layers"],
                       "rbias": -3 * params["layers"]["rbias"]}
    blind = faulty_reference("follows_whatever_the_program_picked")
    assert check.serve_check(blind, family, cfg, conf, wrong, 16, seed=7,
                             reference_params=params)["ok"]
    out = check.serve_check(ARCH, family, cfg, conf, wrong, 16, seed=7,
                            reference_params=params)
    assert not out["ok"] and out["rms_err_over_rms"] > 0.1


def test_a_near_tie_decided_the_other_way_passes(capsys):
    """The program's scores nudged by less than the margin (what bf16 does
    to them on the chip): some picks flip, the reference follows those, and
    the check passes with the logits as close as before; with a margin of
    zero the same program fails."""
    conf, family, cfg, params = tiny(route_margin=0.02, prompt_len=40,
                                     prompts=4)
    nudge = jax.random.normal(jax.random.PRNGKey(5),
                              params["layers"]["rbias"].shape) * 0.004
    nudged = jax.tree.map(lambda a: a, params)
    nudged["layers"] = {**params["layers"],
                        "rbias": params["layers"]["rbias"] + nudge}
    del ARCH.FOLLOWED[:]
    out = check.serve_check(ARCH, family, cfg, conf, nudged, 16, seed=7,
                            reference_params=params)
    assert "differed from the reference's own on" in capsys.readouterr().out
    flipped = sum(f["differed"] for f in ARCH.FOLLOWED)
    assert flipped > 0 and flipped == sum(f["followed"]
                                          for f in ARCH.FOLLOWED)
    assert max(f["largest_gap"] for f in ARCH.FOLLOWED) < 0.02
    assert out["ok"] and out["rms_err_over_rms"] < 1e-5, out
    conf["serve"]["check"]["route_margin"] = 0.0
    out = check.serve_check(ARCH, family, cfg, conf, nudged, 16, seed=7,
                            reference_params=params)
    assert not out["ok"] and out["rms_err_over_rms"] > 0.01, out


def wider(conf, **chk):
    """The rehearsal widened until bf16's error is measurable."""
    conf = copy.deepcopy(conf)
    conf.update(hidden_size=256, moe_intermediate_size=256,
                num_attention_heads=8, num_key_value_heads=2, head_dim=32,
                router_hidden_size=64, num_experts=8, vocab_size=512)
    conf["serve"]["set"]["dtype"] = "bfloat16"
    conf["serve"]["check"].update(prompts=2, prompt_len=48, decode_steps=4,
                                  **chk)
    return conf


@pytest.mark.parametrize("lower", ["int8", "fp8"])
def test_a_band_a_quarter_over_bf16_fails_weights_a_precision_below(lower):
    """The chip's bands are 1.25 times what the bf16 program measured
    there, the picks followed inside the margin its score differences
    showed. At a width the CPU can run, the same rule fails weights
    rounded to int8 (a scale a matrix's output channel) and to fp8: the
    nearest precisions below would not pass as a faster bf16."""
    conf = wider(rehearsal_of(CONF), tolerance=1.0, rms_tolerance=1.0,
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


# -- nothing dropped -------------------------------------------------------------

def lopsided(params, by=5.0):
    """Expert 0 takes every token: its selection bias far above the rest."""
    out = jax.tree.map(lambda a: a, params)
    b = params["layers"]["rbias"]
    out["layers"] = {**params["layers"], "rbias": b.at[:, 0].add(by)}
    return out


def test_one_expert_taking_every_row_is_the_references_result():
    conf, family, cfg, params = tiny(prompts=4, prompt_len=40)
    params = lopsided(params)
    _, routes = family.forward(params, jnp.zeros((1, 24), jnp.int32), cfg,
                               with_routes=True)
    assert int(jnp.max(routes)) == 0                   # total imbalance
    out = check.serve_check(ARCH, family, cfg, conf, params, 16, seed=7)
    assert out["ok"] and out["rms_err_over_rms"] < 1e-5, out


def test_a_program_that_drops_an_over_capacity_row_fails(monkeypatch):
    """The same lopsided weights through an expert layer with GShard's
    capacity rule (a row past ``ceil(T / E x 1.25)`` of its expert gets
    nothing): the check says so."""
    from paddle_tpu import kernels

    conf, family, cfg, params = tiny(prompts=4, prompt_len=40)
    params = lopsided(params)
    real = kernels.dispatched_expert_mlp

    def with_capacity(x, expert, gate, up, down, layer, *, name=None):
        T, Ex = x.shape[0], gate.shape[1]
        hot = jax.nn.one_hot(expert, Ex, dtype=jnp.int32)
        rank = jnp.take_along_axis(jnp.cumsum(hot, 0), expert[:, None], 1)
        keep = rank[:, 0] <= int(np.ceil(T / Ex * 1.25))
        return jnp.where(keep[:, None],
                         real(x, expert, gate, up, down, layer), 0)

    monkeypatch.setattr(kernels, "dispatched_expert_mlp", with_capacity)
    out = check.serve_check(ARCH, family, cfg, conf, params, 16, seed=7)
    assert not out["ok"] and out["rms_err_over_rms"] > 0.05, out


def test_the_capacity_path_drops_what_this_path_computes():
    """32 rows that all pick expert 0, through ``models/moe.py``'s
    capacity dispatch (4 experts, one a token, factor 1.25: 10 slots an
    expert) and through this PR's layer on the same weights: the first 10
    rows agree, the capacity path leaves the other 22 with nothing, this
    path computes every one."""
    from paddle_tpu.kernels import moe_experts as M
    from paddle_tpu.models import moe

    T, D, F, Ex = 32, 128, 128, 4
    mc = moe.moe_tiny(num_experts=Ex, num_experts_per_tok=1, hidden_size=D,
                      intermediate_size=F, dispatch_mode="capacity")
    assert moe.moe_capacity(mc, T) == 10
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (T, D)).at[:, 0].set(8.0)
    g, u, d = (jax.random.normal(k, (1, Ex, F, D)) * 0.1 for k in ks[1:])
    lp = {"router": jnp.zeros((D, Ex)).at[0, 0].set(8.0),
          "e_gate": jnp.swapaxes(g[0], 1, 2), "e_up": jnp.swapaxes(u[0], 1, 2),
          "e_down": d[0]}
    routed, _ = moe._moe_mlp_capacity(x, lp, mc, T)
    own = M.expert_mlp_ref(x, jnp.zeros((T,), jnp.int32), g, u, d, 0)
    np.testing.assert_allclose(routed[:10], own[:10], atol=1e-5)
    assert float(jnp.max(jnp.abs(routed[10:]))) == 0.0          # dropped
    assert float(jnp.min(jnp.max(jnp.abs(own[10:]), -1))) > 1e-3  # computed
    np.testing.assert_allclose(
        M.expert_mlp(x, jnp.zeros((T,), jnp.int32), g, u, d, 0,
                     interpret=True), own, atol=1e-5)


# -- the cell, its metrics, the work function -------------------------------------

NEW = ["prog.decode.moe_ms", "prog.decode.cca_ms", "kern.moe_experts_roofline"]
PHI, FALCON = "phi-4-mini-flash.reason-sat", "falcon-h1-34b.decode-sat"


def test_the_cell_its_metrics_and_the_metrics_it_joined():
    """PR 33's entries are the last of each list, PR 31's the ones before
    them, PR 27's before those (what ``test_phi4flash_cell.py``'s stale
    case held of them, found by name)."""
    doc = MAN.doc
    assert [w["name"] for w in doc["workloads"][-3:]] == [FALCON, PHI, CELL]
    assert [c["name"] for c in doc["configs"][-3:]] \
        == ["falcon-h1-34b", "phi-4-mini-flash", NAME]
    assert [m["name"] for m in doc["per_layer"][-8:]] == [
        "prog.decode.ssm_ms", "kern.ssm_update_roofline",
        "prog.decode.shared_attn_ms", "prog.decode.window_ms",
        "kern.window_attn_roofline"] + NEW
    for m in doc["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    joined = {m["name"] for m in doc["per_layer"]
              if CELL in m.get("workloads", []) and m["workloads"] != [CELL]}
    assert joined == {
        "sched.occupancy_pct", "sched.host_ms_per_step",
        "dev.idle_pct.serve_sat", "prog.decode_chunk_step_ms",
        "prog.decode.dense_ms", "prog.decode.kv_write_ms",
        "prog.decode.unscoped_ms", "prog.mfu.serve",
        "kern.paged_attn_named_roofline"}
    for m in doc["end_to_end"] + doc["per_layer"]:
        cells = m.get("workloads", [])
        if CELL in cells and len(cells) > 1:
            assert cells[-2:] == [PHI, CELL]            # appended, not put in
    cell = MAN.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "reason-sat", 1)
    e2e = {m["name"] for m in MAN.metrics_of(CELL, "end_to_end")}
    assert e2e == {"serve_tok_s", "setup_s"}
    spec = MAN.layer_metric("kern.moe_experts_roofline")
    assert spec["params"]["roofline"]["work"] == "moe_expert_bytes"
    assert spec["params"]["pattern"] == "^%moe_expert_mlp_decode"
    assert callable(ARCH.moe_expert_bytes)
    assert not hasattr(ARCH, "paged_attn_bytes")       # trace_ops' own serves
    spec = MAN.layer_metric("prog.decode.cca_ms")
    assert spec["params"]["through"] == "attn.cca" and "sum" not in spec[
        "params"] and "cross-cut" in spec["what"]
    from benchmark.readers.trace_scope import SCOPES

    spec = MAN.layer_metric("prog.decode.moe_ms")
    assert set(spec["params"]["sum"]) <= set(SCOPES)   # a sibling, not a cut


def test_the_work_function_counts_experts_read_not_sixteen_a_layer():
    from benchmark.harness import trace_reduce as T

    def ev(line, name, start, dur):
        return T.Event("/device:TPU:0", line, name, start, dur)

    trace = T.Trace([
        ev(T.MODULES, "jit_decode_chunk(1)", 0, 10_000_000),
        ev(T.OPS, "%moe_expert_mlp_decode.3 = custom-call", 0, 4_000_000),
        ev(T.OPS, "%moe_expert_mlp_decode.3 = custom-call", 5_000_000,
           4_000_000),
        ev(T.OPS, "%moe_expert_mlp_prefill.9 = custom-call", 9_000_000,
           500_000)])
    ctx = {"config": CONF, "peaks": {"hbm_bytes": 819e9, "flops": 197e12},
           "counters": {"engine.expert_reads": 800 * 20 * 15.5,
                        "engine.decode_steps": 800,
                        "traced_decode_steps": 100}}
    spec = MAN.layer_metric("kern.moe_experts_roofline")["params"]
    least, secs = ARCH.moe_expert_bytes(spec, ctx, trace)
    assert secs == pytest.approx(8e-3)                 # the decode calls'
    byts = 100 * 20 * 15.5 * 3 * 2048 * 2048 * 2
    assert least == pytest.approx(byts / 819e9)
    # all 16 read regardless would be 16 / 15.5 of that time at the bound:
    # the share can only fall by reading what nobody picked
    assert least < 100 * 20 * 16 * 3 * 2048 * 2048 * 2 / 819e9
    ctx["counters"]["engine.decode_steps"] = 0
    assert ARCH.moe_expert_bytes(spec, ctx, trace) == (0.0, 0.0)


# -- files added, entries appended, nothing edited -------------------------------

def _digest(path):
    with open(os.path.join(ROOT, path), "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:16]


def _parent(path):
    try:
        return subprocess.run(
            ["git", "show", f"13e4dcb:{path}"], cwd=ROOT, check=True,
            capture_output=True).stdout
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here to read the parent's file from")


def test_no_file_that_was_there_changed():
    try:
        listed = subprocess.run(
            ["git", "ls-tree", "-r", "--name-only", "13e4dcb", "benchmark",
             "tests/benchmark"], cwd=ROOT, check=True, capture_output=True,
            text=True).stdout.split()
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here to read the parent's tree from")
    assert len(listed) > 50
    for path in listed:
        assert hashlib.sha256(_parent(path)).hexdigest()[:16] \
            == _digest(path), path


def test_benchmark_json_only_gained_entries():
    """Every entry the parent's ``BENCHMARK.json`` had is there unchanged,
    but for the cell's name appended to lists of ``workloads``."""
    was = json.loads(_parent("BENCHMARK.json"))
    now = MAN.doc
    assert {k: now[k] for k in ("command", "paths", "run_seconds")} \
        == {k: was[k] for k in ("command", "paths", "run_seconds")}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(now[group]) >= len(was[group])
        for old, new in zip(was[group], now[group]):
            if new.get("workloads", [])[-1:] == [CELL]:
                new = {**new, "workloads": new["workloads"][:-1]}
            assert old == new, old["name"]
    assert len(now["workloads"]) == len(was["workloads"]) + 1
    assert len(now["configs"]) == len(was["configs"]) + 1
    assert len(now["per_layer"]) == len(was["per_layer"]) + 3
