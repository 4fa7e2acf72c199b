"""The cell ``phi-4-mini-flash.reason-sat`` and what came with it: the
file's stated counts against the architecture's module and the program's
own parameter tree and cache, the plain reference against the program
through the cache (``check.serve_check``, a prompt past the window and a
page), the controls that show the seeded weights hide no fault (each term
of the four kinds of layer dropped or bent in the reference in turn,
weights in fp8, a state in bfloat16), the work functions on a synthetic
trace, and that PR 31 added files and appended entries and edited no file
the benchmark had."""
import copy
import hashlib
import importlib.util
import inspect
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check
from benchmark.harness.manifest import ROOT, Manifest, build_config
from benchmark.run import rehearsal_of

MAN = Manifest()
NAME, CELL = "phi-4-mini-flash", "phi-4-mini-flash.reason-sat"
CONF = MAN.config(NAME)
ARCH = MAN.architecture(CONF)
MIX = MAN.traffic("reason-sat")


# -- the counts ------------------------------------------------------------

def test_the_counts_are_issue_31s_arithmetic():
    """A Mamba layer 119,895,040, a window or full attention layer
    98,322,304, a GMU layer 104,867,840, a cross-attention layer 91,766,144
    (each with its 78,643,200 of SwiGLU and two LayerNorms); 9 + 8 + 1 + 7
    + 7 of them 3,340,393,984, the one table 512,163,840, the last norm."""
    c = CONF
    assert ARCH.layer_counts(c) == {"mamba": 9, "window": 8, "full": 1,
                                    "gmu": 7, "cross": 7}
    assert ARCH.layer_params(c) == {
        "mamba": 119_895_040, "window": 98_322_304, "gmu": 104_867_840,
        "cross": 91_766_144}
    layers = 9 * 119_895_040 + 9 * 98_322_304 + 7 * 104_867_840 \
        + 7 * 91_766_144
    assert layers == 3_340_393_984
    assert ARCH.param_count(c) == ARCH.param_count(c, True) \
        == c["param_count"] == layers + 200_064 * 2_560 + 2 * 2_560 \
        == 3_852_562_944
    assert ARCH.kv_bytes_per_token(c) == c["kv_bytes_per_token"] == 5_120
    assert ARCH.state_bytes_per_slot(c) == c["state_bytes_per_slot"] \
        == 8 * 528 * 5_120 + 9 * (16 * 5_120 * 4 + 3 * 5_120 * 2) \
        == 21_626_880 + 3_225_600 == 24_852_480
    assert ARCH.recurrence_flops_per_token(c) == 6.0 * 9 * 16 * 5_120
    # a key read by one position: 40 heads' 64-wide score, 128-wide value
    assert ARCH.attn_flops_per_key(c) == 2.0 * 40 * (64 + 128) == 15_360
    assert ARCH.model_flops_per_token(c, 4096) == c["model_flops_per_token"][
        "flops"] == 6.0 * 3_852_562_944 + 3 * 15_360 * (8 * 2048 + 8 * 512) \
        + 3 * 6.0 * 9 * 16 * 5_120


def test_the_cache_the_program_keeps_is_the_cache_the_file_counts():
    """``kv_bytes_per_token`` and ``state_bytes_per_slot`` against the
    leaves ``init_pool`` makes for the configuration's own config object:
    ONE layer of pages, a ring a window layer and a state a Mamba layer a
    row, one row more than slots; nothing in a row grows with the context."""
    family, cfg = build_config(CONF, "serve")
    slots = CONF["serve"]["num_slots"]
    cache = jax.eval_shape(lambda: ARCH.make_cache(cfg, 64, 16, slots))
    assert set(cache) == {"k", "v", "state"}
    assert cache["k"].shape == cache["v"].shape == (1, 64, 10, 16, 128)
    a_token = sum(a.dtype.itemsize * int(np.prod(a.shape)) // (64 * 16)
                  for a in (cache["k"], cache["v"]))
    assert a_token == CONF["kv_bytes_per_token"]
    leaves = cache["state"]
    assert leaves["ssm"].shape == (9, slots + 1, 16, 5120)
    assert leaves["ssm"].dtype == jnp.float32
    assert leaves["conv"].shape == (9, slots + 1, 3, 5120)
    assert leaves["ring_k"].shape == leaves["ring_v"].shape \
        == (8, slots + 1, 33, 10, 16, 128)
    assert {leaves[k].dtype for k in ("conv", "ring_k", "ring_v")} \
        == {jnp.dtype(jnp.bfloat16)}
    a_row = sum(a.dtype.itemsize * int(np.prod(a.shape)) // (slots + 1)
                for a in leaves.values())
    assert a_row == CONF["state_bytes_per_slot"]
    pool = CONF["serve"]["pool_tokens"] * CONF["kv_bytes_per_token"]
    held = 2 * CONF["param_count"] + a_row * (slots + 1) + pool
    assert 0.75 < held / (15.75 * 2 ** 30) < 0.85      # before any temporary


def test_every_key_is_the_catalogs_and_nothing_is_cut():
    assert CONF["reduced"] == [] and CONF["source_values"] == {}
    assert MAN.doc["configs"][-1]["reduced"] == []
    want = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
            "intermediate_size": 10240, "layer_norm_eps": 1e-05,
            "max_position_embeddings": 262144, "mb_per_layer": 2,
            "model_type": "phi4flash", "num_attention_heads": 40,
            "num_hidden_layers": 32, "num_key_value_heads": 20,
            "resid_pdrop": 0, "sliding_window": 512,
            "tie_word_embeddings": True, "mlp_bias": False,
            "lm_head_bias": False, "vocab_size": 200064}
    assert {k: CONF[k] for k in want} == want
    assert {k: CONF[k] for k in ("mamba_d_state", "mamba_d_conv",
                                 "mamba_expand", "mamba_dt_rank")} \
        == {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
            "mamba_dt_rank": 160}
    for key in ("assumed", "departures", "deployment"):
        assert CONF[key]
    assert "memory_analysis" in CONF["serve"]["pool_arithmetic"]
    chk = CONF["serve"]["check"]
    assert (chk["prompts"], chk["prompt_len"], chk["decode_steps"]) \
        == (4, 640, 8) and chk["tolerance_why"]
    # the check's compared logits lie past the window's lower bound, the
    # ring's wrap and a page boundary
    last = chk["prompt_len"] + chk["decode_steps"] - 1
    assert chk["prompt_len"] > 528 and last // 16 > (chk["prompt_len"] - 1) // 16


def test_the_mix_is_issue_31s_letter_for_letter():
    want = {"kind": "serve_closed", "requests_per_client": 4,
            "order_seed": 31,
            "prompt": {"median": 256, "sigma": 0.6, "min": 128, "max": 1024},
            "output": {"median": 4096, "sigma": 0.6, "min": 1024,
                       "max": 12288},
            "max_queue_requests": 2, "max_queue_tokens": 16384,
            "trace": {"offset_s": 5.0, "seconds": 4.0}}
    assert {k: MIX[k] for k in want} == want
    serve = CONF["serve"]
    assert (serve["num_slots"], serve["max_len"]) == (128, 16400)
    assert serve["pool_tokens"] % 65536 == 0 and serve["pool_tokens"] >= 524288
    # the longest prompt set-up prefills, and the bucket it lands in
    top = MIX["prompt"]["max"] + MIX["output"]["max"] - 2
    assert top == 13310 and 8192 < top <= 16384 < serve["max_len"]
    cell = MAN.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "reason-sat", 1)


# -- the program against the reference ---------------------------------------

MATRICES = ("in_proj", "out_proj", "wqkv", "wq", "wo", "w1", "w2",
            "gate_up", "down", "lam")


def tiny(**set_):
    conf = rehearsal_of(CONF)
    conf["serve"] = {**conf["serve"], "set": {**conf["serve"]["set"], **set_}}
    family, cfg = build_config(conf, "serve")
    return conf, family, cfg, family.init_params(cfg, jax.random.PRNGKey(3))


def loud(params, by=6.0):
    """The matrices scaled so that a pre-activation at hidden 64 spreads as
    at hidden 2,560: at 0.02 and this width every branch is small."""
    def one(path, v):
        name = jax.tree_util.keystr(path[-1:]).strip("[]'")
        return (v * by).astype(v.dtype) if name in MATRICES else v
    return jax.tree_util.tree_map_with_path(one, params)


def test_forward_matches_the_reference():
    conf, family, cfg, params = tiny()
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 40))
    want = np.asarray(family.forward(loud(params), jnp.asarray(ids), cfg))
    for b in range(2):
        got = ARCH.logits_at(loud(params), jnp.asarray(ids[b]), conf,
                             np.arange(40))
        np.testing.assert_allclose(np.asarray(got), want[b], atol=2e-5)
    assert abs(float(ARCH.loss(params, jnp.asarray(ids[0]), conf))
               - np.log(cfg.vocab_size)) < 0.1


def test_prefill_then_decode_through_the_cache_matches_the_reference():
    """The engine's own programs: 40 prompt tokens (a window of 8 in a ring
    of 12, the pool's pages of 16: past the window, three wraps of the
    ring, two page boundaries), then 6 decode steps."""
    conf, family, cfg, params = tiny()
    assert (cfg.sliding_window, cfg.ring_tokens) == (8, 12)
    assert conf["serve"]["check"]["prompt_len"] > cfg.ring_tokens + 16
    out = check.serve_check(ARCH, family, cfg, conf, loud(params), 16, seed=7)
    assert out["ok"] and out["rms_err_over_rms"] < 1e-5
    assert out["logit_err_over_max"] < 1e-5


# One thing wrong in the reference, by a rewrite of its source: (what the
# reference's text has, what the faulty one has instead).
FAULTS = {
    "window_one_too_wide": ("(ahead < window)", "(ahead <= window)"),
    "no_lambda_vectors": ("jnp.exp(lq1 @ lk1) - jnp.exp(lq2 @ lk2) + l0",
                          "l0"),
    "no_sub_norm": ("""    d = d * jax.lax.rsqrt((d * d).mean(-1, keepdims=True)
                          + c["layer_norm_eps"]) * w["subln"]
""", """    d = d * w["subln"]
"""),
    "no_one_minus_lambda_init": ("return ((1.0 - l0) * d)", "return (d)"),
    "gmu_gate_without_silu": ('carry["m"] * jax.nn.silu(h @ w["w1"])',
                              'carry["m"] * (h @ w["w1"])'),
    "m_taken_after_the_gate": (
        'return (y * jax.nn.silu(z)) @ w["out_proj"], y',
        'return (y * jax.nn.silu(z)) @ w["out_proj"], y * jax.nn.silu(z)'),
    "cross_reads_a_window_layers_keys": (
        'if kind == "full":\n                carry["k"]',
        'if kind == "window":\n                carry["k"]'),
    "no_D": ('c_t @ state + w["D"] * x_t', "c_t @ state"),
    "no_dt_bias": ('@ w["dt_proj"] + w["dt_bias"])', '@ w["dt_proj"])'),
    "no_conv_tail": ("padded[j:j + s] for j in range(k))",
                     "padded[j:j + s] for j in range(k - 1, k))"),
    "no_layer_norm_bias": ("/ jnp.sqrt(var + eps) * g + b",
                           "/ jnp.sqrt(var + eps) * g"),
    "an_untied_head": ('head = params["embed"]',
                       'head = params["embed"][::-1]'),
    "heads_paired_otherwise": ("jnp.repeat(k.reshape(s, nkv // 2, 2, hd), 2, axis=1)",
                               "jnp.tile(k.reshape(s, nkv // 2, 2, hd), (1, 2, 1, 1))"),
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


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_seeded_weights_hide_no_dropped_term(fault):
    """With ``init_params``' deviations every kind of layer is a visible
    share of the logits: each term dropped or bent in the reference in turn
    moves them by more than one and a half times the cell's bands on the
    chip, so ``correct`` would be false (the weakest, LayerNorm's bias and
    a cross layer reading a window layer's keys, by 1.8 times). At the
    published widths: PERF.md section 6, PR 31."""
    conf, family, cfg, params = tiny()
    band = CONF["serve"]["check"]
    out = check.serve_check(faulty_reference(fault), family, cfg, conf,
                            loud(params), 16, seed=7)
    assert not out["ok"]
    assert out["rms_err_over_rms"] > 1.5 * band["rms_tolerance"], out
    assert out["logit_err_over_max"] > 1.3 * band["tolerance"], out


def wider(conf, **chk):
    """The rehearsal widened until bf16's error is measurable."""
    conf = copy.deepcopy(conf)
    conf.update(hidden_size=256, intermediate_size=512, num_attention_heads=8,
                num_key_value_heads=4, vocab_size=512, mamba_dt_rank=16)
    conf["serve"]["set"]["dtype"] = "bfloat16"
    conf["serve"]["check"].update(prompts=2, prompt_len=48, decode_steps=4,
                                  **chk)
    return conf


def test_a_band_a_quarter_over_bf16_fails_fp8_weights():
    """The chip's bands are 1.25 times what the bf16 program measured
    there. At a width the CPU can run, the same rule fails weights rounded
    to fp8: the nearest precision below would not pass as a faster bf16."""
    conf = wider(rehearsal_of(CONF), tolerance=1.0, rms_tolerance=1.0)
    family, cfg = build_config(conf, "serve")
    params = loud(family.init_params(cfg, jax.random.PRNGKey(3)), 3.0)
    bf16 = check.serve_check(ARCH, family, cfg, conf, params, 16, seed=7)
    assert 1e-3 < bf16["rms_err_over_rms"] < 0.1
    conf["serve"]["check"].update(
        tolerance=1.25 * bf16["logit_err_over_max"],
        rms_tolerance=1.25 * bf16["rms_err_over_rms"])
    assert check.serve_check(ARCH, family, cfg, conf, params, 16, seed=7)["ok"]
    rounded = jax.tree.map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
        if w.ndim >= 2 and w.dtype == jnp.bfloat16 else w, params)
    out = check.serve_check(ARCH, family, cfg, conf, rounded, 16, seed=7,
                            reference_params=params)
    assert not out["ok"]
    assert out["rms_err_over_rms"] > 2 * bf16["rms_err_over_rms"]


def test_a_bfloat16_state_fails_the_float32_programs_band():
    """The check with the program's Mamba state kept in bfloat16: after a
    prompt of 40 and 40 decode steps the logits stand further from the
    reference than the REHEARSAL's float32 band allows, and far further
    than with the float32 state. That is what the storage does to the
    logits; it is not a claim about the cell's bands on the chip, which a
    bfloat16 state would pass (as PR 27 found of Falcon-H1's): there the
    state's type is held by ``test_the_cache_the_program_keeps_...``."""
    conf, family, cfg, params = tiny()
    conf = copy.deepcopy(conf)
    conf["serve"]["check"].update(decode_steps=40)
    exact = check.serve_check(ARCH, family, cfg, conf, loud(params), 16,
                              seed=7)
    assert exact["ok"] and exact["rms_err_over_rms"] < 1e-5

    class Bf16State:
        layer, logits_at = ARCH.layer, ARCH.logits_at
        prefill, decode_step = ARCH.prefill, ARCH.decode_step

        @staticmethod
        def make_cache(cfg, pages, page_size, sequences):
            cache = ARCH.make_cache(cfg, pages, page_size, sequences)
            cache["state"]["ssm"] = cache["state"]["ssm"].astype(jnp.bfloat16)
            return cache

    out = check.serve_check(Bf16State, family, cfg, conf, loud(params), 16,
                            seed=7)
    assert not out["ok"]
    assert out["rms_err_over_rms"] > 100 * exact["rms_err_over_rms"]


@pytest.mark.parametrize("group, bucket", [(1, 128), (2, 48), (16, 32)])
def test_a_prefill_is_a_scan_a_segment_and_the_cross_decoder_on_one_position(
        group, bucket):
    """``cache_prefill`` traced (nothing runs) at the rehearsal's widths
    and 12 layers (3 Mamba / window pairs, 2 GMU / cross pairs): a scan
    over each run of pairs (a segment of one layer is that layer's call:
    the only other scan is its recurrence over the bucket's tokens), the
    cross-decoder's on [G, 1] positions whatever the bucket; a group wider
    than 8 rows is passes of 8 round them."""
    from paddle_tpu.inference import paged

    conf = rehearsal_of(CONF)
    conf["num_hidden_layers"] = 12
    family, cfg = build_config(conf, "serve")
    params = jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: ARCH.make_cache(cfg, 64, 16, group))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    traced = jax.make_jaxpr(
        lambda p, c, ids, rows, n, srows: paged.cache_prefill(
            family, p, ids, cfg, c, rows, n, srows))(
        params, cache, i32(group, bucket), i32(group, bucket // 16),
        i32(group), i32(group))
    scans = [e for e in traced.eqns if e.primitive.name == "scan"]
    if group > 8:
        assert [e.params["length"] for e in scans] == [group // 8]
        scans = [e for e in scans[0].params["jaxpr"].eqns
                 if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [3, bucket, 2]
    # the cross-decoder's hidden state has one position: no [G, S, D]
    # value enters the last scan, and the pairs before it carry one
    rows = min(group, 8)
    first, last = ({v.aval.shape for v in e.invars} for e in
                   (scans[0], scans[-1]))
    assert (rows, bucket, cfg.hidden_size) in first
    assert (rows, 1, cfg.hidden_size) in last
    assert (rows, bucket, cfg.hidden_size) not in last


# -- what PR 31 added, and that it edited nothing ------------------------------

NEW = ["prog.decode.shared_attn_ms", "prog.decode.window_ms",
       "kern.window_attn_roofline"]
FALCON = "falcon-h1-34b.decode-sat"


def test_the_cell_its_metrics_and_the_metrics_it_joined():
    """PR 31's entries are the last of each list, PR 27's the ones before
    them (what ``test_falcon_h1_cell.py``'s stale case held of PR 27's,
    found by name)."""
    doc = MAN.doc
    assert [w["name"] for w in doc["workloads"][-2:]] == [FALCON, CELL]
    assert [c["name"] for c in doc["configs"][-2:]] == ["falcon-h1-34b", NAME]
    assert [m["name"] for m in doc["per_layer"][-5:]] \
        == ["prog.decode.ssm_ms", "kern.ssm_update_roofline"] + NEW
    for m in doc["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    joined = {m["name"] for m in doc["per_layer"]
              if CELL in m.get("workloads", []) and m["workloads"] != [CELL]}
    assert joined == {
        "sched.occupancy_pct", "sched.host_ms_per_step",
        "dev.idle_pct.serve_sat", "prog.decode_chunk_step_ms",
        "prog.decode.dense_ms", "prog.decode.kv_write_ms",
        "prog.decode.unscoped_ms", "prog.decode.ssm_ms", "prog.mfu.serve",
        "kern.paged_attn_named_roofline", "kern.ssm_update_roofline"}
    for m in doc["end_to_end"] + doc["per_layer"]:
        cells = m.get("workloads", [])
        if CELL in cells and len(cells) > 1:
            assert cells[-1] == CELL                    # appended, not put in
            if FALCON in cells:
                assert cells[-2] == FALCON
    e2e = {m["name"] for m in MAN.metrics_of(CELL, "end_to_end")}
    assert e2e == {"serve_tok_s", "setup_s"}
    for name, work in (("kern.window_attn_roofline", "window_attn_bytes"),
                       ("kern.paged_attn_named_roofline", "paged_attn_bytes"),
                       ("kern.ssm_update_roofline", "ssm_state_bytes"),
                       ("prog.mfu.serve", "decode_step_flops")):
        assert MAN.layer_metric(name)["params"]["roofline"]["work"] == work
        assert callable(getattr(ARCH, work))         # found before trace_ops'
    for name, scope in (("prog.decode.shared_attn_ms", "attn.shared"),
                        ("prog.decode.window_ms", "attn.window")):
        spec = MAN.layer_metric(name)
        assert spec["params"]["through"] == scope and "sum" not in spec[
            "params"] and "cross-cut" in spec["what"]


def test_the_work_functions_count_the_pool_the_window_and_the_states():
    from benchmark.harness import trace_reduce as T
    from benchmark.readers import trace_ops

    ms = 1e6
    evs = [("/host:CPU", "main", T.WINDOW_SPAN, 0, 60 * ms)]
    evs += [("/device:TPU:0", T.OPS,
             f"%paged_decode_attn.{i} = bf16[128,10,16,128] custom-call()",
             (2 + 4 * i) * ms, 3 * ms) for i in range(4)]
    evs += [("/device:TPU:0", T.OPS,
             f"%paged_decode_attn_window.{i} = bf16[128,10,16,128] "
             f"custom-call()", (20 + 2 * i) * ms, 1 * ms) for i in range(4)]
    evs += [("/device:TPU:0", T.OPS,
             f"%ssm_state_update_s6.{i} = (f32[9,129,16,5120]) custom-call()",
             (30 + i) * ms, 0.5 * ms) for i in range(4)]
    evs += [("/device:TPU:0", T.MODULES, "jit_decode_chunk(3)", 1 * ms,
             40 * ms)]
    tokens, kv = 256, 700_000.0
    ctx = {"trace": T.Trace(evs), "architecture": ARCH, "config": CONF,
           "mix": {}, "peaks": {"hbm_bytes": 819e9, "flops": 197e12},
           "counters": {"traced_tokens_decoded": tokens,
                        "kv_token_steps": kv}}
    window_keys = min(kv, 512.0 * tokens)
    assert window_keys == 131_072
    window = 8 * 5_120 * window_keys / 819e9
    spec = MAN.layer_metric("kern.window_attn_roofline")["params"]
    assert trace_ops.read(spec, ctx) == pytest.approx(100 * window / 4e-3)
    # both names' calls, 16 ms: eight readers of the one pool and the window
    spec = MAN.layer_metric("kern.paged_attn_named_roofline")["params"]
    shared = 8 * 5_120 * kv / 819e9
    assert trace_ops.read(spec, ctx) == pytest.approx(
        100 * (shared + window) / 16e-3)
    spec = MAN.layer_metric("kern.ssm_update_roofline")["params"]
    least = tokens * 2 * 9 * 16 * 5_120 * 4 / 819e9
    assert trace_ops.read(spec, ctx) == pytest.approx(100 * least / 2e-3)
    spec = MAN.layer_metric("prog.mfu.serve")["params"]
    flops = tokens * (2.0 * CONF["param_count"] + 6.0 * 9 * 16 * 5_120) \
        + 15_360.0 * 8 * kv + 15_360.0 * 8 * window_keys
    assert trace_ops.read(spec, ctx) == pytest.approx(
        100 * flops / 197e12 / 40e-3)
    # a context shorter than the window reads what it holds, not 512
    ctx["counters"]["kv_token_steps"] = 1000.0
    spec = MAN.layer_metric("kern.window_attn_roofline")["params"]
    assert trace_ops.read(spec, ctx) == pytest.approx(
        100 * 8 * 5_120 * 1000.0 / 819e9 / 4e-3)


# (sha256, first 16 hex digits, of every file under the benchmark's paths at
# the commit PR 31 started from, 077c69d)
WAS_THERE = {
    "benchmark/__init__.py": "e3b0c44298fc1c14",
    "benchmark/architectures/__init__.py": "e3b0c44298fc1c14",
    "benchmark/architectures/deepseek_moe.py": "54c1457ed52452d0",
    "benchmark/architectures/dense_decoder.py": "c3f329c1bfe8ce06",
    "benchmark/architectures/falcon_h1.py": "d41791be8e93a987",
    "benchmark/configs/deepseek-moe-16b.json": "efd665c3e793e6e2",
    "benchmark/configs/falcon-h1-34b.json": "77b5b13cb0863b69",
    "benchmark/configs/mistral-7b-v0.3.json": "eedb5c7e8d66b33c",
    "benchmark/drivers/__init__.py": "e3b0c44298fc1c14",
    "benchmark/drivers/serve_closed.py": "c48c8a706ad61080",
    "benchmark/drivers/train.py": "92936015a0627c62",
    "benchmark/harness/__init__.py": "e3b0c44298fc1c14",
    "benchmark/harness/check.py": "d354a8c49b9fc360",
    "benchmark/harness/manifest.py": "cc2edf5ed59b61a0",
    "benchmark/harness/paged_calls.py": "7fb353133e745f2f",
    "benchmark/harness/reference.py": "207755a19ea79751",
    "benchmark/harness/serving.py": "273f104b508783f6",
    "benchmark/harness/session.py": "ee42ba777651921c",
    "benchmark/harness/trace_reduce.py": "346ccfe5585c0853",
    "benchmark/harness/traffic.py": "49cb9f6eaa4bdc4b",
    "benchmark/harness/work.py": "da7d800e23313b6d",
    "benchmark/layer_metrics/dev.idle_pct.serve_sat.json": "2ff9b8508afb8a59",
    "benchmark/layer_metrics/dev.idle_pct.train.json": "f611e9cb44b97031",
    "benchmark/layer_metrics/kern.flash_named_roofline.json": "6d7fe11ab8495be9",
    "benchmark/layer_metrics/kern.paged_attn_named_roofline.json": "aa4d5c56653ee7d9",
    "benchmark/layer_metrics/kern.ssm_update_roofline.json": "edd2dcd6158308a3",
    "benchmark/layer_metrics/prog.decode.dense_ms.json": "3bd269b68c5df474",
    "benchmark/layer_metrics/prog.decode.kv_write_ms.json": "08f435a0d3fb298d",
    "benchmark/layer_metrics/prog.decode.ssm_ms.json": "746b858fe3f9a34e",
    "benchmark/layer_metrics/prog.decode.unscoped_ms.json": "9baef8af090f105c",
    "benchmark/layer_metrics/prog.decode_chunk_step_ms.json": "c835dfc952d8e530",
    "benchmark/layer_metrics/prog.mfu.serve.json": "2a14ae832d7022df",
    "benchmark/layer_metrics/prog.mfu.train.json": "35e8b23a07dd5f77",
    "benchmark/layer_metrics/prog.train.attn_ms.json": "57085489f02601a4",
    "benchmark/layer_metrics/prog.train.ce_ms.json": "694f7b9cfeab7358",
    "benchmark/layer_metrics/prog.train.moe_ms.json": "0151defc0b3e1f7d",
    "benchmark/layer_metrics/prog.train.optim_ms.json": "84b0feb74efd898f",
    "benchmark/layer_metrics/prog.train.recompute_ms.json": "45a2b71a3f1c0875",
    "benchmark/layer_metrics/prog.train.unscoped_ms.json": "dca2d47d8443349b",
    "benchmark/layer_metrics/prog.train_step_ms.json": "12ce0d5e570948eb",
    "benchmark/layer_metrics/sched.host_ms_per_step.json": "b3f26aed2c600950",
    "benchmark/layer_metrics/sched.occupancy_pct.json": "89aaf978b6c589ba",
    "benchmark/readers/__init__.py": "e3b0c44298fc1c14",
    "benchmark/readers/counter.py": "db65719325fa9af2",
    "benchmark/readers/trace_host.py": "7082aefaf380f609",
    "benchmark/readers/trace_idle.py": "ab4d32c3593075a5",
    "benchmark/readers/trace_ops.py": "55a041dcd07111b3",
    "benchmark/readers/trace_scope.py": "3761db32fca18efc",
    "benchmark/run.py": "8a643481d1f649f4",
    "benchmark/testdata/chip_capture.json.gz": "73096028236cc515",
    "benchmark/testdata/chip_capture_named.json.gz": "27ef9400df6dffcf",
    "benchmark/traffic/decode-sat.json": "211319dd5fd432cf",
    "benchmark/traffic/train-4k.json": "16d12e59a796f7f3",
    "tests/benchmark/conftest.py": "755d0b193aff0d20",
    "tests/benchmark/test_architectures.py": "69b185e34d5f4bbf",
    "tests/benchmark/test_compile_v5e.py": "160146df6cb119cd",
    "tests/benchmark/test_compile_v5e_falcon_h1.py": "fa79acd7b2e5ff12",
    "tests/benchmark/test_falcon_h1_cell.py": "8a7fcca85ca7c6fa",
    "tests/benchmark/test_manifest.py": "270a6f9c2a284faf",
    "tests/benchmark/test_reference.py": "33a91f77a8923437",
    "tests/benchmark/test_trace_readers.py": "f42cad3c392b728d",
    "tests/benchmark/test_trace_reduce.py": "81336fd25cb01bf3",
    "tests/benchmark/test_traffic.py": "d122614da9ce8120"
}


def test_no_file_that_was_there_changed():
    for path, digest in WAS_THERE.items():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest()[:16] == digest, path


def test_benchmark_json_only_gained_entries():
    """Every entry the parent's ``BENCHMARK.json`` had is there unchanged,
    but for the cell's name appended to lists of ``workloads``."""
    import json
    import subprocess

    try:
        was = json.loads(subprocess.run(
            ["git", "show", "077c69d:BENCHMARK.json"], cwd=ROOT, check=True,
            capture_output=True, text=True).stdout)
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here to read the parent's file from")
    now = MAN.doc
    assert {k: now[k] for k in ("command", "paths", "run_seconds")} \
        == {k: was[k] for k in ("command", "paths", "run_seconds")}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(now[group]) >= len(was[group])
        for old, new in zip(was[group], now[group]):
            if new.get("workloads", [])[-1:] == [CELL]:
                new = {**new, "workloads": new["workloads"][:-1]}
            assert old == new, old["name"]
