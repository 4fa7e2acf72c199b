"""The cell ``falcon-h1-34b.decode-sat`` and what came with it: the file's
stated counts against the architecture's module and the program's own
parameter tree and state, the plain reference against the program through
the cache (``check.serve_check``), the controls that show the seeded
weights hide no fault (each dropped term of the block, every multiplier,
weights in fp8, a state in bfloat16), and that PR 27 added files and
appended entries and edited no file the benchmark had."""
import copy
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check, reference as R
from benchmark.harness.manifest import ROOT, Manifest, build_config
from benchmark.run import rehearsal_of

MAN = Manifest()
NAME, CELL = "falcon-h1-34b", "falcon-h1-34b.decode-sat"
CONF = MAN.config(NAME)
ARCH = MAN.architecture(CONF)


# -- the counts ------------------------------------------------------------

def test_the_counts_are_issue_27s_arithmetic():
    """A block is 430,120,032 parameters (the mixer 68,351,072, attention
    31,457,280, the feed-forward 330,301,440, two norms), the tables
    2 x 261,120 x 5,120, six blocks with them 5,254,594,112."""
    c = CONF
    assert ARCH.mixer_params(c) == 68_351_072 == (
        5120 * 9248 + 4096 * 5120 + 5120 * 4 + 5120 + 4096 + 96)
    assert ARCH.layer_params(c) == 430_120_032
    assert ARCH.param_count(c) == c["param_count"] == 5_254_594_112
    assert ARCH.param_count(c, True) == c["active_param_count"] \
        == 5_254_594_112 - 261_120 * 5_120
    assert ARCH.kv_bytes_per_token(c) == c["kv_bytes_per_token"] == 12_288
    assert ARCH.state_bytes_per_slot(c) == c["state_bytes_per_slot"] \
        == 6 * (32 * 128 * 256 * 4 + 5120 * 3 * 2) == 25_350_144
    # the recurrence: 6 FLOPs a state element a layer a token, forward
    assert ARCH.recurrence_flops_per_token(c) == 6.0 * 6 * 32 * 128 * 256
    dense = 6.0 * c["active_param_count"] + 6.0 * 6 * 1536 * 20 * 128
    assert ARCH.model_flops_per_token(c, 1536) == dense + 3 * 6.0 * 6 * 2 ** 20


def test_the_state_the_program_keeps_is_the_state_the_file_counts():
    """``state_bytes_per_slot`` against the leaves ``init_pool`` makes for
    the configuration's own config object: every layer's bytes of one row,
    and one row more than slots (the row nobody owns)."""
    family, cfg = build_config(CONF, "serve")
    slots = CONF["serve"]["num_slots"]
    cache = jax.eval_shape(lambda: ARCH.make_cache(cfg, 16, 16, slots))
    assert set(cache) == {"k", "v", "state"}
    leaves = cache["state"]
    assert leaves["ssm"].shape == (6, slots + 1, 32, 256, 128)
    assert leaves["ssm"].dtype == jnp.float32
    assert leaves["conv"].shape == (6, slots + 1, 3, 5120)
    assert leaves["conv"].dtype == jnp.bfloat16
    a_row = sum(a.dtype.itemsize * int(np.prod(a.shape)) // (slots + 1)
                for a in leaves.values())
    assert a_row == CONF["state_bytes_per_slot"]
    pool = CONF["serve"]["pool_tokens"] * CONF["kv_bytes_per_token"]
    held = 2 * CONF["param_count"] + a_row * (slots + 1) + pool
    assert 0.80 < held / (15.75 * 2 ** 30) < 0.90      # before any temporary


@pytest.mark.parametrize("group, bucket, passes", [
    (4, 512, 1), (8, 128, 1), (112, 128, 14), (128, 128, 16)])
def test_a_prefill_group_at_the_published_state_is_passes_of_eight(
        group, bucket, passes):
    """``cache_prefill`` traced (nothing runs) at the configuration's own
    widths, with the constant as the program has it: the groups the window
    forms are one pass over the blocks, the warm-up's group of every slot
    is passes of 8 rows (the first rule divided a budget by a row's bytes,
    conv tail included, and sent every group through a row at a time)."""
    from paddle_tpu.inference import paged

    family, cfg = build_config(CONF, "serve")
    params = jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: ARCH.make_cache(cfg, 64, 16, group))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    traced = jax.make_jaxpr(
        lambda p, c, ids, rows, n, srows: paged.cache_prefill(
            family, p, ids, cfg, c, rows, n, srows))(
        params, cache, i32(group, bucket), i32(group, bucket // 16),
        i32(group), i32(group))
    outer = [e for e in traced.eqns if e.primitive.name == "scan"]
    layers = CONF["num_hidden_layers"]
    if passes == 1:
        assert [e.params["length"] for e in outer] == [layers]
    else:
        assert [e.params["length"] for e in outer] == [passes]
        assert f"length={layers}" in str(outer[0].params["jaxpr"])


def test_every_width_is_the_sources_and_depth_alone_is_cut():
    assert CONF["reduced"] == ["num_hidden_layers"]
    assert CONF["source_values"] == {"num_hidden_layers": 72}
    want = {"hidden_size": 5120, "intermediate_size": 21504, "head_dim": 128,
            "num_attention_heads": 20, "num_key_value_heads": 4,
            "mamba_d_ssm": 4096, "mamba_n_heads": 32, "mamba_d_head": 128,
            "mamba_n_groups": 2, "mamba_d_state": 256, "mamba_d_conv": 4,
            "mamba_chunk_size": 128, "vocab_size": 261120,
            "rope_theta": 1e11, "key_multiplier": 0.011048543456039804,
            "attention_out_multiplier": 0.0375,
            "lm_head_multiplier": 0.0078125}
    assert {k: CONF[k] for k in want} == want
    for key in ("assumed", "departures", "deployment", "tolerance_why"):
        assert key in CONF or key in CONF["serve"]["check"]
    assert "memory_analysis" in CONF["serve"]["pool_arithmetic"]


# -- the program against the reference ---------------------------------------

def tiny(**set_):
    conf = rehearsal_of(CONF)
    conf["serve"] = {**conf["serve"], "set": {**conf["serve"]["set"], **set_}}
    family, cfg = build_config(conf, "serve")
    return conf, family, cfg, family.init_params(cfg, jax.random.PRNGKey(3))


def loud(params, by=8.0):
    """The matrices scaled so that a pre-activation at hidden 64 spreads
    as at hidden 5,120 (64 x (8 x .02)^2 against 5120 x .02^2): at 0.02
    and this width every branch is small and the state adds nothing."""
    layers = {k: (v * by).astype(v.dtype)
              if v.ndim == 3 and k != "conv_w" else v
              for k, v in params["layers"].items()}
    return dict(params, layers=layers)


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
    """The engine's own programs, the state in its rows: 16 prompt tokens
    (a chunk of 12: one whole chunk and a part), then 3 decode steps."""
    conf, family, cfg, params = tiny()
    assert cfg.mamba_chunk_size == 12 and cfg.mamba_n_groups == 2
    out = check.serve_check(ARCH, family, cfg, conf, loud(params), 16, seed=7)
    assert out["ok"] and out["rms_err_over_rms"] < 1e-5
    assert out["logit_err_over_max"] < 1e-5


def faulty(fault, conf, params, monkeypatch):
    """The reference with one thing wrong, or other weights: (conf,
    reference weights). The program stays right."""
    layers = params["layers"]

    def relayer(**kw):
        return dict(params, layers=dict(layers, **kw))

    if fault == "no_rope":
        monkeypatch.setattr(R, "rotary", lambda x, theta: x)
    elif fault == "no_conv":                  # the newest tap alone, of 1
        return conf, relayer(conv_w=jnp.zeros_like(
            layers["conv_w"]).at[:, -1].set(1.0))
    elif fault == "no_dt_bias":
        return conf, relayer(dt_bias=jnp.zeros_like(layers["dt_bias"]))
    elif fault == "no_carried_state":         # exp(dt * A) = 0: H_t forgets
        return conf, relayer(A_log=jnp.full_like(layers["A_log"], 60.0))
    elif fault == "no_skip":
        return conf, relayer(D=jnp.zeros_like(layers["D"]))
    elif "[" in fault:                        # one entry of a list set to 1
        key, i = fault[:-1].split("[")
        value = list(conf[key])
        value[int(i)] = 1.0
        return {**conf, key: value}, params
    else:                                     # a multiplier set to 1
        return {**conf, fault: 1.0}, params
    return conf, params


FAULTS = ["no_rope", "no_conv", "no_dt_bias", "no_carried_state", "no_skip",
          "attention_in_multiplier", "attention_out_multiplier",
          "key_multiplier", "embedding_multiplier", "lm_head_multiplier",
          "ssm_in_multiplier", "ssm_out_multiplier", "mlp_multipliers[0]",
          "mlp_multipliers[1]"] + [f"ssm_multipliers[{i}]" for i in range(5)]


@pytest.mark.parametrize("fault", FAULTS)
def test_seeded_weights_hide_no_dropped_term(fault, monkeypatch):
    """With the per-leaf deviations of ``init_params`` every branch is a
    visible share of a block: each term dropped from the reference in turn
    (and each multiplier set to 1) moves the logits by more than twice the
    chip's band, so ``correct`` would be false. (``attention_in_multiplier``
    is 1 at 34B, where dropping it changes nothing; the rehearsal's is
    not.) At the published widths, two blocks: PERF.md section 6, PR 27."""
    conf, family, cfg, params = tiny()
    conf = {**conf, "attention_in_multiplier": 0.5}
    family, cfg = build_config(conf, "serve")
    band = CONF["serve"]["check"]
    bad_conf, ref_params = faulty(fault, conf, loud(params), monkeypatch)
    out = check.serve_check(ARCH, family, cfg, bad_conf, loud(params), 16,
                            seed=7, reference_params=ref_params)
    assert not out["ok"]
    assert out["rms_err_over_rms"] > 2 * band["rms_tolerance"], out
    assert out["logit_err_over_max"] > 2 * band["tolerance"], out


def wider(conf, **chk):
    """The rehearsal widened until bf16's error is measurable."""
    conf = copy.deepcopy(conf)
    conf.update(hidden_size=256, intermediate_size=512, num_hidden_layers=3,
                vocab_size=512, mamba_d_ssm=256, mamba_n_heads=8,
                mamba_d_head=32, mamba_d_state=32)
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
    params = loud(family.init_params(cfg, jax.random.PRNGKey(3)), 4.0)
    bf16 = check.serve_check(ARCH, family, cfg, conf, params, 16, seed=7)
    assert 1e-3 < bf16["rms_err_over_rms"] < 0.05
    conf["serve"]["check"].update(
        tolerance=1.25 * bf16["logit_err_over_max"],
        rms_tolerance=1.25 * bf16["rms_err_over_rms"])
    assert check.serve_check(ARCH, family, cfg, conf, params, 16, seed=7)["ok"]
    rounded = jax.tree.map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
        if w.ndim >= 2 else w, params)
    out = check.serve_check(ARCH, family, cfg, conf, rounded, 16, seed=7,
                            reference_params=params)
    assert not out["ok"]
    assert out["rms_err_over_rms"] > 2 * bf16["rms_err_over_rms"]


def test_a_bfloat16_state_fails_the_float32_programs_band():
    """The check with the program's state kept in bfloat16 (the public
    cache's type): after a prompt of 16 and 40 decode steps the logits
    stand further from the reference than the REHEARSAL's float32 band
    allows, and hundreds of times further than with the float32 state.
    That is what the storage does to the logits; it is not a claim about
    the cell's bands on the chip (0.046 / 0.0525 over 8 steps of a bf16
    program), which a bfloat16 state would pass: there the state's type is
    held by ``test_the_state_the_program_keeps_is_the_state_the_file_
    counts`` alone (the configuration's ``tolerance_why`` says so)."""
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


# -- what PR 27 added, and that it edited nothing ------------------------------

def test_the_cell_its_metrics_and_the_metrics_it_joined():
    doc = MAN.doc
    assert MAN.cell(CELL) == doc["workloads"][-1]
    assert doc["configs"][-1]["name"] == NAME
    assert [m["name"] for m in doc["per_layer"][-2:]] \
        == ["prog.decode.ssm_ms", "kern.ssm_update_roofline"]
    for m in doc["per_layer"][-2:]:
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
        if CELL in m.get("workloads", []) and len(m["workloads"]) > 1:
            assert m["workloads"][-1] == CELL           # appended, not put in
    # the two work functions its metrics name are the architecture's own
    assert MAN.layer_metric("kern.ssm_update_roofline")["params"][
        "roofline"]["work"] == "ssm_state_bytes"
    assert callable(ARCH.ssm_state_bytes) and callable(ARCH.decode_step_flops)


def test_the_work_functions_count_the_state_and_the_recurrence():
    from benchmark.harness import trace_reduce as T
    from benchmark.readers import trace_ops

    ms = 1e6
    evs = [("/host:CPU", "main", T.WINDOW_SPAN, 0, 40 * ms)]
    evs += [("/device:TPU:0", T.OPS,
             f"%ssm_state_update.{i} = (f32[6,129,32,256,128]) custom-call()",
             (2 + 3 * i) * ms, 2 * ms) for i in range(6)]
    evs += [("/device:TPU:0", T.MODULES, "jit_decode_chunk(3)", 1 * ms,
             30 * ms)]
    tokens = 100
    ctx = {"trace": T.Trace(evs), "architecture": ARCH, "config": CONF,
           "mix": {}, "peaks": {"hbm_bytes": 819e9, "flops": 197e12},
           "counters": {"traced_tokens_decoded": tokens,
                        "kv_token_steps": 50_000}}
    spec = MAN.layer_metric("kern.ssm_update_roofline")["params"]
    # 100 tokens x 2 x 6 layers x 4 MiB over 819 GB/s, in 12 ms of calls
    least = tokens * 2 * 6 * 32 * 128 * 256 * 4 / 819e9
    assert trace_ops.read(spec, ctx) == pytest.approx(100 * least / 12e-3)
    spec = MAN.layer_metric("prog.mfu.serve")["params"]
    flops = tokens * (2.0 * CONF["active_param_count"] + 6.0 * 6 * 2 ** 20) \
        + 4.0 * 50_000 * 6 * 20 * 128
    assert trace_ops.read(spec, ctx) \
        == pytest.approx(100 * flops / 197e12 / 30e-3)


# (sha256, first 16 hex digits, of every file under the benchmark's paths at
# the commit PR 27 started from)
WAS_THERE = {
    "benchmark/__init__.py": "e3b0c44298fc1c14",
    "benchmark/architectures/__init__.py": "e3b0c44298fc1c14",
    "benchmark/architectures/deepseek_moe.py": "54c1457ed52452d0",
    "benchmark/architectures/dense_decoder.py": "c3f329c1bfe8ce06",
    "benchmark/configs/deepseek-moe-16b.json": "efd665c3e793e6e2",
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
    "benchmark/layer_metrics/prog.decode.dense_ms.json": "3bd269b68c5df474",
    "benchmark/layer_metrics/prog.decode.kv_write_ms.json": "08f435a0d3fb298d",
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
    "tests/benchmark/test_architectures.py": "69b185e34d5f4bbf",
    "tests/benchmark/test_compile_v5e.py": "160146df6cb119cd",
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
