"""An architecture is a file: the harness finds a configuration's plain
reference, its counts, the serve check's calls into the program and its
kernels' work in ``benchmark/architectures/<name>.py`` by the name in the
configuration's file. One more of them, with its configuration, mix,
cell, work function and metric, is files and appended entries; its
reference is the one that judges its cell; a cache with more in it than
keys and values goes through the check unopened."""
import os
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark.architectures import dense_decoder
from benchmark.harness import check, trace_reduce as T, work
from benchmark.harness.manifest import Manifest, build_config, plugin
from benchmark.readers import counter, trace_ops
from benchmark.run import rehearsal_of
from test_manifest import DOC, MAN, one_more_of_each, rehearsed_both_ways
from test_traffic import rehearse

# What a ``model_config`` PR writes: the dense decoder's pieces under
# another name, a work function of its own for a kernel only it has, and
# (``{attention}``) its own attention.
MODULE = '''"""A test's architecture: the dense decoder, by another module."""
import math

import jax
import jax.numpy as jnp

from ..harness import reference as R
from ..harness import work
from ..harness.paged_calls import decode_step, make_cache, prefill  # noqa
from .dense_decoder import (kv_bytes_per_token, model_flops_per_token,  # noqa
                            param_count, state_bytes_per_slot)

{attention}

def layer(x, w, c):
    w = jax.tree.map(lambda a: a.astype(R.F32), w)
    x = x.astype(R.F32)
    x = x + attention(R.rms_norm(x, w["ln1"], c["rms_norm_eps"]), w, c)
    h = R.rms_norm(x, w["ln2"], c["rms_norm_eps"])
    return x + R.swiglu(h, w["gate"], w["up"], w["down"]), \\
        jnp.zeros((), R.F32)


logits_at, loss = R.decoder_of(layer)


def scan_state_bytes(params, ctx, trace):
    """(least seconds, the calls' seconds) of a kernel this architecture
    alone has: a state of 1,000 bytes read and written by each call."""
    secs, n = trace.matching(params["line"], params["pattern"], whole=True)
    return 2 * 1000 * n / ctx["peaks"]["hbm_bytes"], secs
'''
AS_PUBLISHED = "attention = R.attention\n"
NO_ROPE = '''
def attention(x, w, c):
    """Grouped-query attention with the rotary embedding forgotten."""
    s, d = x.shape
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nh
    q = (x @ w["wq"]).reshape(s, nh, hd)
    k = (x @ w["wk"]).reshape(s, nkv, hd)
    v = (x @ w["wv"]).reshape(s, nkv, hd)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    score = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    score = jnp.where(jnp.tril(jnp.ones((s, s), bool)), score, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, -1), v)
    return out.reshape(s, nh * hd) @ w["wo"]
'''


def test_one_more_architecture_is_files_and_appended_entries(tmp_path):
    """As ``test_one_more_of_each_...``, with an architecture module among
    the files added: the new cell is judged by it."""
    root = str(tmp_path)
    cell, before = one_more_of_each(
        root, ("another_arch", MODULE.replace("{attention}", AS_PUBLISHED)))
    lines = rehearsed_both_ways(root, cell, before)
    assert "architecture another_arch: benchmark.architectures.another_arch" \
        in lines[0]


def test_the_configurations_own_reference_judges_its_cell(tmp_path):
    """The same module with one thing wrong in its ``layer`` (no rope):
    the program is right, the reference is not, and the run says
    ``correct: false``. A check that used a default reference would pass."""
    root = str(tmp_path)
    cell, _ = one_more_of_each(
        root, ("another_arch", MODULE.replace("{attention}", NO_ROPE)))
    _, last = rehearse(cell, 0, root=root)
    assert last["correct"] is False
    c = last["compared"]
    assert c["rms_err_over_rms"]["value"] > 10 * c["rms_err_over_rms"]["limit"]
    assert c["requests_failed"] == {"value": 0, "limit": 0}


# -- kernel work, found by name -------------------------------------------

D0, MS = "/device:TPU:0", 1e6


def kernel_trace():
    """Three whole 2 ms calls of ``ssm_scan`` inside a 30 ms window."""
    evs = [("/host:CPU", "main", T.WINDOW_SPAN, 0, 30 * MS)]
    for i in range(3):
        evs.append((D0, T.OPS, f"%ssm_scan.{i} = bf16[8]{{0}} custom-call()",
                    (3 + 8 * i) * MS, 2 * MS))
    return T.Trace(evs)


@pytest.fixture()
def another_arch(tmp_path):
    """The new module, loaded from a copy's directory as a run would."""
    one_more_of_each(str(tmp_path), (
        "arch_for_trace", MODULE.replace("{attention}", AS_PUBLISHED)))
    yield Manifest(str(tmp_path)).architecture(
        {"architecture": "arch_for_trace"})
    sys.modules.pop("benchmark.architectures.arch_for_trace")


def test_kernel_work_is_found_in_the_architectures_module(another_arch,
                                                          capsys):
    params = {"line": T.OPS, "pattern": "^%ssm_scan",
              "roofline": {"work": "scan_state_bytes"}}
    ctx = {"trace": kernel_trace(), "counters": {}, "config": {}, "mix": {},
           "architecture": another_arch, "peaks": {"hbm_bytes": 2e6}}
    # 3 calls x 2,000 bytes at 2 MB/s are 3 ms at best, and took 6 ms
    assert trace_ops.read(params, ctx) == pytest.approx(50.0)
    assert "NOTHING" not in capsys.readouterr().out
    # the same name under an architecture that has no such function, and
    # a name nobody has: nothing is read, and the run says why
    for arch, name in ((dense_decoder, "scan_state_bytes"),
                       (another_arch, "no_such_work")):
        params["roofline"]["work"] = name
        assert trace_ops.read(params, {**ctx, "architecture": arch}) is None
        out = capsys.readouterr().out
        assert "NOTHING computes the work" in out and name in out


def test_an_architectures_function_comes_before_the_readers_own(capsys):
    """``paged_attn_bytes`` is one of ``trace_ops``' own; an architecture
    that counts its kernel's bytes otherwise gives one of that name."""
    own = types.SimpleNamespace(
        kv_bytes_per_token=lambda c: 100,
        paged_attn_bytes=lambda params, ctx, trace: (0.003, 0.004))
    plain = types.SimpleNamespace(kv_bytes_per_token=lambda c: 100)
    params = {"line": T.OPS, "pattern": "^%ssm_scan",
              "roofline": {"work": "paged_attn_bytes"}}
    ctx = {"trace": kernel_trace(), "counters": {"kv_token_steps": 60},
           "config": {}, "mix": {}, "peaks": {"hbm_bytes": 2e6}}
    assert trace_ops.read(params, {**ctx, "architecture": own}) \
        == pytest.approx(75.0)
    # the reader's own: 60 token-steps x 100 B at 2 MB/s are 3 ms, of 6
    assert trace_ops.read(params, {**ctx, "architecture": plain}) \
        == pytest.approx(50.0)


def test_the_whole_steps_share_of_the_peak():
    """``prog.mfu.train`` and ``prog.mfu.serve`` from their files, on
    hand-written events: the architecture's counts over the program's
    time. A kernel's roofline that moves the same end-to-end metric can
    be no larger than this share lets it."""
    arch = types.SimpleNamespace(
        model_flops_per_token=lambda c, s: 1000.0 * s,
        param_count=lambda c, active=False: 500 if active else 900)
    evs = [("/host:CPU", "main", T.WINDOW_SPAN, 0, 30 * MS),
           (D0, T.MODULES, "jit_step(1)", -6 * MS, 8 * MS)]    # cut
    evs += [(D0, T.MODULES, "jit_step(1)", (3 + 8 * i) * MS, 8 * MS)
            for i in range(3)]
    evs += [(D0, T.MODULES, "jit_decode_chunk(2)", 27 * MS, 2 * MS)]
    ctx = {"trace": T.Trace(evs), "architecture": arch, "config": {
        "num_hidden_layers": 2, "num_attention_heads": 4, "hidden_size": 8},
        "mix": {"batch": 2, "seq_len": 4}, "peaks": {"flops": 4e6},
        "counters": {"traced_tokens_decoded": 6, "kv_token_steps": 10}}
    spec = MAN.layer_metric("prog.mfu.train")["params"]
    # 3 whole steps x 8 tokens x 4,000 FLOPs = 96 kFLOP: 24 ms at the
    # peak, and the three steps took 24 ms
    assert trace_ops.read(spec, ctx) == pytest.approx(100.0)
    spec = MAN.layer_metric("prog.mfu.serve")["params"]
    # 6 tokens x 2 x 500 + 4 x 10 x 2 layers x 4 heads x 2 = 6,640 FLOPs:
    # 1.66 ms at the peak, in a 2 ms program
    assert trace_ops.read(spec, ctx) == pytest.approx(83.0)


# -- the counts ------------------------------------------------------------

def test_a_head_size_stated_by_the_file_is_the_one_counted():
    """Hidden 5,120 with 20 query and 4 key/value heads of 128 (hidden //
    heads would say 256), 6 layers: 12,288 B of keys and values a token,
    31.46 M attention parameters a layer."""
    c = {"hidden_size": 5120, "num_attention_heads": 20,
         "num_key_value_heads": 4, "head_dim": 128, "num_hidden_layers": 6,
         "intermediate_size": 21504, "vocab_size": 261120}
    assert work.head_dim(c) == 128
    assert work.kv_bytes_per_token(c) == 12288
    assert dense_decoder.kv_bytes_per_token(c) == 12288
    assert work.attn_params(c) == 5120 * 128 * 28 + 20 * 128 * 5120 \
        == 31_457_280
    assert work.flash_shape(c, {"batch": 1, "seq_len": 8})["head_dim"] == 128
    # attention's FLOPs a trained token follow heads x head_dim, not hidden
    assert work.train_flops_per_token(c, 0, 10) == 6.0 * 6 * 10 * 20 * 128
    # without the key, as before
    del c["head_dim"]
    assert work.head_dim(c) == 256 and work.kv_bytes_per_token(c) == 24576


def test_the_reference_attends_with_the_files_head_size():
    """Four heads of 2 over a hidden size of 16: the projections are
    [16, 8] and the output is read back through ``wo`` [8, 16]."""
    from benchmark.harness import reference as R

    c = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
         "rope_theta": 1e4}
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    w = {"wq": jax.random.normal(k[0], (16, 8)),
         "wk": jax.random.normal(k[1], (16, 4)),
         "wv": jax.random.normal(k[2], (16, 4)),
         "wo": jax.random.normal(k[3], (8, 16))}
    assert R.attention(jax.random.normal(k[4], (5, 16)), w, c).shape \
        == (5, 16)


@pytest.mark.parametrize("name", [c["name"] for c in DOC["configs"]])
def test_present_configurations_keep_no_state_beside_keys_and_values(name):
    conf = MAN.config(name)
    arch = MAN.architecture(conf)
    assert arch.state_bytes_per_slot(conf) == 0
    for fn in ("layer", "logits_at", "loss", "param_count",
               "kv_bytes_per_token", "model_flops_per_token", "make_cache",
               "prefill", "decode_step"):
        assert callable(getattr(arch, fn)), fn


def test_an_architecture_is_named_and_never_defaulted():
    conf = MAN.config("mistral-7b-v0.3")
    del conf["architecture"]
    with pytest.raises(KeyError):
        MAN.architecture(conf)
    with pytest.raises(ModuleNotFoundError):
        MAN.architecture({"architecture": "no_such_architecture"})
    assert plugin("architectures", "dense_decoder") is dense_decoder
    with open(os.path.join(MAN.bench_dir, "harness", "check.py")) as f:
        text = f.read()
    assert "reference" not in [
        w for line in text.splitlines() if line.startswith(("import", "from"))
        for w in line.replace(",", " ").replace(".", " ").split()]


# -- the cache is the architecture's own ------------------------------------

def test_a_cache_with_a_third_leaf_goes_through_the_check_unopened():
    """A stub architecture whose calls wrap the present ones and carry a
    count of the calls made as a third leaf. The logits are disturbed
    unless the leaf arrives as the last call left it: the check passes,
    so it handed the whole pytree back each time."""
    conf = rehearsal_of(MAN.config("mistral-7b-v0.3"))
    family, cfg = build_config(conf, "serve")
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    made = []

    class Counting(types.SimpleNamespace):
        layer, logits_at = dense_decoder.layer, dense_decoder.logits_at

        @staticmethod
        def make_cache(cfg, num_pages, page_size, sequences):
            cache = dense_decoder.make_cache(cfg, num_pages, page_size,
                                             sequences)
            made.append(sequences)
            return {"kv": cache, "calls": jnp.zeros((sequences,), jnp.int32)}

        @staticmethod
        def prefill(family, params, ids, cfg, cache, rows, slen):
            kv, logits = dense_decoder.prefill(family, params, ids, cfg,
                                               cache["kv"], rows, slen)
            off = (cache["calls"] != 0).astype(logits.dtype)[:, None]
            return {"kv": kv, "calls": cache["calls"] + 1}, logits + off

        @staticmethod
        def decode_step(family, params, cache, tables, lengths, tokens, cfg):
            kv, logits = dense_decoder.decode_step(
                family, params, cache["kv"], tables, lengths, tokens, cfg)
            # the step's number, by the lengths the check passes
            plen = conf["serve"]["check"]["prompt_len"]
            off = (cache["calls"] != lengths - plen).astype(logits.dtype)
            return {"kv": kv, "calls": cache["calls"] + 1}, \
                logits + off[:, None]

    good = check.serve_check(Counting, family, cfg, conf, params, 16, seed=7)
    assert good["ok"] and good["logit_err_over_max"] < 1e-4
    assert made == [conf["serve"]["check"]["prompts"]]
    assert set(good["numbers"]) == {"logit_err_over_max", "rms_err_over_rms"}

    class Forgetful(Counting):
        @staticmethod
        def decode_step(family, params, cache, tables, lengths, tokens, cfg):
            # (what a check that rebuilt the cache from its halves would do)
            cache = {**cache, "calls": jnp.zeros_like(cache["calls"])}
            return Counting.decode_step(family, params, cache, tables,
                                        lengths, tokens, cfg)

    assert not check.serve_check(Forgetful, family, cfg, conf, params, 16,
                                 seed=7)["ok"]


# -- a counter joins by an entry --------------------------------------------

def test_every_public_number_of_the_engines_stats_is_a_counter():
    from benchmark.harness.serving import Serving
    from paddle_tpu.inference.engine import EngineStats

    stats = EngineStats()
    stats.shed, stats.decode_steps, stats.tokens_decoded = 3, 5, 17
    stats.added_by_a_later_pr = 7          # (a program PR's new counter)
    stats.note, stats.flag = "not a number", True
    sv = types.SimpleNamespace(eng=types.SimpleNamespace(stats=stats),
                               slots=4)
    c = Serving.counters(sv)
    # the nine the harness listed by hand until PR 26 keep their names
    assert set(c) >= {"engine." + k for k in (
        "admitted", "completed", "preempted", "decode_steps",
        "tokens_generated", "tokens_decoded", "tokens_prefilled",
        "tokens_discarded", "peak_pages_in_use")} | {"engine.slot_steps"}
    assert c["engine.shed"] == 3 and c["engine.added_by_a_later_pr"] == 7
    assert c["engine.slot_steps"] == 20
    assert not any(k.startswith("engine._") for k in c)
    assert "engine.note" not in c and "engine.flag" not in c
    assert counter.read({"counter": "engine.tokens_decoded",
                         "over": "engine.slot_steps", "scale": 100.0},
                        {"counters": c}) == pytest.approx(85.0)
