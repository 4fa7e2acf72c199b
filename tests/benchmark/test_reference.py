"""The plain reference against the program's own forward pass and loss at
tiny widths on the CPU, float32, for both architectures, each through the
module its configuration's file names: the dense block, and the expert
block with the capacity rule and its drops."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.architectures import deepseek_moe, dense_decoder
from benchmark.harness import check, reference as R
from benchmark.harness.manifest import Manifest, build_config
from benchmark.run import rehearsal_of

MAN = Manifest()
DENSE, MOE = (MAN.architecture(MAN.config(c))
              for c in ("mistral-7b-v0.3", "deepseek-moe-16b"))


def tiny(name, block):
    conf = rehearsal_of(MAN.config(name))
    family, cfg = build_config(conf, block)
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    return conf, family, cfg, params


def test_each_configuration_names_its_architecture():
    assert DENSE is dense_decoder and MOE is deepseek_moe


def test_dense_forward_matches_models_llama():
    conf, family, cfg, params = tiny("mistral-7b-v0.3", "serve")
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 40))
    want = np.asarray(family.forward(params, jnp.asarray(ids), cfg))
    for b in range(2):
        got = DENSE.logits_at(params, jnp.asarray(ids[b]), conf,
                              np.arange(40))
        np.testing.assert_allclose(np.asarray(got), want[b], atol=2e-5)


def test_grouped_query_heads_are_grouped_as_published():
    """Query head h reads KV head h // (heads / kv_heads): changing KV
    head 1 must leave the output of query heads 0 and 1 alone."""
    c = {"num_attention_heads": 4, "num_key_value_heads": 2,
         "rope_theta": 1e4}
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    w = {"wq": jax.random.normal(k[0], (16, 16)),
         "wk": jax.random.normal(k[1], (16, 8)),
         "wv": jax.random.normal(k[2], (16, 8)),
         "wo": jnp.eye(16)}
    x = jax.random.normal(k[3], (5, 16))
    a = R.attention(x, w, c)
    w2 = dict(w, wv=w["wv"].at[:, 4:].add(1.0))
    b = R.attention(x, w2, c)
    np.testing.assert_allclose(a[:, :8], b[:, :8], atol=1e-6)
    assert not np.allclose(a[:, 8:], b[:, 8:])


def test_moe_loss_and_gradient_match_models_moe_with_drops():
    conf, family, cfg, params = tiny("deepseek-moe-16b", "train")
    assert cfg.dispatch_mode is None          # capacity dispatch, as run
    out = check.train_check(MOE, family, cfg, conf, params, seed=5)
    assert out["ok"], out
    assert out["loss_abs_diff"] < 1e-5 and out["grad_norm_rel_diff"] < 1e-4
    assert out["worst_leaf_norm_rel_diff"] < 1e-4
    assert set(out["leaf_norm_rel_diff"]) >= {"['layers']['router']",
                                              "['embed']", "['ln_f']"}
    # and the capacity rule does drop at this size, so the agreement is
    # about the drops too: with room for every slot the loss differs
    roomy = dict(conf, capacity_factor=8.0)
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, 33)
    tight = float(MOE.loss(params, jnp.asarray(ids), conf))
    loose = float(MOE.loss(params, jnp.asarray(ids), roomy))
    assert abs(tight - loose) > 1e-6
    # likewise the renormalised weights of the chosen experts: the file
    # keeps the source's norm_topk_prob (false) and states what the
    # program does beside it; by the source's key alone the loss differs
    assert not conf["norm_topk_prob"] and conf["renormalise_routed_weights"]
    as_source = dict(conf, renormalise_routed_weights=False)
    assert abs(tight - float(MOE.loss(params, jnp.asarray(ids),
                                      as_source))) > 1e-6


@pytest.mark.parametrize("tokens,want", [(16384, 1920), (4096, 512),
                                         (1024, 120), (64, 8), (8, 8)])
def test_capacity_rule_is_the_programs(tokens, want):
    from paddle_tpu.models import moe

    conf = MAN.config("deepseek-moe-16b")
    _, cfg = build_config(conf, "train")
    assert MOE.capacity(conf, tokens) == want \
        == moe.moe_capacity(cfg, tokens)


def test_serving_check_catches_a_wrong_program():
    """The paged programs against the reference: right as they are, and
    wrong (over the tolerance) once a weight is disturbed under them."""
    conf, family, cfg, params = tiny("mistral-7b-v0.3", "serve")
    good = check.serve_check(DENSE, family, cfg, conf, params, 16, seed=7)
    assert good["ok"] and good["logit_err_over_max"] < 1e-4

    class Skewed:
        """The family with a feed-forward that drops its gate."""
        def __getattr__(self, k):
            return getattr(family, k)

        @staticmethod
        def decode_mlp(x, lp, c):
            return x + (x @ lp["up"]) @ lp["down"]

    bad = check.serve_check(DENSE, Skewed(), cfg, conf, params, 16, seed=7)
    assert not bad["ok"]


def test_training_check_catches_a_fault_in_one_small_leaf():
    """A router that gets no gradient: the global norm barely moves (it
    would pass its band), the router's own norm is off by all of it."""
    conf, family, cfg, params = tiny("deepseek-moe-16b", "train")

    class NoRouterGradient:
        def __getattr__(self, k):
            return getattr(family, k)

        @staticmethod
        def loss_fn(p, batch, c):
            layers = dict(p["layers"], router=jax.lax.stop_gradient(
                p["layers"]["router"]))
            return family.loss_fn(dict(p, layers=layers), batch, c)

    out = check.train_check(MOE, NoRouterGradient(), cfg, conf, params,
                            seed=5)
    assert not out["ok"]
    assert out["worst_leaf"] == "['layers']['router']"
    assert out["worst_leaf_norm_rel_diff"] == pytest.approx(1.0)
    # a leaf with a band of its own is held to that one
    own = copy.deepcopy(conf)
    own["train"]["check"]["leaf_norm_tolerance_of"] = {out["worst_leaf"]: 2.0}
    assert check.train_check(MOE, NoRouterGradient(), cfg, own, params,
                             seed=5)["ok"]
    assert out["loss_abs_diff"] < 1e-5
    assert out["grad_norm_rel_diff"] < 0.02     # the band PR 23 first had


def wider(conf, **check):
    """The dense rehearsal widened until bf16's error is measurable and a
    query-key score spreads as at the published width."""
    conf = copy.deepcopy(conf)
    conf.update(hidden_size=256, intermediate_size=512, num_hidden_layers=4,
                vocab_size=512)
    conf["serve"]["set"]["dtype"] = "bfloat16"
    conf["serve"]["check"].update(prompts=2, prompt_len=48, decode_steps=4,
                                  **check)
    return conf


def test_a_band_a_quarter_over_bf16_fails_lower_precision():
    """The chip's bands are 1.25 times what the bf16 program measured
    there. At a width the CPU can run, the same rule (1.25 times this
    size's own bf16 error) fails weights rounded to int8 a column and to
    fp8, so such a path would not pass as a faster bf16."""
    conf = wider(rehearsal_of(MAN.config("mistral-7b-v0.3")),
                 tolerance=1.0, rms_tolerance=1.0)
    family, cfg = build_config(conf, "serve")
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    bf16 = check.serve_check(DENSE, family, cfg, conf, params, 16, seed=7)
    assert 1e-3 < bf16["rms_err_over_rms"] < 0.05
    conf["serve"]["check"].update(
        tolerance=1.25 * bf16["logit_err_over_max"],
        rms_tolerance=1.25 * bf16["rms_err_over_rms"])
    assert check.serve_check(DENSE, family, cfg, conf, params, 16, seed=7)["ok"]

    def int8(w):
        step = jnp.max(jnp.abs(w.astype(jnp.float32)), -2, keepdims=True) / 127
        return (jnp.round(w.astype(jnp.float32) / step) * step).astype(w.dtype)

    def fp8(w):
        return w.astype(jnp.float8_e4m3fn).astype(w.dtype)

    for lower in (int8, fp8):
        rounded = jax.tree.map(lambda w: lower(w) if w.ndim >= 2 else w,
                               params)
        out = check.serve_check(DENSE, family, cfg, conf, rounded, 16,
                                seed=7, reference_params=params)
        assert not out["ok"], lower.__name__
        assert out["rms_err_over_rms"] > 2 * bf16["rms_err_over_rms"]


@pytest.mark.parametrize("fault", ["no_rope", "no_mask"])
def test_random_weights_do_not_hide_a_rope_or_mask_fault(fault, monkeypatch):
    """Weights are normal(0, 0.02): at hidden 4,096 a query-key score has
    a standard deviation of 4096 x 0.02^2 = 1.6, so attention is far from
    uniform and a wrong position or mask moves the logits by about their
    own size. Here at hidden 256, weights scaled to the same spread."""
    conf = wider(rehearsal_of(MAN.config("mistral-7b-v0.3")))
    family, cfg = build_config(conf, "serve")
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    params = jax.tree.map(lambda w: (w * 4.0).astype(w.dtype)
                          if w.ndim >= 2 else w, params)   # 256 x .08^2 = 1.6
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, 48))
    at = np.arange(20, 28)          # (the last row sees every token anyway)
    good = np.asarray(DENSE.logits_at(params, ids, conf, at))
    if fault == "no_rope":
        monkeypatch.setattr(R, "rotary", lambda x, theta: x)
    else:
        monkeypatch.setattr(jnp, "tril", jnp.ones_like)
    bad = np.asarray(DENSE.logits_at(params, ids, conf, at))
    rms = np.sqrt(np.mean((bad - good) ** 2) / np.mean(good ** 2))
    assert rms > 0.5, rms                   # against a band of 0.05
