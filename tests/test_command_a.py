"""Command A+ at a tiny size (one period of three window layers and a full
layer, 8 query heads over 2 key/value heads of 16, a window of 8 in rings
of 12, 16 router outputs of which this "chip" holds 4 from the fifth, 4
picks a token beside 4 shared experts): the stack through the paged
programs and the engine against ``forward`` and the plain reference, two
periods (a kind declared by several segments), long prompts' pieces, the
shares of the experts adding up to the uncut layer, the shared
expert-parallel layer, and the refusals."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.architectures import command_a as REF
from paddle_tpu.core import enforce as E
from paddle_tpu.inference import Request, ServingEngine, paged
from paddle_tpu.inference.paged import (cache_decode_step, cache_prefill,
                                        cache_prefill_shared,
                                        cache_verify_window, init_pool)
from paddle_tpu.models import axk1, expert_parallel
from paddle_tpu.models import command_a as C


@pytest.fixture(scope="module")
def model():
    c = C.command_a_tiny()
    return c, jax.jit(lambda k: C.init_params(c, k))(jax.random.PRNGKey(0))


def make_cache(c, pages, ps, rows):
    return init_pool(c, pages, ps, pool_layout=C.pool_layout(c),
                     state_shapes=C.state_shapes(c), state_rows=rows)


def conf_of(c) -> dict:
    """The reference's view of a config: the source's key names."""
    return {"num_hidden_layers": c.num_hidden_layers,
            "layer_types": list(c.layer_types),
            "num_attention_heads": c.num_attention_heads,
            "num_key_value_heads": c.num_key_value_heads,
            "head_dim": c.head_dim, "rope_theta": c.rope_theta,
            "sliding_window": c.sliding_window,
            "layer_norm_eps": c.layer_norm_eps,
            "num_experts_per_tok": c.num_experts_per_tok,
            "first_held_expert": c.first_held_expert,
            "logit_scale": c.logit_scale}


def run_paged(c, params, ids, plen, ps):
    """Prefill of the first ``plen`` tokens, then a decode step a token:
    every step's logits [n, steps + 1, V] and picks."""
    n, total = ids.shape
    per = -(-total // ps)
    rows = np.arange(n * per, dtype=np.int32).reshape(n, per)
    s_pad = -(-plen // ps) * ps
    padded = np.zeros((n, s_pad), np.int32)
    padded[:, :plen] = ids[:, :plen]
    prefill = jax.jit(lambda p, i, ca, r, sl: cache_prefill(
        C, p, i, c, ca, r, sl, jnp.arange(n), routes=True))
    decode = jax.jit(lambda p, ca, ln, tok: cache_decode_step(
        C, p, ca, jnp.asarray(rows), ln, tok, c, jnp.arange(n),
        routes=True))
    cache, logits, picks = prefill(
        params, jnp.asarray(padded), make_cache(c, n * per, ps, n),
        jnp.asarray(rows[:, :s_pad // ps]), jnp.full((n,), plen, jnp.int32))
    got, took = [logits], [picks[:, :, :plen]]
    for t in range(plen, total):
        cache, logits, picks = decode(params, cache,
                                      jnp.full((n,), t + 1, jnp.int32),
                                      jnp.asarray(ids[:, t], jnp.int32))
        got.append(logits)
        took.append(picks)
    return jnp.stack(got, 1), jnp.concatenate(took, 2), cache


def forward(params, ids, c, **kw):
    return jax.jit(lambda p, i: C.forward(p, i, c, **kw))(
        params, jnp.asarray(ids))


# -- the stack and the cache --------------------------------------------------

def test_the_stack_is_declared_and_the_cache_is_rings_beside_a_pool(model):
    c, params = model
    assert [(s.kind, s.count, s.keeps) for s in C.segments(c)] == [
        ("window", 3, ("ring",)), ("full", 1, ("pages",))]
    assert C.pool_layout(c) == (1, 2, 16)
    cache = make_cache(c, 6, 4, 3)
    assert cache["k"].shape == (1, 6, 2, 4, 16)
    assert cache["state"]["ring_k"].shape == (3, 4, 3, 2, 4, 16)
    assert params["window"]["wq"].shape == (3, 64, 128)
    assert params["full"]["router"].shape == (1, 64, 16)
    assert params["experts"]["gate"].shape == (4, 4, 32, 64)
    # two periods: the kinds' layers stacked in the stack's order
    c2 = C.command_a_tiny(num_hidden_layers=8)
    assert [s.kind for s in C.segments(c2)] == ["window", "full"] * 2
    assert [C._stack_layer("window", i, c2) for i in range(6)] \
        == [0, 1, 2, 4, 5, 6]
    assert [C._stack_layer("full", i, c2) for i in range(2)] == [3, 7]
    # at the published widths: a ring of 4,112 tokens in 257 pages of 16
    big = C.CommandAConfig(num_hidden_layers=4)
    assert C.state_shapes(big)["ring_k"][0] == (257, 8, 16, 128)
    assert C.pool_layout(big) == (1, 8, 128)


@pytest.mark.parametrize("layers,ps", [(4, 4), (4, 8), (8, 4)])
def test_prefill_then_decode_through_rings_and_pool_is_forward(layers, ps):
    """A prompt past the window and the ring's wrap that ends inside a
    page, then decode steps that cross into the next: the logits and every
    layer's picks are forward's (one period, and two: a kind declared by
    two segments takes its layers in turn)."""
    c = C.command_a_tiny(num_hidden_layers=layers)
    params = jax.jit(lambda k: C.init_params(c, k))(
        jax.random.PRNGKey(layers))
    ids = np.random.default_rng(1).integers(0, c.vocab_size, (2, 20))
    want, routes = forward(params, ids, c, with_routes=True)
    got, picks, cache = run_paged(c, params, ids, 14, ps)
    np.testing.assert_allclose(got, want[:, 13:], atol=2e-6)
    np.testing.assert_array_equal(picks, routes)
    assert set(cache) == {"k", "v", "state"}


def test_forward_is_the_plain_reference(model):
    c, params = model
    ids = np.random.default_rng(2).integers(0, c.vocab_size, (1, 24))
    want = REF.logits_at(params, jnp.asarray(ids[0]), conf_of(c),
                         np.arange(24))
    np.testing.assert_allclose(forward(params, ids, c)[0],
                               want, atol=2e-5)


def test_long_prompts_go_through_in_pieces(model, monkeypatch):
    """A prompt's attention a key/value head at a time and its rotation a
    piece of rows at a time, each written over its input: the same
    logits."""
    c, params = model
    ids = np.random.default_rng(3).integers(0, c.vocab_size, (2, 20))
    want, _, _ = run_paged(c, params, ids, 16, 4)
    monkeypatch.setattr(paged, "_PROMPT_ATTN_BYTES", 0)
    monkeypatch.setattr(C, "EXPERT_TOKENS", 8)
    got, _, _ = run_paged(c, params, ids, 16, 4)
    np.testing.assert_allclose(got, want, atol=2e-6)


# -- the share of the experts -------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer(model):
    """Eight chips of 2 of the 16 experts each: their partial outputs, the
    attention and the shared experts (what every chip computes alike)
    counted once, add up to the layer with all 16 held, in the reference
    and in the program alike."""
    c, params = model
    x = params["embed"][jnp.arange(24) % c.vocab_size] * 30.0
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    whole = {k: jax.random.normal(kk, (16, 32, 64)) * 0.2
             for k, kk in zip(("gate", "up", "down"), ks)}
    conf = conf_of(c)
    w, window = REF.layer_weights(params, 1, conf)

    layer = jax.jit(lambda part, first: REF.layer(
        x, part, {**conf, "first_held_expert": first}, window)[0])

    def ref(first, n):
        return layer({**w, "experts": jax.tree.map(
            lambda a: a[first:first + n], whole)}, first)

    common = ref(0, 0)
    parts = [ref(2 * i, 2) - common for i in range(8)]
    np.testing.assert_allclose(common + sum(parts), ref(0, 16), atol=1e-4)
    assert all(float(jnp.max(jnp.abs(p))) > 1e-3 for p in parts)

    class Ops(C._NoCache):
        pass

    def program(first, n):
        cs = C.command_a_tiny(first_held_expert=first, n_routed_experts=n)
        lp = {**jax.tree.map(lambda a: a[1], params["window"]),
              "experts": jax.tree.map(lambda a: jnp.stack(
                  [a[first:first + n]] * 4), whole)}
        return C.stack_block("window", x[None], lp, cs, 1, Ops())[0][0]

    common = program(0, 0)
    total = common + sum(program(2 * i, 2) - common for i in range(8))
    np.testing.assert_allclose(total, program(0, 16), atol=1e-4)
    np.testing.assert_allclose(program(0, 16), ref(0, 16), atol=1e-4)


def test_axk1_and_command_a_share_one_expert_parallel_layer():
    assert axk1.routed is C.routed is expert_parallel.routed
    assert C.CommandAConfig(num_hidden_layers=4).n_group == 1


# -- the engine ---------------------------------------------------------------

def test_the_engine_serves_it_and_counts_held_picks(model):
    c, params = model
    eng = ServingEngine(C, params, c, num_slots=3, max_len=48, page_size=4,
                        num_pages=40)
    assert set(eng.cache.pool) == {"k", "v", "state"}
    prompts = [(np.arange(6 + 3 * i) * 7 + i) % c.vocab_size
               for i in range(5)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p.astype(np.int32),
                           max_new_tokens=9))
    while eng.step():
        pass
    st = eng.stats
    assert st.completed == 5 and st.preempted == 0
    # every slot of the grid routes k picks a layer a step; those among
    # experts 4..7 are computed here, the others are other chips'
    assert st.expert_rows == st.decode_steps * 4 * 3 * 4
    assert st.expert_picks_held + st.expert_picks_elsewhere == st.expert_rows
    assert 0 < st.expert_picks_held < st.expert_rows
    # greedy: forward's argmax, every sequence in one batch (padding after
    # a sequence's end changes nothing before it)
    seqs = [np.concatenate([p, eng.outputs[i].tokens[:-1]])
            for i, p in enumerate(prompts)]
    batch = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, q in enumerate(seqs):
        batch[i, :len(q)] = q
    logits = forward(params, batch, c)
    for i, p in enumerate(prompts):
        toks = eng.outputs[i].tokens
        assert list(np.argmax(logits[i, len(p) - 1:len(p) - 1 + len(toks)],
                              -1)) == list(toks)


# -- refusals -----------------------------------------------------------------

@pytest.mark.parametrize("flag,missing", [
    ("prefix_cache", "snapshot of the state"),
    ("spec_decode", "rollback of the state"),
    ("kv_quant", "quantized form of the state")])
def test_what_the_rings_cannot_do_is_refused(model, flag, missing):
    c, params = model
    with pytest.raises(E.UnimplementedError,
                       match=f"serving_{flag}.*{missing}"):
        ServingEngine(C, params, c, num_slots=2, max_len=32, page_size=4,
                      **{flag: True})


def test_the_data_plane_refuses_to_share_or_rewind_rings(model):
    c, params = model
    cache = make_cache(c, 4, 4, 1)
    with pytest.raises(E.UnimplementedError, match="verify window"):
        cache_verify_window(C, params, jnp.zeros((1, 2), jnp.int32), c,
                            cache, jnp.zeros((1, 2), jnp.int32),
                            jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool))
    with pytest.raises(E.UnimplementedError, match="recurrent state"):
        cache_prefill_shared(C, params, jnp.zeros((1, 4), jnp.int32), c,
                             cache, jnp.zeros((1, 1), jnp.int32),
                             jnp.ones((1,), jnp.int32),
                             jnp.zeros((1, 1), jnp.int32))


@pytest.mark.parametrize("kw,match", [
    ({"ep_size": 8}, "experts across chips"),
    ({"vision_config": {"model_type": "siglip"}}, "vision tower"),
    ({"layer_types": ("full_attention",) * 4}, "published stack"),
    ({"use_parallel_block": False}, "parallel block"),
    ({"shared_expert_combination_strategy": "sum"}, "averaged"),
    ({"first_held_expert": 14}, "held experts")])
def test_what_is_not_written_is_refused_by_name(kw, match):
    error = E.PreconditionNotMetError if "first_held_expert" in kw \
        else E.UnimplementedError
    with pytest.raises(error, match=match):
        C.command_a_tiny(**kw)


def test_no_train_step():
    with pytest.raises(E.UnimplementedError, match="no train step"):
        C.loss_fn(None, None, None)
