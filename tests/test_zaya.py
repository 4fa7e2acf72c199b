"""ZAYA1 at a tiny size (2 layers, 4 query and 2 key heads of 16, 4
experts of 32, router width 16): the layer through the paged programs and
the engine against ``forward`` (every position at once, no cache), the
rows a sequence keeps, the picks the programs hand back and the engine
counts, the refusals, and the expert kernel in interpret mode against its
reference, under total imbalance too."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.core import enforce as E
from paddle_tpu.inference import Request, ServingEngine
from paddle_tpu.inference.paged import (PagedKVCache, cache_decode_step,
                                        cache_prefill, init_pool)
from paddle_tpu.kernels import moe_experts as M
from paddle_tpu.models import zaya as Z


@pytest.fixture(scope="module")
def model():
    c = Z.zaya_tiny()
    return c, Z.init_params(c, jax.random.PRNGKey(0))


def make_cache(c, pages, ps, rows):
    return init_pool(c, pages, ps, state_shapes=Z.state_shapes(c),
                     state_rows=rows)


# -- the tree, the rows, the stream ----------------------------------------

def test_the_experts_lie_beside_the_scanned_layers(model):
    c, params = model
    L = c.num_hidden_layers
    assert set(params) == {"embed", "layers", "experts", "ln_f"}
    for leaf in jax.tree.leaves(params["layers"]):
        assert leaf.shape[0] == L
    for name in ("gate", "up", "down"):
        assert params["experts"][name].shape == (
            L, c.num_experts, c.moe_intermediate_size, c.hidden_size)
    assert params["layers"]["conv2_w"].shape == (L, 2, 6, 16, 16)
    assert params["layers"]["tau"].shape == (L, 2)


def test_a_row_is_two_tails_and_a_value_in_the_models_type():
    big = Z.ZayaConfig()
    assert (big.latent, big.rotary_dim, big.rope_theta) == (1280, 64, 5e6)
    assert Z.state_shapes(big) == {"cca": ((2688,), jnp.bfloat16)}
    c = Z.zaya_tiny()
    eng = ServingEngine(Z, Z.init_params(c, jax.random.PRNGKey(0)), c,
                        num_slots=3, max_len=32, page_size=4, num_pages=12)
    assert eng.cache.pool["state"]["cca"].shape == (2, 4, 2 * 96 + 16)
    assert eng.cache.pool["k"].shape == (2, 12, 2, 4, 16)


def test_only_the_published_layer_is_written():
    for bad in (dict(num_experts_per_tok=2), dict(cca_time0=3),
                dict(layer_types=("hybrid", "hybrid_sliding")),
                dict(tie_word_embeddings=False)):
        with pytest.raises(E.UnimplementedError, match="published ZAYA1-8B"):
            Z.zaya_tiny(**bad)


# -- the paged programs against forward --------------------------------------

@pytest.mark.parametrize("ps,lens", [(4, (11, 8, 1)), (8, (5, 16, 2)),
                                     (16, (16, 3, 9))])
def test_prefill_then_decode_is_forward(model, ps, lens):
    """Prompts of several lengths in one group (one of a single token, one
    that ends on a page's last slot), then six decode steps: the tails a
    prefill leaves are the ones the first decode step reads, and the steps
    cross into the next page."""
    c, params = model
    n, steps = len(lens), 6
    top = max(lens) + steps
    ids = np.random.default_rng(ps).integers(0, c.vocab_size, (n, top)) \
        .astype(np.int32)
    full, routes = Z.forward(params, jnp.asarray(ids), c, with_routes=True)
    s_pad = -(-max(lens) // ps) * ps
    per = -(-top // ps)
    rows = np.arange(n * per, dtype=np.int32).reshape(n, per)
    padded = np.zeros((n, s_pad), np.int32)
    for j, m in enumerate(lens):
        padded[j, :m] = ids[j, :m]
    ln = np.asarray(lens, np.int32)
    cache, logits, picks = cache_prefill(
        Z, params, jnp.asarray(padded), c, make_cache(c, n * per, ps, n),
        jnp.asarray(rows[:, :s_pad // ps]), jnp.asarray(ln), jnp.arange(n),
        routes=True)
    assert picks.shape == (c.num_hidden_layers, n, s_pad)
    for j, m in enumerate(lens):
        np.testing.assert_allclose(logits[j], full[j, m - 1], atol=2e-6)
        np.testing.assert_array_equal(picks[:, j, :m], routes[:, j, :m])
    for _ in range(steps):
        tok = jnp.asarray(ids[np.arange(n), ln])
        ln = ln + 1
        cache, logits, picks = cache_decode_step(
            Z, params, cache, jnp.asarray(rows), jnp.asarray(ln), tok, c,
            jnp.arange(n), routes=True)
        for j in range(n):
            np.testing.assert_allclose(logits[j], full[j, ln[j] - 1],
                                       atol=2e-6)
            np.testing.assert_array_equal(picks[:, j, 0],
                                          routes[:, j, ln[j] - 1])


def test_the_programs_hand_picks_back_only_where_asked(model):
    c, params = model
    cache = make_cache(c, 4, 4, 1)
    ids, rows = jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None]
    out = cache_prefill(Z, params, ids, c, cache, rows[:, :1],
                        jnp.asarray([4]), jnp.asarray([0]))
    assert len(out) == 2
    out = cache_decode_step(Z, params, out[0], rows, jnp.asarray([5]),
                            jnp.asarray([1]), c, jnp.asarray([0]))
    assert len(out) == 2


def test_an_idle_slot_leaves_every_row_but_nobodys_alone(model):
    c, params = model
    cache = make_cache(c, 8, 4, 2)
    cache["state"]["cca"] = cache["state"]["cca"] + 1.0
    rows = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    new, _ = cache_decode_step(Z, params, cache, rows, jnp.asarray([3, 0]),
                               jnp.asarray([1, 2]), c, jnp.asarray([0, 1]))
    was, now = cache["state"]["cca"], new["state"]["cca"]
    assert not np.allclose(now[:, 0], was[:, 0])       # the live slot's row
    np.testing.assert_array_equal(now[:, 1], was[:, 1])  # the idle slot's


# -- the engine ---------------------------------------------------------------

def greedy(params, c, prompt, n):
    ids = list(prompt)
    fwd = jax.jit(lambda p, i: Z.forward(p, i, c))
    for _ in range(n):
        ids.append(int(jnp.argmax(fwd(params, jnp.asarray([ids]))[0, -1])))
    return ids[len(prompt):]


def test_the_engine_serves_it_and_counts_the_picks(model):
    """More requests than slots (slots are re-used, rows re-assigned), a
    prompt of one token, answers that end inside a chunk: every output is
    the greedy loop over ``forward``; the three sums only grow and are
    what the picks say."""
    c, params = model
    eng = ServingEngine(Z, params, c, num_slots=3, max_len=48, page_size=4,
                        num_pages=24)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, max_new_tokens=m, prompt=rng.integers(
        0, c.vocab_size, n).astype(np.int32))
        for i, (n, m) in enumerate([(5, 9), (1, 12), (13, 7), (8, 20),
                                    (3, 3)])]
    for r in reqs:
        eng.submit(r)
    seen = []
    while eng.step():
        st = eng.stats
        seen.append((st.expert_rows, st.expert_reads, st.expert_rows_busiest))
    for r in reqs:
        assert list(eng.outputs[r.rid].tokens) == greedy(
            params, c, r.prompt, r.max_new_tokens)
    st = eng.stats
    assert seen == sorted(seen)
    assert st.expert_rows == st.decode_steps * 3 * c.num_hidden_layers
    # a layer a step reads between one expert and as many as it has rows
    cells = st.decode_steps * c.num_hidden_layers
    assert cells <= st.expert_reads <= 3 * cells
    assert st.expert_rows / c.num_experts <= st.expert_rows_busiest \
        <= st.expert_rows
    assert st.state_rows_assigned == 5 and st.peak_state_rows_in_use == 3
    assert eng._picks is None
    eng.cache.alloc.check_invariants()


@pytest.mark.parametrize("flag,missing", [
    ("prefix_cache", "a snapshot of the state at the shared prefix's end"),
    ("spec_decode", "a rollback of the state past the rejected drafts"),
    ("kv_quant", "a quantized form of the state beside int8 pages")])
def test_what_a_tail_has_no_counterpart_for_is_refused(model, flag, missing):
    c, params = model
    with pytest.raises(E.UnimplementedError, match=missing):
        ServingEngine(Z, params, c, num_slots=2, max_len=32, page_size=4,
                      **{flag: True})


def test_a_fork_is_refused_by_the_allocator():
    c = Z.zaya_tiny()
    cache = PagedKVCache(c, 8, 4, 4, state_shapes=Z.state_shapes(c),
                         state_rows=2)
    cache.alloc.alloc(0, 6)
    with pytest.raises(E.UnimplementedError, match="no state snapshot"):
        cache.alloc.fork(0, 1)


# -- the expert layer -----------------------------------------------------------

def weights(key, L, Ex, F, D, dtype):
    ks = jax.random.split(key, 3)
    return [(jax.random.normal(k, (L, Ex, F, D)) * 0.05).astype(dtype)
            for k in ks]


@pytest.mark.parametrize("T,Ex,picks", [
    (64, 16, "spread"), (64, 16, "one"), (200, 4, "two"), (5, 16, "spread"),
    (96, 4, "one")])
def test_the_kernel_is_the_reference_whatever_the_imbalance(T, Ex, picks):
    """Interpret mode: every row through its own expert, one expert taking
    every row and two taking them seven to one included; both layers of
    the stack, read where they lie."""
    D, F, L = 128, 256, 2
    ks = jax.random.split(jax.random.PRNGKey(T + Ex), 3)
    g, u, d = weights(ks[0], L, Ex, F, D, jnp.float32)
    x = jax.random.normal(ks[1], (T, D), jnp.float32)
    e = {"spread": jax.random.randint(ks[2], (T,), 0, Ex),
         "one": jnp.full((T,), Ex - 2, jnp.int32),
         "two": jnp.where(jnp.arange(T) % 7 == 0, 0, Ex - 1)}[picks]
    for layer in range(L):
        y = M.expert_mlp(x, e, g, u, d, layer, interpret=True)
        r = M.expert_mlp_ref(x, e, g, u, d, layer)
        np.testing.assert_allclose(y, r, atol=5e-6)
        # and each row is its own expert's plain product
        j = T // 2
        ge, ue, de = (w[layer, e[j]] for w in (g, u, d))
        own = (jax.nn.silu(x[j] @ ge.T) * (x[j] @ ue.T)) @ de
        np.testing.assert_allclose(y[j], own, atol=5e-6)


@pytest.mark.parametrize("T,Ex,tm", [(64, 16, 16), (64, 4, 16), (7, 4, 8),
                                     (1000, 8, 64), (16384, 16, 512)])
def test_rows_are_laid_out_expert_by_expert_in_whole_tiles(T, Ex, tm):
    e = jnp.asarray(np.random.default_rng(T).integers(0, Ex, T), jnp.int32)
    if T == 7:
        e = jnp.zeros((T,), jnp.int32)               # one run
    slot, source, tile_expert, used = (np.asarray(a)
                                       for a in M.slots(e, Ex, tm))
    assert len(source) == len(tile_expert) * tm >= T
    np.testing.assert_array_equal(source[slot], np.arange(T))  # none dropped
    assert (source < T).sum() == T and len(set(slot)) == T
    np.testing.assert_array_equal(tile_expert[slot // tm], np.asarray(e))
    assert used == sum(-(-int(n) // tm) for n in np.bincount(e, minlength=Ex))
    assert (slot < used * tm).all()
    assert (tile_expert[used:] == tile_expert[used - 1]).all()
    assert (np.diff(tile_expert) >= 0).all()


def test_a_tile_is_an_experts_even_share_within_the_types_tile():
    assert [M.row_tile(T, 16, jnp.bfloat16)
            for T in (1, 64, 256, 512, 2048, 16384, 1 << 20)] \
        == [16, 16, 16, 32, 128, 512, 512]
    assert M.row_tile(64, 16, jnp.float32) == 8


def test_the_dispatcher_counts_its_fallback_off_the_chip(model):
    c, params = model
    jax.clear_caches()
    kernels.reset_dispatch_stats()
    Z.forward(params, jnp.zeros((1, 8), jnp.int32), c)
    got = kernels.dispatch_stats()
    assert got["moe_fallback"] == 1 and got["moe"] == 0   # one scan body
