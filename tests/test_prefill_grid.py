"""A prefill says what it launched, where it launches it (PR 35): the
span ``serving.prefill.dispatch`` carries the grid of its one call
(``rows``, ``width``) and the real tokens in it (``tokens``), and
``EngineStats.prefill_grid_tokens`` adds the same grid up beside
``tokens_prefilled``. The benchmark reads both
(``prog.prefill_tok_s``, ``sched.prefill_fill_pct``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference import Request, ServingEngine
from paddle_tpu.inference.engine import EngineStats
from paddle_tpu.models import llama as L
from paddle_tpu.monitor import trace

DISPATCH = "serving.prefill.dispatch"


@pytest.fixture(scope="module")
def tiny():
    cfg = L.llama_tiny(num_hidden_layers=2, dtype=jnp.float32)
    return cfg, L.init_params(cfg, jax.random.PRNGKey(0))


def _ring_on():
    pt.set_flags({"FLAGS_enable_monitor": True})
    trace.clear()
    return lambda name: [e.get("args", {}) for e in trace.events()
                         if e["name"] == name]


def _ring_off():
    pt.set_flags({"FLAGS_enable_monitor": False})
    trace.clear()


@pytest.fixture
def ring():
    """The monitor on, the ring empty; off and empty again after."""
    yield _ring_on()
    _ring_off()


def requests(cfg, lengths, seed=3, new=4):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, max_new_tokens=new, prompt=rng.integers(
        0, cfg.vocab_size, n).astype(np.int32))
        for i, n in enumerate(lengths)]


@pytest.fixture(scope="module")
def served(tiny):
    """Seven prompts of three buckets through four slots."""
    cfg, params = tiny
    ring = _ring_on()
    eng = ServingEngine(L, params, cfg, num_slots=4, max_len=48,
                        page_size=4, decode_chunk=2)
    lengths = (5, 6, 7, 9, 17, 3, 12)
    eng.run(requests(cfg, lengths))
    out = eng, lengths, ring(DISPATCH), ring("serving.prefill")
    _ring_off()
    return out


@pytest.mark.parametrize("holds", [
    lambda st, n, d, p: sum(a["tokens"] for a in d) == st.tokens_prefilled
    == sum(n),
    lambda st, n, d, p: sum(a["rows"] * a["width"] for a in d)
    == st.prefill_grid_tokens,
    lambda st, n, d, p: st.prefill_grid_tokens >= st.tokens_prefilled > 0,
    # a real row is never wider than its grid, a grid never has fewer
    # rows than its group, and both are powers of two
    lambda st, n, d, p: all(
        a["tokens"] <= g["group"] * a["width"] and a["rows"] >= g["group"]
        and a["rows"] & (a["rows"] - 1) == 0
        and a["width"] == g["s_pad"] for a, g in zip(d, p)),
    # serving.prefill keeps its attrs, and one dispatch a group
    lambda st, n, d, p: len(d) == len(p) and sum(
        g["group"] for g in p) == st.admitted == len(n),
], ids=["tokens", "grid", "grid_ge_tokens", "rows_and_width", "one_a_group"])
def test_the_dispatch_span_says_the_work_it_launched(served, holds):
    eng, lengths, dispatch, prefill = served
    assert holds(eng.stats, lengths, dispatch, prefill)


def test_a_group_of_three_computes_over_four_rows(tiny, ring):
    cfg, params = tiny
    eng = ServingEngine(L, params, cfg, num_slots=4, max_len=32,
                        page_size=4)
    eng.run(requests(cfg, (5, 6, 7)))           # one bucket of 8
    assert ring(DISPATCH) == [{"rows": 4, "width": 8, "tokens": 18}]
    assert ring("serving.prefill") == [{"group": 3, "s_pad": 8}]
    assert eng.stats.prefill_grid_tokens == 32
    assert eng.stats.tokens_prefilled == 18


def test_with_the_prefix_cache_the_width_is_the_uncached_tail(tiny, ring):
    cfg, params = tiny
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    eng = ServingEngine(L, params, cfg, num_slots=2, max_len=32,
                        page_size=4, decode_chunk=3, prefix_cache=True)
    for i, n in enumerate((3, 5)):     # serial: the first seeds the radix
        tail = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        eng.run([Request(rid=i, prompt=np.concatenate([prefix, tail]),
                         max_new_tokens=4)])
    assert eng.stats.prefix_tokens_saved == 8
    first, second = ring(DISPATCH)
    assert first == {"rows": 1, "width": 16, "tokens": 11}
    # 13 tokens pad to 16; the 8 cached ones are neither computed nor
    # counted: the program runs over the 8 that are left, 5 of them real
    assert second == {"rows": 1, "width": 8, "tokens": 5}
    assert eng.stats.prefill_grid_tokens == 24
    assert eng.stats.tokens_prefilled == 16


def test_with_the_monitor_off_the_counter_still_counts(tiny):
    cfg, params = tiny
    eng = ServingEngine(L, params, cfg, num_slots=2, max_len=32,
                        page_size=4)
    eng.run(requests(cfg, (5,)))
    assert trace.events() == []
    assert (eng.stats.prefill_grid_tokens, eng.stats.tokens_prefilled) \
        == (8, 5)


@pytest.mark.parametrize("key", [
    "prefill_grid_tokens", "tokens_decoded", "state_rows_in_use",
    "peak_state_rows_in_use", "state_rows_assigned", "expert_rows",
    "expert_reads", "expert_rows_busiest"])
def test_as_dict_has_every_counter(key):
    """Additions only: every key reads the attribute of its name."""
    st = EngineStats()
    setattr(st, key, 7)
    assert st.as_dict()[key] == 7


def test_as_dict_renamed_nothing():
    st = EngineStats()
    public = {k for k in vars(st) if not k.startswith("_")}
    assert public <= set(st.as_dict())
    assert set(st.as_dict()) - public == {"batch_occupancy"}
