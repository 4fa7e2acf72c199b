"""The host work of a serving step that runs while the device works.

Where no request of a prefill group names an EOS, nothing the scheduler
decides before the next chunk's dispatch reads the group's first tokens:
``ServingEngine`` then hands them to the chunk on the device, builds the
chunk's inputs while the device prefills, and reads them after the
chunk's dispatch. These tests hold that path to the one that downloads
at once (an EOS that no token can equal forces it), and the allocator's
one-scatter block table to its rows.
"""
import jax
import numpy as np
import pytest

from paddle_tpu.inference import Request, ServingEngine
from paddle_tpu.inference.paged import PagedKVCache
from paddle_tpu.models import llama as L

NEVER = 10 ** 6                       # an EOS outside every vocabulary

SCENES = {
    # name: (engine keywords, [(prompt length, new tokens)], temperature)
    "one_group": (dict(num_slots=4, max_len=32, page_size=4), [(5, 6)] * 3,
                  0.0),
    "two_buckets": (dict(num_slots=4, max_len=48, page_size=4),
                    [(3, 5), (9, 4), (4, 7), (10, 3)], 0.0),
    "joins_later": (dict(num_slots=2, max_len=32, page_size=4,
                         decode_chunk=2), [(3, 5), (4, 9), (3, 4), (5, 1)],
                    0.0),
    # 2 slots on a 5-page pool: both prompts fit, both growing past 8
    # positions cannot, so the reserve of a chunk preempts
    "preempts": (dict(num_slots=2, max_len=16, page_size=4, num_pages=5,
                      decode_chunk=2), [(5, 8), (5, 8)], 0.0),
    "sampled": (dict(num_slots=2, max_len=32, page_size=4), [(4, 6)] * 3,
                0.8),
}


@pytest.fixture(scope="module")
def model():
    cfg = L.llama_tiny()
    return cfg, L.init_params(cfg, jax.random.PRNGKey(3))


def run(model, scene, eos):
    cfg, params = model
    kw, reqs, temp = SCENES[scene]
    eng = ServingEngine(L, params, cfg, **kw)
    rng = np.random.default_rng(7)
    for rid, (n, new) in enumerate(reqs):
        eng.submit(Request(
            rid=rid, prompt=rng.integers(0, cfg.vocab_size, n)
            .astype(np.int32), max_new_tokens=new, eos_token_id=eos,
            temperature=temp, key=jax.random.PRNGKey(rid) if temp else None))
    while True:
        busy = eng.step()
        assert not eng._unfetched          # nothing outlives a step
        if not busy:
            break
    eng.cache.alloc.check_invariants()
    assert eng.cache.alloc.free_pages == eng.cache.num_pages
    return eng


@pytest.mark.serving
@pytest.mark.parametrize("scene", sorted(SCENES))
def test_tokens_equal_whether_the_first_token_waits_or_not(model, scene):
    later, at_once = run(model, scene, None), run(model, scene, NEVER)
    assert sorted(later.outputs) == sorted(at_once.outputs)
    for rid, out in later.outputs.items():
        np.testing.assert_array_equal(out.tokens, at_once.outputs[rid].tokens)
        assert out.tokens.size == SCENES[scene][1][rid][1]
        assert out.preemptions == at_once.outputs[rid].preemptions
    for name in ("admitted", "completed", "preempted", "decode_steps",
                 "tokens_generated", "tokens_prefilled", "tokens_discarded"):
        assert getattr(later.stats, name) == getattr(at_once.stats, name)
    if scene == "preempts":
        assert later.stats.preempted >= 1


@pytest.mark.serving
@pytest.mark.parametrize("eos, waits", [(None, True), (NEVER, False)])
def test_the_download_waits_only_where_no_eos_can_end_a_request(
        model, monkeypatch, eos, waits):
    cfg, params = model
    eng = ServingEngine(L, params, cfg, num_slots=2, max_len=32, page_size=4)
    eng.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=4, eos_token_id=eos))
    seen = []
    reserve = eng._ensure_chunk_capacity
    monkeypatch.setattr(
        eng, "_ensure_chunk_capacity",
        lambda live, c: seen.append(len(eng._unfetched)) or reserve(live, c))
    eng.step()
    # at the reserve, between the prefill's dispatch and the chunk's
    assert seen == [1 if waits else 0]
    slot = eng.slots[0]
    assert slot.gen == len(slot.tokens) and slot.pending == slot.tokens[-1]


@pytest.mark.parametrize("width", [None, 6])
def test_block_tables_is_one_row_of_block_row_a_sequence(model, width):
    cfg, _ = model
    cache = PagedKVCache(cfg, num_pages=40, page_size=4, max_pages_per_seq=8)
    rng = np.random.default_rng(0)
    for sid in range(6):
        cache.alloc.alloc(sid, int(rng.integers(1, 6 * 4)))
    ids = [3, None, 0, 5, None, None, 1]
    table = cache.block_tables(ids, width)
    assert table.dtype == np.int32 and table.shape == (7, width or 8)
    for row, sid in zip(table, ids):
        want = (np.full(width or 8, cache.num_pages) if sid is None
                else cache.alloc.block_row(sid, width))
        np.testing.assert_array_equal(row, want)
    assert (cache.block_tables([None, None]) == cache.num_pages).all()
    with pytest.raises(Exception, match="more pages than the table's width"):
        cache.block_tables(ids, 2)
