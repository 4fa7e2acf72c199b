"""A decode step's KV write (``kernels/kv_write.py``): the Pallas kernel in
interpret mode, bit for bit against the two scatters it replaces, at the
serving cells' head counts and page sizes over small pools; what it must
leave alone; what ``supported`` refuses; the dispatcher's two counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.kernels import kv_write as KW

BF16, F32 = jnp.bfloat16, jnp.float32

# leaf shape, the layer written (None: a pool without the layer axis),
# slots, dtype. The cells': Mistral 8 heads, Falcon-H1 4, ZAYA 2 (pages of
# 64), Phi's one-layer pool and its rings 10 (pages of 16).
POOLS = {
    "mistral-8-heads-pages-of-64": ((3, 9, 8, 64, 128), 2, 6, BF16),
    "falcon-h1-4-heads-pages-of-64": ((2, 12, 4, 64, 128), 0, 8, BF16),
    "zaya-2-heads-pages-of-64": ((4, 10, 2, 64, 128), 3, 8, BF16),
    "phi-10-heads-pages-of-16": ((1, 24, 10, 16, 128), 0, 8, BF16),
    "phi-ring-6d": ((3, 7, 3, 10, 16, 128), 1, 6, BF16),
    "no-layer-axis": ((12, 2, 16, 128), None, 4, BF16),
    "float32-pages-of-8": ((2, 12, 2, 8, 128), 1, 4, F32),
    "float32-pages-of-16-head-256": ((2, 10, 2, 16, 256), 0, 4, F32),
}
# where in its page each slot's token lands
OFFSETS = {
    "first-row": lambda ps, sub, B, rng: np.zeros(B, np.int32),
    "a-tiles-last-row": lambda ps, sub, B, rng: np.full(B, sub - 1, np.int32),
    "the-pages-last-row": lambda ps, sub, B, rng: np.full(B, ps - 1, np.int32),
    "anywhere": lambda ps, sub, B, rng: rng.integers(0, ps, B).astype(np.int32),
}


def _bits(a):
    return np.asarray(jax.lax.bitcast_convert_type(
        a, jnp.uint16 if a.dtype == BF16 else jnp.uint32))


def _case(shape, layer, B, dtype, offsets, seed, inactive):
    rng = np.random.default_rng(seed)
    kv, ps, hd = shape[-3:]
    P = KW._pages(jax.ShapeDtypeStruct(shape, dtype), layer)
    rows = rng.permutation(P)[:B].astype(np.int32)
    rows[list(inactive)] = P
    off = offsets(ps, KW._sublane(dtype), B, rng)
    mk = lambda s: jnp.asarray(rng.standard_normal(s), dtype)
    return (mk(shape), mk(shape), layer, jnp.asarray(rows), jnp.asarray(off),
            mk((B, kv, hd)), mk((B, kv, hd)))


@pytest.mark.parametrize("offsets", OFFSETS)
@pytest.mark.parametrize("pool", POOLS)
def test_kernel_is_the_scatter_bit_for_bit(pool, offsets):
    """Some slots inactive (the sentinel ``P``): the kernel's two pools
    are the reference's, every bit; the live slots' rows hold their
    tokens and every other element of both pools is the input's."""
    shape, layer, B, dtype = POOLS[pool]
    args = _case(shape, layer, B, dtype, OFFSETS[offsets], 7, (1, B - 1))
    pk, pv, _, rows, off, k, v = args
    got = KW.kv_token_write(*args, interpret=True)
    want = KW.kv_token_write_ref(*args)
    for g, w, old, new in zip(got, want, (pk, pv), (k, v)):
        assert g.shape == w.shape == old.shape and g.dtype == old.dtype
        np.testing.assert_array_equal(_bits(g), _bits(w))
        P = KW._pages(old, layer)
        flat = _bits(g).reshape((-1, P) + shape[-3:])
        before = _bits(old).reshape(flat.shape).copy()
        for b in range(B):
            if int(rows[b]) < P:
                at = (layer or 0, int(rows[b]), slice(None), int(off[b]))
                np.testing.assert_array_equal(flat[at], _bits(new)[b])
                before[at] = flat[at]
        np.testing.assert_array_equal(flat, before)


@pytest.mark.parametrize("pool", ["mistral-8-heads-pages-of-64",
                                  "phi-ring-6d", "float32-pages-of-8"])
def test_all_slots_inactive_leaves_the_pools_as_they_were(pool):
    shape, layer, B, dtype = POOLS[pool]
    args = _case(shape, layer, B, dtype, OFFSETS["anywhere"], 3, range(B))
    for g, old in zip(KW.kv_token_write(*args, interpret=True), args[:2]):
        np.testing.assert_array_equal(_bits(g), _bits(old))


def test_a_row_off_the_page_writes_nothing():
    """An offset past the page is dropped, as the scatter drops it: the
    kernel must not copy a tile that begins in the next page."""
    shape, layer, B, dtype = POOLS["falcon-h1-4-heads-pages-of-64"]
    pk, pv, _, rows, off, k, v = _case(shape, layer, B, dtype,
                                       OFFSETS["anywhere"], 19, ())
    off = off.at[0].set(shape[-2]).at[3].set(shape[-2] + 17)
    args = (pk, pv, layer, rows, off, k, v)
    for g, w in zip(KW.kv_token_write(*args, interpret=True),
                    KW.kv_token_write_ref(*args)):
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("budget,chunks", [(8 << 20, 1), (320 << 10, 2),
                                           (80 << 10, 8)])
def test_slots_in_chunks_that_fit_the_budget(monkeypatch, budget, chunks):
    """Where the slots' tiles do not fit the budget a grid step takes a
    chunk of them: the same pools, however many steps."""
    shape, layer, B, dtype = POOLS["phi-10-heads-pages-of-16"]
    monkeypatch.setattr(KW, "_VMEM_BUDGET", budget)
    assert B // KW._slots_per_chunk(B, 10, 16, 128, 2) == chunks
    args = _case(shape, layer, B, dtype, OFFSETS["anywhere"], 11, (2,))
    for g, w in zip(KW.kv_token_write(*args, interpret=True),
                    KW.kv_token_write_ref(*args)):
        np.testing.assert_array_equal(_bits(g), _bits(w))


def test_a_traced_layer_under_jit_and_a_scan():
    """The programs' form: the pools as a scan's carry, the layer the
    scan's counter, the call behind a ``jit``."""
    shape, _, B, dtype = POOLS["zaya-2-heads-pages-of-64"]
    pk, pv, _, rows, off, k, v = _case(shape, 0, B, dtype,
                                       OFFSETS["anywhere"], 5, (0,))

    def run(write):
        def step(c, layer):
            return write(c[0], c[1], layer, rows, off, k * (layer + 1), v), None
        return jax.jit(lambda a, b: jax.lax.scan(
            step, (a, b), jnp.arange(shape[0]))[0])(pk, pv)

    got = run(lambda *a: KW.kv_token_write(*a, interpret=True))
    for g, w in zip(got, run(KW.kv_token_write_ref)):
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("why,pool,k", [
    ("the int8 pair", {"q": jnp.zeros((2, 4, 2, 32, 128), jnp.int8),
                       "s": jnp.zeros((2, 4, 2), F32)},
     jnp.zeros((4, 2, 128), BF16)),
    ("int8 codes alone", jnp.zeros((2, 4, 2, 32, 128), jnp.int8),
     jnp.zeros((4, 2, 128), BF16)),
    ("a bf16 page under its tile of 16", jnp.zeros((2, 4, 2, 8, 128), BF16),
     jnp.zeros((4, 2, 128), BF16)),
    ("a float32 page of 12 rows", jnp.zeros((2, 4, 2, 12, 128), F32),
     jnp.zeros((4, 2, 128), F32)),
    ("a head of 64", jnp.zeros((2, 4, 2, 16, 64), BF16),
     jnp.zeros((4, 2, 64), BF16)),
    ("values of other heads", jnp.zeros((2, 4, 2, 16, 128), BF16),
     jnp.zeros((4, 4, 128), BF16)),
])
def test_supported_refuses(why, pool, k):
    assert not KW.supported(pool, k), why


def test_supported_takes_the_cells_pools():
    for name, (shape, _, B, dtype) in POOLS.items():
        assert KW.supported(jax.ShapeDtypeStruct(shape, dtype),
                            jax.ShapeDtypeStruct((B,) + shape[-3::2], dtype)), name


@pytest.mark.parametrize("pool,counter", [
    ("zaya-2-heads-pages-of-64", "kv_write"),          # supported
    ("tiny-head", "kv_write_fallback"),                # a head of 16
])
def test_dispatcher_counts_a_trace(pool, counter):
    """Off a TPU the dispatcher takes the scatters; with the kernels
    registered in interpret mode it takes the kernel where the pool is
    supported and the scatters where it is not. Each counts once a
    trace, and both give the reference's pools."""
    shape, layer, B, dtype = POOLS.get(pool, ((2, 12, 2, 4, 16), 1, 4, F32))
    args = _case(shape, layer, B, dtype, OFFSETS["anywhere"], 13, (1,))
    want = KW.kv_token_write_ref(*args)
    before = kernels.dispatch_stats()
    got = kernels.dispatched_kv_token_write(*args)
    mid = kernels.dispatch_stats()
    assert mid["kv_write_fallback"] == before["kv_write_fallback"] + 1
    assert mid["kv_write"] == before["kv_write"]
    try:
        kernels.register(interpret=True)
        got2 = kernels.dispatched_kv_token_write(*args)
    finally:
        kernels.register()
    after = kernels.dispatch_stats()
    assert after[counter] == mid[counter] + 1
    other = {"kv_write": "kv_write_fallback",
             "kv_write_fallback": "kv_write"}[counter]
    assert after[other] == mid[other]
    for g, g2, w in zip(got, got2, want):
        np.testing.assert_array_equal(_bits(g), _bits(w))
        np.testing.assert_array_equal(_bits(g2), _bits(w))


@pytest.mark.parametrize("offsets", ["first-row", "anywhere"])
def test_the_one_leaf_of_a_latent_pool_is_written_alone(offsets):
    """A latent pool (``models/axk1.py``: one row ``[c ; k_pe]`` of 640 a
    token, no values beside it) through the same call with no second half:
    one pool back, the reference's bit for bit, a row a live slot."""
    shape, layer, B = (5, 9, 1, 128, 640), 3, 8
    pool, _, _, rows, off, row, _ = _case(shape, layer, B, BF16,
                                          OFFSETS[offsets], 11, (2,))
    got = KW.kv_token_write(pool, None, layer, rows, off, row, None,
                            interpret=True)
    want = KW.kv_token_write_ref(pool, None, layer, rows, off, row, None)
    assert len(got) == len(want) == 1 and got[0].shape == shape
    np.testing.assert_array_equal(_bits(got[0]), _bits(want[0]))
    live = np.asarray(rows) < 9
    np.testing.assert_array_equal(
        _bits(got[0][layer, rows[live], 0, off[live]]), _bits(row[live, 0]))
    assert KW.supported(pool, row)
    before = kernels.dispatch_stats()["kv_write_fallback"]
    (off_chip,) = kernels.dispatched_kv_token_write(pool, None, layer, rows,
                                                    off, row, None)
    assert kernels.dispatch_stats()["kv_write_fallback"] == before + 1
    np.testing.assert_array_equal(_bits(off_chip), _bits(want[0]))


def test_two_halves_trace_to_the_parents_kernel():
    """The K/V pools' write (every serving cell's) is the parent's program
    equation for equation: PR 37 generalised the kernel to one leaf or
    two and changed nothing for two."""
    import importlib.util
    import subprocess

    try:
        text = subprocess.run(
            ["git", "show", "ca25dec:paddle_tpu/kernels/kv_write.py"],
            cwd=__file__.rsplit("/tests/", 1)[0], check=True,
            capture_output=True, text=True).stdout
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here to read the parent's kernel from")
    spec = importlib.util.spec_from_loader("paddle_tpu.kernels._parent_kw",
                                           loader=None)
    old = importlib.util.module_from_spec(spec)
    old.__package__ = "paddle_tpu.kernels"
    exec(compile(text, "_parent_kw", "exec"), old.__dict__)
    shape, layer, B, dtype = POOLS["mistral-8-heads-pages-of-64"]
    args = _case(shape, layer, B, dtype, OFFSETS["anywhere"], 3, (2,))

    def trace(m):
        return str(jax.make_jaxpr(lambda *a: m.kv_token_write(
            *a, interpret=True))(*args))

    assert trace(KW) == trace(old)
