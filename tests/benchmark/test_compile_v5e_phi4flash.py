"""``phi-4-mini-flash.reason-sat``'s kernels and its programs, compiled at
the cell's real shapes for a TPU v5e that is described and not attached
(as ``test_compile_v5e_falcon_h1.py``: nothing runs, so nothing here is a
result or a time). What the chip's compiler would refuse fails here, and
``memory_analysis`` says whether the cell fits and whether the pool, the
rings and the states exist once.

The topology is described inside a module-scoped fixture, never while a
module is imported.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness.manifest import Manifest, build_config

CONF = Manifest().config("phi-4-mini-flash")
SLOTS = CONF["serve"]["num_slots"]
PAGE = 16
PAGES = CONF["serve"]["pool_tokens"] // PAGE
MAXP = CONF["serve"]["max_len"] // PAGE
GiB = 2 ** 30
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _s(shape, dtype, where):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=where)


def _on(tree, where):
    return jax.tree.map(lambda a: _s(a.shape, a.dtype, where), tree)


def _total(mem):
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def test_sizes_are_the_cells():
    assert (SLOTS, PAGES, MAXP) == (128, 32768, 1025)


def test_s6_update_kernel_a_slot_grid_in_place(one_chip):
    """The cell's slots x [16, 5120] float32 a layer, 9 layers and the row
    nobody owns: the kernel compiles, its output is its input's buffer and
    the call needs no memory of the state's size beside its arguments."""
    from paddle_tpu.kernels.ssm import s6_supported, ssm_state_update_s6

    f32 = jnp.float32
    state = _s((9, SLOTS + 1, 16, 5120), f32, one_chip)
    dt = _s((SLOTS, 5120), f32, one_chip)
    assert s6_supported(state, dt)
    c = jax.jit(ssm_state_update_s6, donate_argnums=(0,)).lower(
        state, _s((), jnp.int32, one_chip), _s((SLOTS,), jnp.int32, one_chip),
        dt, dt, _s((16, 5120), f32, one_chip), _s((SLOTS, 16), f32, one_chip),
        _s((SLOTS, 16), f32, one_chip)).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text and "ssm_state_update_s6" in text
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= 9 * (SLOTS + 1) * 16 * 5120 * 4
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("g,s", [(1, 16384), (2, 8192), (8, 128), (4, 640)])
def test_s6_prefill_scan_kernel(one_chip, g, s):
    """The prefill's recurrence at d_inner 5,120, d_state 16: the top
    bucket, the warm-up's pass of 8 rows, the check's 640 tokens."""
    from paddle_tpu.kernels.ssm import s6_scan, s6_scan_supported

    f32 = jnp.float32
    x = _s((g, s, 5120), BF16, one_chip)
    a = _s((16, 5120), f32, one_chip)
    bc = _s((g, s, 16), BF16, one_chip)
    assert s6_scan_supported(x, a)
    c = jax.jit(s6_scan).lower(x, _s((g, s, 5120), f32, one_chip), a, bc,
                               bc).compile()
    assert "s6_scan" in c.as_text() and "tpu_custom_call" in c.as_text()


def test_paged_kernel_pairs_of_64_as_heads_of_128(one_chip):
    """40 queries of 128 (``q1|0``, ``0|q2``) over 10 key/value pairs of
    128, the cell's slots, its ONE layer of 32,768 pages of 16 and block
    tables of 1,025 pages a slot."""
    from paddle_tpu.kernels.paged_attention import (ragged_paged_attention,
                                                    supported)

    q = _s((SLOTS, 40, 128), BF16, one_chip)
    pool = _s((1, PAGES, 10, PAGE, 128), BF16, one_chip)
    bt = _s((SLOTS, MAXP), jnp.int32, one_chip)
    assert supported(q, pool, bt)
    c = jax.jit(lambda q, k, v, bt, n: ragged_paged_attention(
        q, k, v, bt, n, scale=0.125, layer=0)).lower(
        q, pool, pool, bt, _s((SLOTS,), jnp.int32, one_chip)).compile()
    assert "paged_decode_attn" in c.as_text()


def test_window_kernel_over_the_rings(one_chip):
    """The same kernel over 8 layers of rings of 33 pages a row, with the
    window's lower bound: named ``paged_decode_attn_window``."""
    from paddle_tpu.kernels.paged_attention import ring_window_attention

    ring = _s((8, SLOTS + 1, 33, 10, PAGE, 128), BF16, one_chip)
    c = jax.jit(lambda q, rk, rv, layer, rows, n: ring_window_attention(
        q, rk, rv, layer, rows, n, window=512, scale=0.125)).lower(
        _s((SLOTS, 40, 128), BF16, one_chip), ring, ring,
        _s((), jnp.int32, one_chip), _s((SLOTS,), jnp.int32, one_chip),
        _s((SLOTS,), jnp.int32, one_chip)).compile()
    assert "paged_decode_attn_window" in c.as_text()
    # the rings are read where they lie: nothing of their size is made
    assert c.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("g,s", [(1, 16384), (2, 8192), (2, 128)])
def test_flash_forward_with_a_window(one_chip, g, s):
    import importlib

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    q = _s((g, s, 40, 128), BF16, one_chip)
    kv = _s((g, s, 10, 128), BF16, one_chip)
    assert fa.supported(q, kv, kv)
    c = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, scale=0.125, window=512)).lower(
        q, kv, kv).compile()
    assert "flash_fwd" in c.as_text()


def _programs(one_chip, monkeypatch):
    from paddle_tpu import kernels
    from paddle_tpu.inference.paged import init_pool

    # the described chip: the dispatchers take their kernels, as on a TPU
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    kernels.register()
    family, cfg = build_config(CONF, "serve")
    params = _on(jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    cache = _on(jax.eval_shape(lambda: init_pool(
        cfg, PAGES, PAGE, state_shapes=family.state_shapes(cfg),
        state_rows=SLOTS, pool_layout=family.pool_layout(cfg))), one_chip)
    return family, cfg, params, cache


def test_decode_chunk_fits_the_chip_and_holds_the_cache_once(one_chip,
                                                             monkeypatch):
    """The turbo decode chunk (16 steps) at the cell's sizes: weights, the
    one-layer page pool and a row of rings and states a slot (and one
    nobody owns) are its arguments and come back in their own buffers;
    what the program needs beside them is far less than a second copy of
    the pool or of the rows. The numbers are in the configuration's
    ``pool_arithmetic``."""
    from paddle_tpu.inference import engine

    family, cfg, params, cache = _programs(one_chip, monkeypatch)
    chunk = 16

    def decode_chunk(*args):
        return engine._decode_chunk(family, cfg, chunk, False, *args)

    def i32(*shape):
        return _s(shape, jnp.int32, one_chip)

    c = jax.jit(decode_chunk, donate_argnums=(1,)).lower(
        params, cache, i32(SLOTS, MAXP), i32(SLOTS), i32(SLOTS), i32(SLOTS),
        _s((SLOTS,), jnp.bool_, one_chip), i32(SLOTS),
        _s((chunk, SLOTS, 2), jnp.uint32, one_chip),
        _s((SLOTS,), jnp.float32, one_chip), i32(SLOTS), i32(SLOTS)).compile()
    text = c.as_text()
    for name in ("paged_decode_attn", "paged_decode_attn_window",
                 "ssm_state_update_s6"):
        assert name in text, name
    mem = c.memory_analysis()
    rows = (SLOTS + 1) * CONF["state_bytes_per_slot"]
    pool = CONF["serve"]["pool_tokens"] * CONF["kv_bytes_per_token"]
    weights = 2 * CONF["param_count"]
    print(f"decode chunk: arguments {mem.argument_size_in_bytes / GiB:.3f} "
          f"GiB, alias {mem.alias_size_in_bytes / GiB:.3f}, temporaries "
          f"{mem.temp_size_in_bytes / GiB:.3f}, total {_total(mem) / GiB:.3f}")
    assert mem.argument_size_in_bytes >= weights + rows + pool
    assert mem.alias_size_in_bytes >= rows + pool        # both donated
    # no second pool, no second set of rows
    assert mem.temp_size_in_bytes < 1.0 * GiB < min(pool, rows)
    # 15.75 GiB usable, 0.26 of them the runtime's own; at least 75% full
    assert 0.75 * 15.75 * GiB < _total(mem) < 15.45 * GiB, _total(mem) / GiB


@pytest.mark.parametrize("g,s", [(1, 16384), (2, 8192), (128, 128)])
def test_widest_prefill_programs_fit_beside_the_cache(one_chip, monkeypatch,
                                                      g, s):
    """The prefill programs that hold most: one prompt of the top bucket,
    two of the next, and the warm-up's one group of every slot (in passes
    of 8 rows)."""
    from paddle_tpu.inference.paged import cache_prefill

    family, cfg, params, cache = _programs(one_chip, monkeypatch)

    def i32(*shape):
        return _s(shape, jnp.int32, one_chip)

    c = jax.jit(lambda p, ids, ca, rows, slen, srows: cache_prefill(
        family, p, ids, cfg, ca, rows, slen, srows),
        donate_argnums=(2,)).lower(
        params, i32(g, s), cache, i32(g, s // PAGE), i32(g),
        i32(g)).compile()
    mem = c.memory_analysis()
    print(f"prefill {g} x {s}: temporaries {mem.temp_size_in_bytes / GiB:.3f}"
          f" GiB, total {_total(mem) / GiB:.3f}")
    assert "flash_fwd" in c.as_text()
    assert _total(mem) < 15.45 * GiB, _total(mem) / GiB
