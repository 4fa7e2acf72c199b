"""``falcon-h1-34b.decode-sat``'s kernels and its decode-chunk program,
compiled at the cell's real shapes for a TPU v5e that is described and not
attached (as ``test_compile_v5e.py``: nothing runs, so nothing here is a
result or a time). What the chip's compiler would refuse fails here, and
``memory_analysis`` says whether the cell fits and whether the recurrent
state exists once.

The topology is described inside a module-scoped fixture, never while a
module is imported.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness.manifest import Manifest, build_config

CONF = Manifest().config("falcon-h1-34b")
SLOTS = CONF["serve"]["num_slots"]
PAGE = 16
GiB = 2 ** 30


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


def test_state_update_kernel_a_slot_grid_in_place(one_chip):
    """The cell's slots x 32 heads x [256, 128] float32 a layer, 6 layers and the
    row nobody owns: the kernel compiles, its output is its input's buffer
    and the call needs no memory beside its arguments."""
    from paddle_tpu.kernels.ssm import ssm_state_update

    f32 = jnp.float32
    c = jax.jit(ssm_state_update, donate_argnums=(0,)).lower(
        _s((6, SLOTS + 1, 32, 256, 128), f32, one_chip),
        _s((), jnp.int32, one_chip), _s((SLOTS,), jnp.int32, one_chip),
        _s((SLOTS, 32), f32, one_chip), _s((SLOTS, 32, 128), f32, one_chip),
        _s((SLOTS, 2, 256), f32, one_chip),
        _s((SLOTS, 2, 256), f32, one_chip)).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text and "ssm_state_update" in text
    mem = c.memory_analysis()
    state = 6 * (SLOTS + 1) * 32 * 256 * 128 * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


def test_paged_decode_kernel_20_query_4_kv_heads(one_chip):
    """20 query / 4 KV heads of 128, the cell's slots, its pool of 5,632
    pages of 16 and block tables of 129 pages a slot."""
    from paddle_tpu.kernels.paged_attention import (ragged_paged_attention,
                                                    supported)

    pages = CONF["serve"]["pool_tokens"] // PAGE
    maxp = CONF["serve"]["max_len"] // PAGE
    assert (pages, maxp) == (5632, 129)
    q = _s((SLOTS, 20, 128), jnp.bfloat16, one_chip)
    pool = _s((pages, 4, PAGE, 128), jnp.bfloat16, one_chip)
    bt = _s((SLOTS, maxp), jnp.int32, one_chip)
    assert supported(q, pool, bt)
    c = jax.jit(ragged_paged_attention).lower(
        q, pool, pool, bt, _s((SLOTS,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()


def test_decode_chunk_fits_the_chip_and_holds_the_state_once(one_chip,
                                                             monkeypatch):
    """The turbo decode chunk (16 steps) at the cell's sizes: weights, the
    page pool and a row of state a slot (and one nobody owns) are its arguments, the state and the
    pool come back in their own buffers, and what it needs beside them
    (the pool's second copy, float32 logits, layout copies of q/k/v's
    weights) is far less than a second state would be. The numbers are in
    the configuration's ``pool_arithmetic``."""
    from paddle_tpu import kernels
    from paddle_tpu.inference import engine
    from paddle_tpu.inference.paged import init_pool

    # the described chip: the dispatchers take their kernels, as on a TPU
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    family, cfg = build_config(CONF, "serve")
    params = _on(jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    cache = _on(jax.eval_shape(lambda: init_pool(
        cfg, CONF["serve"]["pool_tokens"] // PAGE, PAGE,
        state_shapes=family.state_shapes(cfg), state_rows=SLOTS)), one_chip)
    chunk = 16

    def decode_chunk(*args):
        return engine._decode_chunk(family, cfg, chunk, False, *args)

    def i32(*shape):
        return _s(shape, jnp.int32, one_chip)

    c = jax.jit(decode_chunk, donate_argnums=(1,)).lower(
        params, cache, i32(SLOTS, CONF["serve"]["max_len"] // PAGE),
        i32(SLOTS), i32(SLOTS), i32(SLOTS),
        _s((SLOTS,), jnp.bool_, one_chip), i32(SLOTS),
        _s((chunk, SLOTS, 2), jnp.uint32, one_chip),
        _s((SLOTS,), jnp.float32, one_chip), i32(SLOTS), i32(SLOTS)).compile()
    assert c.as_text().count("tpu_custom_call") >= 2     # paged, ssm update
    mem = c.memory_analysis()
    state = (SLOTS + 1) * CONF["state_bytes_per_slot"]
    pool = CONF["serve"]["pool_tokens"] * CONF["kv_bytes_per_token"]
    weights = 2 * CONF["param_count"]
    assert mem.argument_size_in_bytes >= weights + state + pool
    assert mem.alias_size_in_bytes >= state + pool       # both donated
    # the pool's second copy and half a GiB of everything else: no room
    # in that for a second state
    assert mem.temp_size_in_bytes < pool + 0.6 * GiB < state
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # 15.75 GiB usable, 0.26 of them the runtime's own
    assert 0.80 * 15.75 * GiB < total < 15.45 * GiB, total / GiB
