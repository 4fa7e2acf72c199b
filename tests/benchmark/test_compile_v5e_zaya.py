"""``zaya1-8b.reason-sat``'s kernels and its programs, compiled at the
cell's real shapes for a TPU v5e that is described and not attached (as
``test_compile_v5e_phi4flash.py``: nothing runs, so nothing here is a
result or a time). What the chip's compiler would refuse fails here, and
``memory_analysis`` says whether the cell fits and whether the pool and
the rows exist once.

The topology is described inside a module-scoped fixture, never while a
module is imported.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness.manifest import Manifest, build_config

CONF = Manifest().config("zaya1-8b")
SLOTS = CONF["serve"]["num_slots"]
GiB = 2 ** 30
BF16 = jnp.bfloat16


def _page():
    """The page size the cell runs at: the tracked entry for its shape."""
    import json
    import os

    from benchmark.harness.manifest import ROOT

    with open(os.path.join(ROOT, "autotune_cache.json")) as f:
        return json.load(f)[
            "paged:tpu:bfloat16:b64h8kv2d128:m16400"]["page_size"]


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
    assert (SLOTS, CONF["serve"]["pool_tokens"],
            CONF["serve"]["max_len"]) == (64, 245760, 16400)
    assert CONF["serve"]["pool_tokens"] % _page() == 0


@pytest.mark.parametrize("rows,tile", [(64, 16), (256, 16), (2048, 128),
                                       (16384, 512)])
def test_expert_kernel_at_a_decode_step_and_at_the_top_bucket(one_chip, rows,
                                                              tile):
    """Every row through its own expert of 16 x three 2,048 x 2,048
    matrices, read where they lie in the 20 layers' stack: a decode
    step's 64 rows in tiles of 16, the top bucket's 16,384 in tiles of
    512; nothing of the stack's size is made."""
    from paddle_tpu.kernels import moe_experts as M

    w = _s((20, 16, 2048, 2048), BF16, one_chip)
    x = _s((rows, 2048), BF16, one_chip)
    assert M.supported(x, w) and M.row_tile(rows, 16, BF16) == tile
    c = jax.jit(lambda x, e, g, u, d, layer: M.expert_mlp(
        x, e, g, u, d, layer, name="moe_expert_mlp_decode")).lower(
        x, _s((rows,), jnp.int32, one_chip), w, w, w,
        _s((), jnp.int32, one_chip)).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text and "moe_expert_mlp_decode" in text
    assert c.memory_analysis().temp_size_in_bytes < 0.3 * GiB


def test_paged_kernel_at_two_key_heads(one_chip):
    """8 queries over 2 key-value heads of 128, the cell's slots, its 20
    layers of pages at the tracked size and block tables of a whole
    ``max_len``."""
    from paddle_tpu.kernels.paged_attention import (ragged_paged_attention,
                                                    supported)

    page = _page()
    q = _s((SLOTS, 8, 128), BF16, one_chip)
    pool = _s((20, CONF["serve"]["pool_tokens"] // page, 2, page, 128), BF16,
              one_chip)
    bt = _s((SLOTS, -(-CONF["serve"]["max_len"] // page)), jnp.int32,
            one_chip)
    assert supported(q, pool, bt)
    c = jax.jit(lambda q, k, v, bt, n, layer: ragged_paged_attention(
        q, k, v, bt, n, layer=layer)).lower(
        q, pool, pool, bt, _s((SLOTS,), jnp.int32, one_chip),
        _s((), jnp.int32, one_chip)).compile()
    assert "paged_decode_attn" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def _programs(one_chip, monkeypatch):
    from paddle_tpu import kernels
    from paddle_tpu.inference.paged import init_pool

    # the described chip: the dispatchers take their kernels, as on a TPU
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    kernels.register()
    family, cfg = build_config(CONF, "serve")
    page = _page()
    params = _on(jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    cache = _on(jax.eval_shape(lambda: init_pool(
        cfg, CONF["serve"]["pool_tokens"] // page, page,
        state_shapes=family.state_shapes(cfg), state_rows=SLOTS)), one_chip)
    return family, cfg, params, cache, page


def test_decode_chunk_fits_the_chip_and_holds_the_cache_once(one_chip,
                                                             monkeypatch):
    """The turbo decode chunk (16 steps) at the cell's sizes: weights, the
    page pool and a row of tails a slot (and one nobody owns) are its
    arguments and come back in their own buffers; what the program needs
    beside them is far less than a second copy of the pool, and nothing of
    the experts' size (8 GB) is made. The numbers are in the
    configuration's ``pool_arithmetic``."""
    from paddle_tpu.inference import engine

    family, cfg, params, cache, page = _programs(one_chip, monkeypatch)
    chunk, maxp = 16, -(-CONF["serve"]["max_len"] // page)

    def decode_chunk(*args):
        return engine._decode_chunk(family, cfg, chunk, False, *args)

    def i32(*shape):
        return _s(shape, jnp.int32, one_chip)

    c = jax.jit(decode_chunk, donate_argnums=(1,)).lower(
        params, cache, i32(SLOTS, maxp), i32(SLOTS), i32(SLOTS), i32(SLOTS),
        _s((SLOTS,), jnp.bool_, one_chip), i32(SLOTS),
        _s((chunk, SLOTS, 2), jnp.uint32, one_chip),
        _s((SLOTS,), jnp.float32, one_chip), i32(SLOTS), i32(SLOTS)).compile()
    text = c.as_text()
    for name in ("paged_decode_attn", "moe_expert_mlp_decode"):
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
    assert mem.temp_size_in_bytes < 1.0 * GiB < pool     # no second pool
    # 15.75 GiB usable, 0.26 of them the runtime's own; at least 75% full
    assert 0.75 * 15.75 * GiB < _total(mem) < 15.45 * GiB, _total(mem) / GiB


@pytest.mark.parametrize("g,s", [(1, 16384), (2, 8192), (64, 128)])
def test_widest_prefill_programs_fit_beside_the_cache(one_chip, monkeypatch,
                                                      g, s):
    """The prefill programs that hold most: one prompt of the top bucket,
    two of the next, and the warm-up's one group of every slot (in passes
    of 8 rows)."""
    from paddle_tpu.inference.paged import cache_prefill

    family, cfg, params, cache, page = _programs(one_chip, monkeypatch)

    def i32(*shape):
        return _s(shape, jnp.int32, one_chip)

    c = jax.jit(lambda p, ids, ca, rows, slen, srows: cache_prefill(
        family, p, ids, cfg, ca, rows, slen, srows),
        donate_argnums=(2,)).lower(
        params, i32(g, s), cache, i32(g, s // page), i32(g),
        i32(g)).compile()
    mem = c.memory_analysis()
    print(f"prefill {g} x {s}: temporaries {mem.temp_size_in_bytes / GiB:.3f}"
          f" GiB, total {_total(mem) / GiB:.3f}")
    text = c.as_text()
    assert "flash_fwd" in text and "moe_expert_mlp_prefill" in text
    assert _total(mem) < 15.45 * GiB, _total(mem) / GiB
