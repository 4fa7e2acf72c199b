"""``ax-k1.reason-sat``'s programs compiled at the cell's real shapes for a
TPU v5e that is described and not attached (as ``test_compile_v5e_zaya.py``:
nothing runs, so nothing here is a result or a time). What the chip's
compiler would refuse fails here, and ``memory_analysis`` says whether the
cell fits and whether the latent pool exists once: the decode chunk, the
widest prefill (one prompt of the top bucket) and the warm-up's group of
every slot.

The topology is described inside a module-scoped fixture, never while a
module is imported.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness.manifest import ROOT, Manifest, build_config

CONF = Manifest().config("ax-k1")
SLOTS = CONF["serve"]["num_slots"]
GiB = 2 ** 30


def _page():
    """The page size the cell runs at: the tracked entry for its shape."""
    with open(os.path.join(ROOT, "autotune_cache.json")) as f:
        return json.load(f)[
            "paged:tpu:bfloat16:b256h64kv64d192:m16400"]["page_size"]


@pytest.fixture(scope="module")
def one_chip():
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


def _total(mem):
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


@pytest.mark.parametrize("what", ["decode", (1, 16384), (256, 128)],
                         ids=["decode_chunk", "prefill_1x16384",
                              "prefill_256x128"])
def test_the_cells_programs_fit_the_chip_and_hold_the_pool_once(
        one_chip, monkeypatch, what):
    """The turbo decode chunk (16 steps: the latent kernel, the in-place
    row write and the held experts' kernel in it), one prompt of the top
    bucket and the warm-up's group of all 256 slots (in passes of 8 rows),
    at the cell's sizes. The pool is an argument that comes back in its
    own buffer; the numbers are in the configuration's
    ``pool_arithmetic``."""
    from paddle_tpu import kernels
    from paddle_tpu.inference import engine
    from paddle_tpu.inference.paged import cache_prefill, init_pool

    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    kernels.register()
    family, cfg = build_config(CONF, "serve")
    page = _page()

    def on(tree):
        return jax.tree.map(lambda a: _s(a.shape, a.dtype, one_chip), tree)

    def i32(*shape):
        return _s(shape, jnp.int32, one_chip)

    params = on(jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0))))
    cache = on(jax.eval_shape(lambda: init_pool(
        cfg, CONF["serve"]["pool_tokens"] // page, page,
        pool_layout=family.pool_layout(cfg))))
    if what == "decode":
        chunk, maxp = 16, -(-CONF["serve"]["max_len"] // page)
        c = jax.jit(lambda *a: engine._decode_chunk(
            family, cfg, chunk, False, *a), donate_argnums=(1,)).lower(
            params, cache, i32(SLOTS, maxp), None, i32(SLOTS), i32(SLOTS),
            _s((SLOTS,), jnp.bool_, one_chip), i32(SLOTS),
            _s((chunk, SLOTS, 2), jnp.uint32, one_chip),
            _s((SLOTS,), jnp.float32, one_chip), i32(SLOTS),
            i32(SLOTS)).compile()
        names = ("mla_decode", "kv_token_write", "moe_expert_mlp_decode")
    else:
        g, s = what
        c = jax.jit(lambda p, ids, ca, rows, slen: cache_prefill(
            family, p, ids, cfg, ca, rows, slen), donate_argnums=(2,)).lower(
            params, i32(g, s), cache, i32(g, s // page), i32(g)).compile()
        names = ("flash_fwd", "moe_expert_mlp_prefill")
    text = c.as_text()
    for name in names:
        assert name in text, name
    mem = c.memory_analysis()
    pool = CONF["serve"]["pool_tokens"] * CONF["kv_bytes_per_token"]
    print(f"{what}: arguments {mem.argument_size_in_bytes / GiB:.3f} GiB, "
          f"alias {mem.alias_size_in_bytes / GiB:.3f}, temporaries "
          f"{mem.temp_size_in_bytes / GiB:.3f}, total "
          f"{_total(mem) / GiB:.3f}")
    assert mem.argument_size_in_bytes >= 2 * CONF["param_count"] + pool
    assert mem.alias_size_in_bytes >= pool            # donated, held once
    assert mem.temp_size_in_bytes < 3.5 * GiB < pool  # no second pool
    # 15.75 GiB usable, 0.26 of them the runtime's own; at least 75% full
    assert 0.75 * 15.75 * GiB < _total(mem) < 15.45 * GiB, \
        _total(mem) / GiB
