"""``command-a-plus-05-2026.longdoc-sat``'s programs compiled at the cell's
real shapes for a TPU v5e that is described and not attached (as
``test_compile_v5e_axk1.py``: nothing runs, so nothing here is a result or
a time). What the chip's compiler would refuse fails here, and
``memory_analysis`` says whether the cell fits and whether the pool and
the rings exist once: the decode chunk, the widest prefill (one prompt of
the top bucket) and the warm-up's group of every slot.

The topology is described inside a module-scoped fixture, never while a
module is imported.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness.manifest import ROOT, Manifest, build_config

CONF = Manifest().config("command-a-plus-05-2026")
SLOTS = CONF["serve"]["num_slots"]
GiB = 2 ** 30
# held aside in every compile, as an argument the program never reads: the
# 0.26 GiB the runtime keeps of the chip's 15.75, and some room. XLA gives
# a program's temporaries what the chip leaves it, so a compile that
# passes with this held aside leaves the runtime its own.
RESERVE = 0.3 * GiB


def _page():
    """The page size the cell runs at: the tracked cache has no entry of
    its own for the shape, so the engine takes its nearest tuned
    neighbour's (``autotune.paged_page_size``, cached mode)."""
    from paddle_tpu.kernels.autotune import AutotuneCache

    key = (f"paged:tpu:bfloat16:b{SLOTS}h128kv8d128:"
           f"m{-(-CONF['serve']['max_len'] // 16) * 16}")
    cache = AutotuneCache(os.path.join(ROOT, "autotune_cache.json"))
    assert cache.get(key) is None
    return cache.get_nearest(key)[1]["page_size"]


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


@pytest.mark.parametrize("what", ["decode", (1, 16384), (SLOTS, 1024)],
                         ids=["decode_chunk", "prefill_1x16384",
                              f"prefill_{SLOTS}x1024"])
def test_the_cells_programs_fit_the_chip_and_hold_the_cache_once(
        one_chip, monkeypatch, what):
    """The turbo decode chunk (16 steps: the paged kernel over the full
    layer's pool, its window form over the three rings, the in-place
    writes and the held experts' kernel in it), one prompt of the top
    bucket and the warm-up's group of every slot (in passes of 8 rows), at
    the cell's sizes. The cache is an argument that comes back in its own
    buffer; the numbers are in the configuration's ``pool_arithmetic``."""
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
        state_shapes=family.state_shapes(cfg), state_rows=SLOTS,
        pool_layout=family.pool_layout(cfg))))
    reserve = _s((int(RESERVE) // 2,), jnp.bfloat16, one_chip)
    if what == "decode":
        chunk, maxp = 16, -(-CONF["serve"]["max_len"] // page)
        c = jax.jit(lambda _, *a: engine._decode_chunk(
            family, cfg, chunk, False, *a), donate_argnums=(2,),
            keep_unused=True).lower(
            reserve, params, cache, i32(SLOTS, maxp), i32(SLOTS),
            i32(SLOTS), i32(SLOTS), _s((SLOTS,), jnp.bool_, one_chip),
            i32(SLOTS), _s((chunk, SLOTS, 2), jnp.uint32, one_chip),
            _s((SLOTS,), jnp.float32, one_chip), i32(SLOTS),
            i32(SLOTS)).compile()
        names = ("paged_decode_attn", "paged_decode_attn_window",
                 "kv_token_write", "moe_expert_mlp_decode")
    else:
        g, s = what
        c = jax.jit(lambda _, p, ids, ca, rows, slen, sr: cache_prefill(
            family, p, ids, cfg, ca, rows, slen, sr),
            donate_argnums=(3,), keep_unused=True).lower(
            reserve, params, i32(g, s), cache, i32(g, s // page), i32(g),
            i32(g)).compile()
        names = ("flash_fwd", "moe_expert_mlp_prefill")
    text = c.as_text()
    for name in names:
        assert name in text, name
    mem = c.memory_analysis()
    kept = (CONF["serve"]["pool_tokens"] * CONF["kv_bytes_per_token"]
            + (SLOTS + 1) * CONF["state_bytes_per_slot"])
    print(f"{what}: arguments (the reserve among them) "
          f"{mem.argument_size_in_bytes / GiB:.3f} GiB, "
          f"alias {mem.alias_size_in_bytes / GiB:.3f}, temporaries "
          f"{mem.temp_size_in_bytes / GiB:.3f}, total "
          f"{_total(mem) / GiB:.3f}")
    assert mem.argument_size_in_bytes >= 2 * CONF["param_count"] + kept \
        + RESERVE
    assert mem.alias_size_in_bytes >= kept            # donated, held once
    assert mem.temp_size_in_bytes < 2.5 * GiB < kept  # no second cache
    # at least 75% of the chip's 15.75 GiB is the cell's own
    assert 0.75 * 15.75 * GiB < _total(mem) - RESERVE, _total(mem) / GiB


def test_the_weights_and_the_routers_balance_fit_the_chip(one_chip,
                                                           monkeypatch):
    """The one jitted call that makes the weights from the seed, the
    routers' balance over random sequences among it (the held experts'
    kernel runs in it), fits the chip beside the runtime's share: it runs
    before the pool and the rings exist."""
    from paddle_tpu import kernels

    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    kernels.register()
    family, cfg = build_config(CONF, "serve")
    c = jax.jit(lambda k: family.init_params(cfg, k)).lower(
        _s((2,), jnp.uint32, one_chip)).compile()
    assert "moe_expert_mlp_prefill" in c.as_text()
    mem = c.memory_analysis()
    assert mem.output_size_in_bytes >= 2 * CONF["param_count"]
    assert _total(mem) + RESERVE < 15.75 * GiB, _total(mem) / GiB
