"""The latent decode kernel alone, compiled for a TPU v5e that is described
and not attached, at ``ax-k1.reason-sat``'s shape (the cell's programs are
tests/benchmark/test_compile_v5e_axk1.py's): a block or a VMEM budget the
chip's compiler refuses fails here and costs no chip time. Nothing runs, so
nothing here is a result or a time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU's library, and every
xdist worker imports every test file.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _s(shape, dtype, where):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=where)


def test_the_latent_kernel_alone_at_the_cells_shape(one_chip):
    """``mla_decode`` alone over the cell's pool (256 slots, its pages,
    rows of 640, a table of ``max_len``): blocks of 512 tokens, their
    copies ``_AHEAD`` blocks ahead beside the two a trip computes on, and
    the chip's compiler takes it."""
    from benchmark.harness.manifest import ROOT, Manifest, build_config
    from paddle_tpu.kernels import mla_decode as M

    conf = Manifest().config("ax-k1")
    serve = conf["serve"]
    family, cfg = build_config(conf, "serve")
    layers, _, width, _ = family.pool_layout(cfg)
    with open(os.path.join(ROOT, "autotune_cache.json")) as f:
        page = json.load(f)[
            "paged:tpu:bfloat16:b256h64kv64d192:m16400"]["page_size"]
    slots, maxp = serve["num_slots"], -(-serve["max_len"] // page)
    pool = _s((layers, serve["pool_tokens"] // page, 1, page, width),
              jnp.bfloat16, one_chip)
    q = _s((slots, cfg.num_attention_heads, width), jnp.bfloat16, one_chip)
    bt = _s((slots, maxp), jnp.int32, one_chip)
    n = _s((slots,), jnp.int32, one_chip)

    def call(q, pool, bt, n, layer):
        return M.mla_decode(q, pool, bt, n, rank=cfg.kv_lora_rank,
                            scale=cfg.softmax_scale, layer=layer)

    jaxpr = jax.make_jaxpr(call)(q, pool, bt, n, jnp.int32(0))
    kernel, = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    scratch = [v.aval.shape for v in kernel.params["jaxpr"].invars]
    assert M._BUFFERS == M._AHEAD + 2 >= 3
    assert (M._BUFFERS, 512 // page, page, width) in scratch
    assert (cfg.num_attention_heads, 512) in scratch      # a block's scores
    c = jax.jit(call).lower(q, pool, bt, n, _s((), jnp.int32,
                                                 one_chip)).compile()
    assert "mla_decode" in c.as_text()
