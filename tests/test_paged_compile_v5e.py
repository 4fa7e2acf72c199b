"""The paged decode kernel, compiled for a TPU v5e that is described and
not attached, at the shapes the serving engine can hand it beyond the
benchmark's own cell (tests/benchmark/test_compile_v5e.py keeps that one):
a block the chip's compiler refuses, or a VMEM budget that does not fit,
fails here and costs no chip time. Nothing runs, so nothing here is a
result or a time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU's library, and
every xdist worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    import os

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


# slots, query heads, KV heads, head dim, page size, pool pages, table
# pages, q dtype, int8 pages
CASES = {
    "deepseek-moe-mha-16-16": (64, 16, 16, 128, 16, 3328, 288,
                               jnp.bfloat16, False),
    "int8-pages-of-32": (64, 32, 8, 128, 32, 1664, 144, jnp.bfloat16, True),
    "32-slots-tables-of-1024": (32, 32, 8, 128, 16, 8192, 1024,
                                jnp.bfloat16, False),
    "int8-tables-of-1024": (32, 32, 8, 128, 32, 8192, 1024,
                            jnp.bfloat16, True),
    "group-8-head-dim-256": (8, 64, 8, 256, 16, 512, 64, jnp.bfloat16,
                             False),
    "float32-pages-of-8": (8, 8, 2, 128, 8, 256, 16, jnp.float32, False),
    "one-slot-table-of-one-page": (1, 32, 8, 128, 16, 64, 1, jnp.bfloat16,
                                   False),
}


@pytest.mark.parametrize("case", CASES)
def test_paged_decode_kernel_compiles(one_chip, case):
    from paddle_tpu.kernels import paged_attention as PA

    B, nh, kv, hd, ps, P, maxp, dtype, quant = CASES[case]

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q = s((B, nh, hd), dtype)
    pages = s((P, kv, ps, hd), jnp.int8 if quant else dtype)
    bt, ln = s((B, maxp), jnp.int32), s((B,), jnp.int32)
    assert PA.supported(q, pages, bt, quant=quant)
    if quant:
        scales = s((P, kv), jnp.float32)
        c = jax.jit(lambda q, k, v, bt, ln, ks, vs: PA.ragged_paged_attention(
            q, k, v, bt, ln, k_scales=ks, v_scales=vs)).lower(
                q, pages, pages, bt, ln, scales, scales).compile()
    else:
        c = jax.jit(PA.ragged_paged_attention).lower(
            q, pages, pages, bt, ln).compile()
    text = c.as_text()
    # the names and the shape the benchmark's readers match: one custom
    # call, called paged_decode_attn, that returns one 4-D array
    assert text.count("tpu_custom_call") == 1
    assert "paged_decode_attn" in text
