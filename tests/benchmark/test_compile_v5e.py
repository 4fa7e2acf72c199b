"""The main path's kernels, compiled at the cells' real shapes for a TPU
v5e that is described and not attached: what the chip's compiler would
refuse (a block off the tiling, too much fast memory) fails here, at no
chip time. Nothing runs, so nothing here is a result or a time.

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
    except Exception as e:  # no TPU compiler in this installation
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


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_paged_decode_kernel_mistral_64_slots(one_chip):
    """32 query / 8 KV heads of 128, 64 slots, pages of 16, the serving
    cells' pool of 3,328 pages and block tables of 288 pages a slot."""
    from paddle_tpu.kernels.paged_attention import ragged_paged_attention

    pages = _s((3328, 8, 16, 128), jnp.bfloat16, one_chip)
    c = jax.jit(ragged_paged_attention).lower(
        _s((64, 32, 128), jnp.bfloat16, one_chip), pages, pages,
        _s((64, 288), jnp.int32, one_chip),
        _s((64,), jnp.int32, one_chip)).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("heads,kv_heads", [(32, 8), (16, 16)],
                         ids=["mistral-gqa-32-8", "deepseek-mha-16"])
def test_flash_forward_and_backward_4x4096(one_chip, heads, kv_heads):
    """Causal flash at 4 x 4,096 x heads x 128, forward and both backward
    kernels, for the two head layouts the cells train and prefill with."""
    from paddle_tpu.kernels.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    q = _s((4, 4096, heads, 128), jnp.bfloat16, one_chip)
    kv = _s((4, 4096, kv_heads, 128), jnp.bfloat16, one_chip)
    c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    assert c.as_text().count("tpu_custom_call") >= 3
