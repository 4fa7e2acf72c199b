"""The dense flash kernels, compiled for a TPU v5e that is described and
not attached, at the blocks the dispatcher's shape rule gives them: the
train cell's call, forward and both backward kernels, and the serving
cells' prefill groups. A block the chip's compiler refuses, a slice of a
resident span off the tiling, or a working set past the VMEM the kernels
ask for fails here and costs no chip time
(tests/benchmark/test_compile_v5e.py keeps the call at the signature's
defaults). Nothing runs, so nothing here is a result or a time.

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


@pytest.fixture
def dispatch(monkeypatch):
    """The flash dispatcher as it runs on the chip: the kernel path,
    blocks from the autotuner in the benchmark's "cached" mode on a cache
    with no entry, so the shape rule's."""
    from paddle_tpu import kernels
    from paddle_tpu.kernels import autotune

    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "cached")
    monkeypatch.setattr(autotune, "_CACHE",
                        autotune.AutotuneCache("/nonexistent/cache.json"))
    kernels.reset_dispatch_stats()
    return kernels._make_flash_dispatch(False)


def _qkv(b, s, h, kv, d, dtype, where):
    q = jax.ShapeDtypeStruct((b, s, h, d), dtype, sharding=where)
    k = jax.ShapeDtypeStruct((b, s, kv, d), dtype, sharding=where)
    return q, k, k


def _used(b, s, h, kv, d, dtype):
    from paddle_tpu.kernels import autotune
    return autotune.used_blocks()[
        f"flash:cpu:{jnp.dtype(dtype).name}:b{b}h{h}kv{kv}:"
        f"q{s}k{s}d{d}:c1"]


# batch, sequence, heads, KV heads, head dim, dtype: the train cell's
# call; what else trains through the kernel (GQA, float32, head dim 256);
# a sequence whose K and V do not fit whole (spans of 8,192)
TRAIN = {
    "deepseek-moe-16b.train-4k": (4, 4096, 16, 16, 128, jnp.bfloat16),
    "gqa-32-8": (2, 4096, 32, 8, 128, jnp.bfloat16),
    "float32": (2, 1024, 8, 8, 128, jnp.float32),
    "head-dim-256": (2, 2048, 8, 2, 256, jnp.bfloat16),
    "spans-of-a-32k-sequence": (1, 32768, 2, 1, 128, jnp.bfloat16),
}


@pytest.mark.parametrize("case", TRAIN)
def test_forward_and_both_backward_kernels_compile(one_chip, dispatch, case):
    from paddle_tpu import kernels

    shape = TRAIN[case]

    def loss(q, k, v):
        return dispatch(q, k, v, causal=True).astype(jnp.float32).sum()

    c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_qkv(*shape, one_chip)).compile()
    text = c.as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text
    assert kernels.dispatch_stats()["flash_fallback"] == 0
    used = _used(*shape)
    assert used["source"] == "shape-rule"
    if case == "deepseek-moe-16b.train-4k":
        assert used["blocks"] == [512, 512]


# the serving cells' prefill programs: a group of 1-8 rows of one bucket,
# Mistral's 32 / 8 heads and Falcon-H1's 20 / 4
@pytest.mark.parametrize("heads,kv_heads", [(32, 8), (20, 4)],
                         ids=["mistral-7b-v0.3", "falcon-h1-34b"])
@pytest.mark.parametrize("rows,bucket", [
    (8, 128), (8, 256), (4, 512), (2, 1024), (1, 2048), (8, 2048)])
def test_a_prefill_groups_forward_kernel_compiles(one_chip, dispatch, rows,
                                                  bucket, heads, kv_heads):
    from paddle_tpu import kernels

    shape = (rows, bucket, heads, kv_heads, 128, jnp.bfloat16)
    c = jax.jit(lambda q, k, v: dispatch(q, k, v, causal=True)).lower(
        *_qkv(*shape, one_chip)).compile()
    text = c.as_text()
    assert text.count("tpu_custom_call") == 1 and "flash_fwd" in text
    assert kernels.dispatch_stats()["flash_fallback"] == 0
    assert _used(*shape)["source"] == "shape-rule"
