"""The paged decode kernel, compiled for a TPU v5e that is described and
not attached, at the shapes the serving engine can hand it beyond the
benchmark's own cell (tests/benchmark/test_compile_v5e.py keeps that one):
a block the chip's compiler refuses, or a VMEM budget that does not fit,
fails here and costs no chip time; and the serving cells' 16-step decode
chunk, whose compiled form says whether the page pool is held once.
Nothing runs, so nothing here is a result or a time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU's library, and
every xdist worker imports every test file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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


def _s(shape, dtype, where):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=where)


# the two serving cells' head counts, layers, slots, pool and table pages
@pytest.mark.parametrize("nh,kv,L,B,P,maxp", [
    (32, 8, 16, 64, 3328, 288), (20, 4, 6, 128, 5632, 129)],
    ids=["mistral-7b-v0.3", "falcon-h1-34b"])
def test_paged_decode_kernel_compiles_on_a_layer_of_the_whole_pool(
        one_chip, nh, kv, L, B, P, maxp):
    """The pool with its layer axis and ``layer`` a traced scalar: the
    kernel reads it where it lies, and the call makes nothing of a pool
    half's or a layer's size."""
    from paddle_tpu.kernels import paged_attention as PA

    q = _s((B, nh, 128), jnp.bfloat16, one_chip)
    pool = _s((L, P, kv, 16, 128), jnp.bfloat16, one_chip)
    bt = _s((B, maxp), jnp.int32, one_chip)
    assert PA.supported(q, pool, bt)
    c = jax.jit(lambda q, k, v, bt, ln, l: PA.ragged_paged_attention(
        q, k, v, bt, ln, layer=l)).lower(
            q, pool, pool, bt, _s((B,), jnp.int32, one_chip),
            _s((), jnp.int32, one_chip)).compile()
    text = c.as_text()
    assert text.count("tpu_custom_call") == 1 and "paged_decode_attn" in text
    assert c.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<type>\S+) (?P<op>[\w\-]+)\(")


def _pool_shaped(text, shapes):
    """(name, opcode, line) of every instruction of the optimised module
    whose result is one array of one of ``shapes`` (type strings up to the
    layout, e.g. ``bf16[16,3328,8,16,128]``)."""
    found = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m["type"].split("{")[0] in shapes:
            found.append((m["name"], m["op"], line))
    return found


@pytest.mark.parametrize("name,temp_gib", [("mistral-7b-v0.3", 1.0),
                                           ("falcon-h1-34b", 0.5)])
def test_decode_chunk_holds_the_pool_once(one_chip, monkeypatch, name,
                                          temp_gib):
    """The engine's 16-step decode chunk at a serving cell's sizes: the
    donated cache (the pool, and a recurrent family's state) comes back in
    its own buffers, the temporaries are far under a pool half, and the
    optimised module has no instruction that makes a pool half or a layer
    of one but the two ``attn.kv_write`` scatters, which write into the
    carry's own buffer, and the bitcasts that show the kernel each half
    as [L * P, ...]: no copy, no slice, no second buffer. (With the
    pool as the layer scan's xs/ys this read 4.20 GiB of temporaries for
    Mistral's cell, a whole pool and 0.95.)"""
    from benchmark.harness.manifest import Manifest, build_config
    from paddle_tpu import kernels
    from paddle_tpu.inference import engine
    from paddle_tpu.inference.paged import init_pool

    conf = Manifest().config(name)
    slots, page = conf["serve"]["num_slots"], 16
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    family, cfg = build_config(conf, "serve")
    shapes = getattr(family, "state_shapes", None)

    def on(tree):
        return jax.tree.map(lambda a: _s(a.shape, a.dtype, one_chip), tree)

    params = on(jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0))))
    cache = on(jax.eval_shape(lambda: init_pool(
        cfg, conf["serve"]["pool_tokens"] // page, page,
        state_shapes=shapes(cfg) if shapes else None, state_rows=slots)))
    chunk = 16

    def i32(*shape):
        return _s(shape, jnp.int32, one_chip)

    c = jax.jit(
        lambda *a: engine._decode_chunk(family, cfg, chunk, False, *a),
        donate_argnums=(1,)).lower(
            params, cache, i32(slots, conf["serve"]["max_len"] // page),
            i32(slots) if shapes else None, i32(slots), i32(slots),
            _s((slots,), jnp.bool_, one_chip), i32(slots),
            _s((chunk, slots, 2), jnp.uint32, one_chip),
            _s((slots,), jnp.float32, one_chip), i32(slots),
            i32(slots)).compile()
    mem = c.memory_analysis()
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert held == conf["serve"]["pool_tokens"] * conf["kv_bytes_per_token"] \
        + (slots + 1) * conf.get("state_bytes_per_slot", 0)
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < temp_gib * GiB, \
        mem.temp_size_in_bytes / GiB

    half = cache["k"].shape
    flat = (half[0] * half[1],) + half[2:]
    kinds = {"bf16[%s]" % ",".join(map(str, s))
             for s in (half, half[1:], flat)}
    made = [(n, op, line) for n, op, line in _pool_shaped(c.as_text(), kinds)
            if op not in ("parameter", "get-tuple-element", "bitcast")]
    assert made, "the scatters that append a step's keys and values"
    for n, op, line in made:
        assert not re.search("copy|dynamic-slice|dynamic-update-slice",
                             n + " " + op), line
        assert "AllocateBuffer" not in line, line
        assert op == "scatter" or (op == "fusion"
                                   and "attn.kv_write/scatter" in line), line
    # one fused scatter a pool half, each into its operand's buffer
    fused = [line for _, op, line in made if op == "fusion"]
    assert len(fused) == 2
    assert all('"aliasing_operands"' in line for line in fused)
