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
import math
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


# leaf shape, slots, dtype: the four serving cells' pools (Phi's rings
# too) and one float32 case
KV_WRITES = {
    "mistral-7b-v0.3": ((16, 832, 8, 64, 128), 64, jnp.bfloat16),
    "falcon-h1-34b": ((6, 1408, 4, 64, 128), 128, jnp.bfloat16),
    "zaya1-8b": ((20, 3840, 2, 64, 128), 64, jnp.bfloat16),
    "phi-4-mini-flash-pool": ((1, 8192, 10, 64, 128), 128, jnp.bfloat16),
    "phi-4-mini-flash-rings": ((8, 129, 33, 10, 16, 128), 128, jnp.bfloat16),
    "float32-pages-of-8": ((4, 256, 2, 8, 128), 8, jnp.float32),
}


@pytest.mark.parametrize("case", KV_WRITES)
def test_kv_token_write_compiles_in_place(one_chip, case):
    """The decode step's KV write at a cell's shapes: one custom call
    named ``kv_token_write``, both pool halves back in their own buffers,
    and nothing of a half's size beside them."""
    from paddle_tpu.kernels import kv_write as KW

    shape, B, dtype = KV_WRITES[case]
    pool = _s(shape, dtype, one_chip)
    val = _s((B,) + shape[-3::2], dtype, one_chip)
    idx = _s((B,), jnp.int32, one_chip)
    assert KW.supported(pool, val)
    c = jax.jit(KW.kv_token_write, donate_argnums=(0, 1)).lower(
        pool, pool, _s((), jnp.int32, one_chip), idx, idx, val, val).compile()
    text = c.as_text()
    assert text.count("tpu_custom_call") == 1 and "kv_token_write" in text
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool.size * pool.dtype.itemsize
    assert mem.temp_size_in_bytes < 16 * 2 ** 20


# the cell's configuration, its page size, the most its temporaries may be
@pytest.mark.parametrize("name,page,temp_gib", [
    ("mistral-7b-v0.3", 16, 1.0), ("falcon-h1-34b", 16, 0.5),
    ("zaya1-8b", 64, 0.1), ("phi-4-mini-flash", 64, 0.3)])
def test_decode_chunk_holds_the_pool_once(one_chip, monkeypatch, name, page,
                                          temp_gib):
    """The engine's 16-step decode chunk at a serving cell's sizes: the
    donated cache (the pool, and a family's state and rings) comes back in
    its own buffers, the temporaries are far under a pool half, and the
    optimised module has no instruction that makes a pool half, a ring
    leaf or a layer of one: no copy, no slice, no second buffer, and no
    scatter. What writes a step's keys and values is one
    ``kv_token_write`` a layer scan's body (a stack's: one for the pool's
    layer, one for the rings), traced under ``attn.kv_write``, whose two
    results are its two pool operands' buffers; the bitcasts show it and
    the paged kernel each half as [L * P, ...]. (With the pool as the
    layer scan's xs/ys this read 4.20 GiB of temporaries for Mistral's
    cell, a whole pool and 0.95.)"""
    from benchmark.harness.manifest import Manifest, build_config
    from paddle_tpu import kernels
    from paddle_tpu.inference import engine
    from paddle_tpu.inference.paged import init_pool

    conf = Manifest().config(name)
    slots = conf["serve"]["num_slots"]
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    family, cfg = build_config(conf, "serve")
    shapes = getattr(family, "state_shapes", None)
    layout = getattr(family, "pool_layout", lambda c: None)(cfg)

    def on(tree):
        return jax.tree.map(lambda a: _s(a.shape, a.dtype, one_chip), tree)

    params = on(jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0))))
    cache = on(jax.eval_shape(lambda: init_pool(
        cfg, conf["serve"]["pool_tokens"] // page, page,
        state_shapes=shapes(cfg) if shapes else None, state_rows=slots,
        **({} if layout is None else {"pool_layout": layout}))))
    chunk = 16

    def i32(*shape):
        return _s(shape, jnp.int32, one_chip)

    c = jax.jit(
        lambda *a: engine._decode_chunk(family, cfg, chunk, False, *a),
        donate_argnums=(1,)).lower(
            params, cache, i32(slots, -(-conf["serve"]["max_len"] // page)),
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

    text = c.as_text()
    leaves = [cache["k"].shape] + sorted(
        {a.shape for k, a in cache.get("state", {}).items() if "ring" in k})
    kinds = {"bf16[%s]" % ",".join(map(str, s)) for leaf in leaves
             for s in (leaf, leaf[1:], (math.prod(leaf[:-3]),) + leaf[-3:])}
    made = [line for _, op, line in _pool_shaped(text, kinds)
            if op not in ("parameter", "get-tuple-element", "bitcast")]
    assert not made, made
    assert not [line for line in text.splitlines()
                if "attn.kv_write" in line and "scatter" in line]
    writes = [line for line in text.splitlines()
              if "custom-call(" in line and "kv_token_write" in line]
    assert len(writes) == len(leaves)
    for line in writes:
        assert re.search(r'op_name="[^"]*attn\.kv_write/[^"]*kv_token_write',
                         line), line[:400]
        assert "output_to_operand_aliasing={{0}: (4, {}), {1}: (5, {})}" \
            in line, line[:400]
