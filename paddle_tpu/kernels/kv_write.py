"""A decode step's KV write: one token a slot into the page pool, both
halves (or the one leaf of a latent pool) by one in-place Pallas call.

A decode step computes one new key and one new value a slot a layer,
``[B, kv_heads, head_dim]`` each, and each belongs at row ``off`` of a
page ``[kv_heads, page_size, head_dim]`` of its pool half. As an XLA
scatter that is ``B * kv_heads`` rows of ``head_dim`` elements a half,
and the v5e pays one latency a row, one after the other (94-100 ns:
48 us for 512 rows of 256 B, 0.3% of what the bytes allow; PERF.md,
PR 28 and PR 36).

``kv_token_write`` (named ``kv_token_write`` in a trace) moves tiles
instead. A single bf16 row is not addressable by a DMA: the pool's HBM
layout packs two bf16 rows into the 32-bit words of a sublane
(``T(8,128)(2,1)``), so the unit is the aligned SUBLANE TILE that holds
row ``off``: ``[kv_heads, 16, head_dim]`` of the page in bf16 (8 rows in
float32). A slot's tile of each half is copied into VMEM, the token's
row put in by a select on a row iota, and the tile copied back to where
it came from. The pools stay in HBM (``memory_space=pl.ANY``) and each
output aliases its input (``input_output_aliases``), so a program that
donates the cache holds each half once and nothing of its size is made.

- The pages, rows and the layer ride scalar prefetch, the layer folded
  into the page (``layer * P + page`` of the pool seen as ``[L * P,
  ...]``: a bitcast, as ``paged_attention._pages_of`` has it).
- A grid step takes a CHUNK of slots (all of them where their tiles fit
  ``_VMEM_BUDGET``): every live slot's two reads are started, then slot
  by slot the reads are awaited, the rows put in and the two writes
  started, then every write is awaited. **Live slots never name the same
  page in a step** (the scatter this replaces declared
  ``unique_indices=True``: a slot owns the page its sequence is writing),
  so no tile is shared and the copies of a chunk may all be in flight at
  once.
- A pool of ONE leaf (a latent cache, ``models/axk1.py``: a row ``[c ;
  k_pe]`` a token that keys and values both read) is the same call with
  no second half: ``pool_v`` and ``v`` None.
- An INACTIVE slot (``rows`` outside ``[0, P)``: the engine's sentinel
  ``P``, which the scatter's ``mode="drop"`` dropped) issues no copy at
  all: ``pl.when``, never a redirected write, which could land stale
  data on a live page.

``kv_token_write_ref`` is the scatter it replaces, in plain XLA: every
backend, and what the dispatcher (``kernels/__init__.py``, counter
``kv_write_fallback``) takes off the TPU and for shapes ``supported``
refuses.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _sublane

# What a chunk's tiles may hold of VMEM, both halves together: half of
# the 16 MiB a kernel gets by default. Mistral's 64 slots x 8 heads are
# 4 MiB, so a cell's step is one chunk or two (Phi: 128 slots x 10 heads
# are 10 MiB).
_VMEM_BUDGET = 8 * 1024 * 1024


def _slots_per_chunk(B: int, kv: int, sub: int, hd: int,
                     itemsize: int, halves: int = 2) -> int:
    """The most slots whose tiles of every half fit the budget, as a
    divisor of ``B`` (a grid step takes a whole chunk)."""
    c = max(1, min(B, _VMEM_BUDGET // (halves * kv * sub * hd * itemsize)))
    while B % c:
        c -= 1
    return c


def _write_kernel(pages_ref, off_ref, *refs):
    # per half: its new rows, its pool (aliased to its output, the copy
    # the kernel writes), its VMEM tiles; then the semaphores
    n = (len(refs) - 1) // 4
    rows_in, hbms, bufs = refs[:n], refs[2 * n:3 * n], refs[3 * n:4 * n]
    sems = refs[-1]
    C, kv, sub, hd = bufs[0].shape
    base = pl.program_id(0) * C
    halves = tuple(zip(hbms, bufs, rows_in, range(n)))

    def copies(j, to_hbm):
        """The copies of slot ``j`` of the chunk: its tile of each half
        into its buffer, or back."""
        i = base + j
        t0 = pl.multiple_of(off_ref[i] // sub * sub, sub)
        for hbm, buf, _, s in halves:
            tile = hbm.at[pages_ref[i], :, pl.ds(t0, sub), :]
            src, dst = (buf.at[j], tile) if to_hbm else (tile, buf.at[j])
            yield pltpu.make_async_copy(src, dst, sems.at[s, j])

    def each_live(body):
        def one(j, carry):
            @pl.when(pages_ref[base + j] >= 0)
            def _():
                body(j)
            return carry
        lax.fori_loop(0, C, one, 0)

    @each_live
    def _(j):
        for cp in copies(j, False):
            cp.start()

    row = lax.broadcasted_iota(jnp.int32, (sub, hd), 0)

    @each_live
    def _(j):
        for cp in copies(j, False):
            cp.wait()
        mine = row == off_ref[base + j] % sub
        for _, buf, new_ref, _ in halves:
            # float32 holds every bf16 exactly: the select is bit-exact,
            # and the v5e's vector unit has no bf16 form of it
            new = new_ref[j].astype(jnp.float32)             # [kv, hd]
            for h in range(kv):
                buf[j, h] = jnp.where(
                    mine, new[h:h + 1, :],
                    buf[j, h].astype(jnp.float32)).astype(buf.dtype)
        for cp in copies(j, True):
            cp.start()

    @each_live
    def _(j):
        for cp in copies(j, True):
            cp.wait()


def supported(pool, k) -> bool:
    """Whether the Pallas kernel takes this pool half and these values:
    not the int8 ``{"q", "s"}`` pair (a token's write rescales its whole
    page there: ``inference/paged.py``), bf16 or float32, a page of whole
    sublane tiles (16 rows in bf16, 8 in float32) and a head of whole lane
    tiles."""
    if isinstance(pool, dict) or pool.ndim < 4 or k.ndim != 3:
        return False
    if jnp.dtype(pool.dtype) not in (jnp.dtype(jnp.float32),
                                     jnp.dtype(jnp.bfloat16)):
        return False
    kv, ps, hd = pool.shape[-3:]
    return (tuple(k.shape[1:]) == (kv, hd) and hd % 128 == 0
            and ps % _sublane(pool.dtype) == 0)


def _pages(pool, layer):
    """How many pages a layer of ``pool`` holds: every axis between the
    layer's (where ``layer`` names one) and the last three, flattened."""
    return math.prod(pool.shape[(0 if layer is None else 1):-3])


def kv_token_write(pool_k, pool_v, layer, rows, off, k, v, *,
                   interpret=False):
    """Write ``k``, ``v`` [B, kv_heads, head_dim] at row ``off`` [B] of
    pages ``rows`` [B] of layer ``layer`` of the two pool halves, in
    place (``pool_v`` and ``v`` None: a pool of one leaf, written
    alone). ``pool_k`` / ``pool_v``: ``[layers, pages, kv_heads,
    page_size, head_dim]`` with ``layer`` an int32 scalar (traced or
    not), or one layer alone ``[pages, ...]`` with ``layer`` None; more
    axes between the layer's and the heads' count as pages, flattened (a
    ring leaf ``[layers, rows, pages, ...]`` takes ``row * pages +
    page``). ``rows`` lie in ``[0, P]``: ``P`` (the pages of a layer) is
    the sentinel of a slot with nothing to write (an ``off`` outside the
    page writes nothing either). Live slots name different pages.
    Returns the pools (a tuple of one for one leaf): every element no
    slot names untouched, and each its input's own buffer where the
    caller donates it."""
    pools = tuple(p for p in (pool_k, pool_v) if p is not None)
    vals = tuple(x for x in (k, v) if x is not None)
    n = len(pools)
    shape = pool_k.shape
    kv, ps, hd = shape[-3:]
    B = k.shape[0]
    P = _pages(pool_k, layer)
    sub = _sublane(pool_k.dtype)
    C = _slots_per_chunk(B, kv, sub, hd, jnp.dtype(pool_k.dtype).itemsize,
                         n)
    rows, off = rows.astype(jnp.int32), off.astype(jnp.int32)
    first = 0 if layer is None else jnp.asarray(layer, jnp.int32) * P
    # what the scatter's mode="drop" dropped: a page or a row off the pool
    live = (rows >= 0) & (rows < P) & (off >= 0) & (off < ps)
    pages = jnp.where(live, first + rows, -1)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    row_spec = pl.BlockSpec((C, kv, hd),
                            lambda c, pages_ref, off_ref: (c, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // C,),
        in_specs=[row_spec] * n + [hbm] * n,
        out_specs=[hbm] * n,
        scratch_shapes=[pltpu.VMEM((C, kv, sub, hd), p.dtype) for p in pools]
        + [pltpu.SemaphoreType.DMA((n, C))],         # (K | V, slot)
    )
    flat = (-1, kv, ps, hd)
    out = pl.pallas_call(
        _write_kernel,
        name="kv_token_write",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((math.prod(shape[:-3]),) + shape[-3:],
                                        p.dtype) for p in pools],
        # operands: pages, off, the new rows, the pools -> the outputs
        input_output_aliases={2 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pages, off, *(x.astype(p.dtype) for x, p in zip(vals, pools)),
      *(p.reshape(flat) for p in pools))
    return tuple(o.reshape(shape) for o in out)


def kv_token_write_ref(pool_k, pool_v, layer, rows, off, k, v):
    """The same write as XLA scatters, a row a (slot, head) of each half:
    the contract of ``kv_token_write``, for a pool of any type."""
    kvi = jnp.arange(k.shape[1])
    at = (rows[:, None], kvi[None, :], off[:, None])
    if layer is not None:
        at = (layer,) + at
    as_pages = (() if layer is None else pool_k.shape[:1]) \
        + (_pages(pool_k, layer),) + pool_k.shape[-3:]

    def write(pool, val):
        return pool.reshape(as_pages).at[at].set(
            val.astype(pool.dtype), mode="drop",
            unique_indices=True).reshape(pool.shape)

    return tuple(write(p, x) for p, x in ((pool_k, k), (pool_v, v))
                 if p is not None)
