"""Pallas TPU decode attention over a LATENT paged cache (multi-head
latent attention, DeepSeek-V2/V3's MLA, absorbed form).

A layer of the pool keeps ONE row a token, ``[c ; k_pe]``: the normed
latent ``c`` (``kv_lora_rank`` wide, 512 at DeepSeek-V3's widths) and the
rotated key shared by every head (``qk_rope_head_dim``, 64), 576 numbers
in all, read by all the heads. A decode query arrives absorbed: per head
``q_lat_h = W_UK_h^T q_nope_h`` beside its rotated ``q_pe_h``, one row of
the same width ``[q_lat ; q_pe]``. Then

    score_h(s) = [q_lat ; q_pe]_h . row(s) * scale
    o_lat_h    = sum_s softmax(score_h)(s) c(s)        (the row's first r)

and the caller takes ``o_h = W_UV_h o_lat_h`` out of the latent. The
values are the first ``r`` lanes of the rows the keys are: nothing else
is kept or read.

TPU-native design, ``paged_attention.py``'s own:
- Grid ``(batch,)``, sequential; the block table and the lengths ride a
  scalar prefetch; the pool stays in HBM (``memory_space=pl.ANY``), every
  layer of it, seen as ``[layers * pages, page_size, width]`` and read
  through a table of ``layer * pages + page``.
- A sequence is read in blocks of N pages into VMEM; a page is copied
  ONCE for all the heads. The copies run ``_AHEAD`` (3) blocks ahead of
  the block a trip waits for, as ONE stream over every sequence that
  holds a token: a cursor in SMEM (sequence, block) names the next block
  to start, and after a sequence's last block it moves to the next live
  sequence's first, so the copies never drain at a sequence's end.
- A block is two MXU products: ``[heads, width] x [N * ps, width]^T``
  for the scores (all 64 heads fill half of the MXU's 128 rows) and
  ``[heads, N * ps] x [N * ps, r]`` for the values, with one
  online-softmax update between them in float32.
- The loop is skewed by a block so that the MXU's work and the float32
  work stand in one basic block: trip ``i`` makes block ``i + 1``'s score
  product and, beside it, block ``i``'s softmax update (on the scores the
  trip before made, kept in VMEM) and value product. So two blocks are
  computed on while ``_AHEAD`` are in flight: ``_AHEAD + 2`` buffers. A
  prologue makes block 0's scores and an epilogue finishes the last
  block: the one stretch a sequence in which the softmax runs beside no
  product.
- The row is not a whole number of lane tiles (576 = 4.5 x 128): every
  block is the row WHOLE (a block equal to the array's last dimension is
  legal), and the value product reads a lane-aligned prefix of it.

``mla_decode_ref`` is the same attention in plain XLA (a gather of the
slots' pages): every backend, and what the dispatcher
(``kernels/__init__.py``, counters ``mla`` / ``mla_fallback``) takes off
the TPU and for shapes ``supported`` refuses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _NEG_INF, _pages_of, _sublane

# A block's buffers of pages fit _VMEM_BUDGET; _BLOCK_TOKENS caps it (a
# sequence's last block is on average half dead, and a trip's score tile
# [heads, tokens] grows with it). A block's copies start _AHEAD blocks
# before it is waited for, whatever sequence each belongs to: two buffers
# a trip computes on, _AHEAD in flight (one block ahead left the copies
# idle while a trip computed, and at every sequence's end: PERF.md §6).
_VMEM_BUDGET = 8 * 1024 * 1024
_BLOCK_TOKENS = 512
_AHEAD = 3
_BUFFERS = _AHEAD + 2


def _pages_per_block(ps, width, itemsize, maxp) -> int:
    by_vmem = _VMEM_BUDGET // (_BUFFERS * ps * width * itemsize)
    return max(1, min(by_vmem, _BLOCK_TOKENS // ps, maxp))


def _kernel(bt_ref, len_ref, q_ref, pool, o_ref, buf, sems, at_ref, s_ref,
            acc, m_s, l_s, *, scale, page_size, max_pages, pages_per_block):
    ps, npb = page_size, pages_per_block
    tb = npb * ps
    width = pool.shape[-1]
    r = o_ref.shape[-1]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    length = len_ref[b]
    nblk = pl.cdiv(length, tb)

    def block_dma(seq, blk, slot, start):
        """Start, or wait for, the copies of one block's live pages."""
        live = pl.cdiv(len_ref[seq], ps) - blk * npb
        for j in range(npb):
            @pl.when(j < live)
            def _(j=j):
                page = bt_ref[seq * max_pages + blk * npb + j] if start else 0
                cp = pltpu.make_async_copy(pool.at[page], buf.at[slot, j],
                                           sems.at[slot])
                cp.start() if start else cp.wait()

    def next_live(seq):
        return jax.lax.while_loop(
            lambda s: jnp.logical_and(
                s < nb, len_ref[jnp.minimum(s, nb - 1)] == 0),
            lambda s: s + 1, seq + 1)

    def issue(slot):
        """Start the copies of the block at the cursor (``at_ref[0]``, its
        sequence, ``at_ref[1]`` the block) into buffer ``slot``, and move
        the cursor to the next block of a sequence that holds a token."""
        seq, blk = at_ref[0], at_ref[1]

        @pl.when(seq < nb)
        def _():
            block_dma(seq, blk, slot, start=True)
            last = blk + 1 >= pl.cdiv(len_ref[seq], tb)
            at_ref[0] = jax.lax.cond(last, lambda: next_live(seq),
                                     lambda: seq)
            at_ref[1] = jnp.where(last, 0, blk + 1)

    @pl.when(b == 0)
    def _first():
        # a row no copy filled is multiplied by p == 0 in a last block's
        # value product: it must not hold a NaN
        buf[...] = jnp.zeros_like(buf)
        at_ref[0] = next_live(-1)
        at_ref[1] = 0
        at_ref[2] = 0                    # blocks waited for
        for k in range(_AHEAD):
            issue(k)

    acc[...] = jnp.zeros_like(acc)
    m_s[...] = jnp.full_like(m_s, _NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    base = at_ref[2]             # block i is in buffer (base + i) % _BUFFERS

    def scores(i):
        """Start the copies _AHEAD blocks on; wait for block ``i``'s; its
        raw score product."""
        issue((base + i + _AHEAD) % _BUFFERS)
        cur = (base + i) % _BUFFERS
        block_dma(b, i, cur, start=False)
        return jax.lax.dot_general(
            q_ref[0], buf[cur].reshape(tb, width), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [heads, tb]

    def update(i, s):
        """Fold block ``i`` (``s`` its raw scores) into the softmax."""
        cur = (base + i) % _BUFFERS
        s = s * scale
        pos = i * tb + jax.lax.broadcasted_iota(jnp.int32, (1, tb), 1)
        s = jnp.where(pos < length, s, _NEG_INF)
        m_prev, l_prev = m_s[:, :1], l_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_s[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), l_s.shape)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        acc[...] = acc[...] * alpha + jnp.dot(
            p.astype(buf.dtype), buf[cur, :, :, :r].reshape(tb, r),
            preferred_element_type=jnp.float32)

    @pl.when(nblk > 0)
    def _():
        s_ref[...] = scores(0)

        def trip(i, carry):
            s_next = scores(i + 1)
            update(i, s_ref[...])
            s_ref[...] = s_next
            return carry

        jax.lax.fori_loop(0, nblk - 1, trip, 0)
        update(nblk - 1, s_ref[...])

    at_ref[2] = base + nblk

    l = l_s[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)                       # empty slot -> 0
    o_ref[0] = (acc[...] / l).astype(o_ref.dtype)


def _flat_pool(pool, layer, block_tables):
    """(table, the pool as [pages, page_size, width]): a pool
    ``[layers, pages, 1, ps, width]`` (the page pool's layout with one
    "head") or ``[pages, 1, ps, width]`` with ``layer`` None."""
    bt, (flat,) = _pages_of(layer, block_tables, pool)
    return bt, flat.reshape((-1,) + flat.shape[-2:])


def mla_decode(q, pool, block_tables, lengths, *, rank, scale, layer=None,
               interpret=False):
    """Absorbed latent attention, one position a sequence. ``q`` [B,
    heads, width]: ``[q_lat ; q_pe]`` a head; ``pool`` [layers, pages, 1,
    page_size, width] with ``layer`` the int32 scalar naming the layer
    (or one layer alone, [pages, 1, page_size, width], and ``layer``
    None); ``block_tables`` [B, max_pages]; ``lengths`` [B] (0: an empty
    slot, a zero row). Returns ``o_lat`` [B, heads, rank], the softmax's
    mix of the rows' first ``rank`` numbers, in q's type."""
    bt, flat = _flat_pool(pool, layer, block_tables)
    B, nh, width = q.shape
    ps = flat.shape[1]
    maxp = block_tables.shape[1]
    npb = _pages_per_block(ps, width, jnp.dtype(flat.dtype).itemsize, maxp)
    lengths = jnp.minimum(lengths.astype(jnp.int32), maxp * ps)

    def per_seq(b, bt_, ln_):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, nh, width), per_seq),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, nh, rank), per_seq),
        scratch_shapes=[
            pltpu.VMEM((_BUFFERS, npb, ps, width), flat.dtype),
            pltpu.SemaphoreType.DMA((_BUFFERS,)),
            pltpu.SMEM((3,), jnp.int32),         # the copies' cursor
            pltpu.VMEM((nh, npb * ps), jnp.float32),   # a block's scores
            pltpu.VMEM((nh, rank), jnp.float32),
            pltpu.VMEM((nh, 128), jnp.float32),
            pltpu.VMEM((nh, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, page_size=ps,
                          max_pages=maxp, pages_per_block=npb),
        name="mla_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(bt.reshape(-1), lengths, q.astype(flat.dtype), flat)


def mla_decode_ref(q, pool, block_tables, lengths, *, rank, scale,
                   layer=None):
    """``mla_decode`` in plain XLA: the slots' pages gathered, float32
    scores, the same masking (an empty slot is a zero row, never NaN)."""
    bt, flat = _flat_pool(pool, layer, block_tables)
    B, nh, width = q.shape
    ps = flat.shape[1]
    maxp = block_tables.shape[1]
    rows = flat.at[bt.reshape(-1)].get(mode="promise_in_bounds").reshape(
        B, maxp * ps, width).astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows) * scale
    live = (jnp.arange(maxp * ps)[None, :] < lengths[:, None])[:, None]
    s = jnp.where(live, s, _NEG_INF)
    e = jnp.where(live, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    l = jnp.sum(e, -1, keepdims=True)
    o = jnp.einsum("bhs,bsr->bhr", e / jnp.where(l == 0.0, 1.0, l),
                   rows[..., :rank])
    return o.astype(q.dtype)


def supported(q, pool, rank) -> bool:
    """Whether the Pallas kernel takes these shapes: the pool's page
    layout with one head, bf16 or float32, a page of whole sublane tiles,
    a value prefix of whole lane tiles."""
    if q.ndim != 3 or pool.ndim not in (4, 5) or pool.shape[-3] != 1:
        return False
    if jnp.dtype(pool.dtype) not in (jnp.dtype(jnp.float32),
                                     jnp.dtype(jnp.bfloat16)):
        return False
    ps, width = pool.shape[-2:]
    return (q.shape[-1] == width and rank % 128 == 0 and rank <= width
            and ps % _sublane(pool.dtype) == 0)


def work(kv_token_steps: float, heads: int, width: int, rank: int,
         itemsize: int = 2):
    """(FLOPs, bytes) the absorbed decode has to do over
    ``kv_token_steps`` live cached tokens read, a layer: two FLOPs a
    multiply-add of the score product (``width`` a token a head) and of
    the value product (``rank``); the live rows read once for all the
    heads. The queries and outputs are the caller's to add."""
    return (2.0 * heads * (width + rank) * kv_token_steps,
            float(width * itemsize) * kv_token_steps)
