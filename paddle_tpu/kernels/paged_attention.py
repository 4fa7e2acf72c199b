"""Pallas TPU ragged paged attention (decode).

Reference capability: the vLLM-style PagedAttention decode kernel
(csrc/attention/paged_attention_v1.cu in the reference serving stacks) as
rebuilt TPU-native by Ragged Paged Attention (arxiv 2604.15464): each
sequence's KV cache lives in non-contiguous fixed-size pages named by a
block table, and one decode query attends over exactly its own ragged
length — no batch-uniform max-length padding in either HBM traffic or
FLOPs.

TPU-native design: the kernel's work follows the live tokens.
- Grid ``(batch,)``, sequential. The block table and the lengths ride a
  ``PrefetchScalarGridSpec`` scalar prefetch; the two page pools stay in
  HBM (``memory_space=pl.ANY``), every layer of them, and the kernel
  fetches pages itself.
- A sequence is read in BLOCKS of N pages: one ``make_async_copy`` a
  page carries all its KV heads (``kv_heads * page_size * head_dim``
  contiguous elements of the pool) into a VMEM buffer laid out
  ``[kv_heads, N, page_size, head_dim]``, so a head's pages of a block
  are ``[N * page_size, head_dim]`` without moving data. A block whose
  pages are all live starts and waits for its N copies in straight-line
  code; a sequence's last block loops over its live pages.
- The loop over a sequence's blocks runs ``cdiv(length, N * page_size)``
  trips. A table entry past the sequence's live pages never becomes an
  index, a copy or a trip; a length of 0 (an empty slot of the serving
  engine's fixed slot grid) makes no trip and writes a zero row.
- The copies run ``_AHEAD`` (3) blocks ahead of the block a trip waits
  for, as ONE stream over every sequence that holds a token: a cursor in
  SMEM (sequence, block) names the next block to start, and after a
  sequence's last block it moves to the next live sequence's first, so
  the copies never drain at a sequence's end. A count of the blocks
  waited for rides in SMEM beside it: block ``i`` of the stream is in
  buffer ``i mod _BUFFERS``.
- The loop is skewed by a block so that the MXU's work and the float32
  work stand in one basic block: trip ``i`` waits for block ``i + 1``
  and makes its score product (all KV heads batched: ``[kv, gp, hd] x
  [kv, N*ps, hd]``) and, beside it, block ``i``'s online-softmax update
  (on the scores the trip before made, kept in VMEM; positions past
  ``length`` masked; float32 running max, sum and accumulator) and PV
  product (``p`` cast to the pages' dtype). So two blocks are computed
  on while ``_AHEAD`` are in flight: ``_BUFFERS`` = ``_AHEAD + 2``. A
  prologue makes a sequence's first scores and an epilogue finishes its
  last block.
- GQA: queries reshape to [B, kv_heads, group, head_dim]; the group dim
  is zero-padded to the sublane tile so every matmul is legal.
- N follows from the shapes (``_pages_per_block``): what a buffer's
  2 MiB holds of a block's K (or V), capped in tokens. The depth is one
  constant for every shape, no knob, chosen by timing the kernel alone
  on the v5e at 2, 3 and 4 blocks ahead at every serving cell's shape
  (PERF.md section 5). Each call records its N, its depth and the bytes
  it keeps in flight in ``autotune.used_blocks()``.

Layouts: a pool half is ``[layers, num_pages, kv_heads, page_size,
head_dim]`` (one page of every KV head is contiguous: 32 KiB at 8 heads
of 128 and pages of 16 in bf16) and the call names one ``layer`` of it,
a traced scalar: the serving program holds the whole pool in ONE buffer
and never cuts a layer out of it. The kernel sees that buffer as
``[layers * num_pages, ...]`` (a bitcast) and a table of ``layer *
num_pages + page`` (``_pages_of``), so a page's address is one index. On
the v5e that form times as the kernel on one layer's pool does, and the
layer as a third prefetched scalar with ``hbm.at[layer, page]`` 2-3%
slower (PERF.md, PR 28). A caller that holds one layer alone passes
``[num_pages, kv_heads, page_size, head_dim]`` and no ``layer``: the
same body. q is ``[batch, num_heads, head_dim]`` — one decode position
per sequence.

``paged_attention_ref`` is the pure-jnp gather fallback — identical
math, runs on every backend — which tier-1 exercises on CPU and the
dispatcher (kernels/__init__.py) uses when the kernel is unsupported.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune

_NEG_INF = -1e30


def _sublane(dtype) -> int:
    return 16 if jnp.dtype(dtype).itemsize == 2 else 8


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

# What one block may hold. _BUFFER_BYTES is what a buffer of K (and one
# of V) may hold: the 2 MiB it held when the kernel kept one block in
# flight, so the stream changes when a copy starts and not the blocks.
# There are _BUFFERS of each, two a trip computes on and _AHEAD in
# flight. The kernel's VMEM limit, _VMEM_LIMIT, leaves 8 MiB beside them
# to q, the output, the score tiles and Mosaic's temporaries (the v5e's
# default is 16 MiB in all). _BLOCK_TOKENS caps the block, and binds at
# every serving cell's shape: the arithmetic of a trip is paid on the
# whole block, and a sequence's last block is on average half dead. On
# the v5e blocks of 512 tokens read 36k live tokens of 64 sequences 2%
# faster than blocks of 256 and a full pool 16% faster (PERF.md section
# 6). One block ahead, queued a sequence at a time, left the copies idle
# while a trip computed and drained them at every sequence's end
# (PERF.md section 5).
_AHEAD = 3
_BUFFERS = _AHEAD + 2
_BUFFER_BYTES = 2 * 1024 * 1024
_VMEM_LIMIT = 2 * _BUFFERS * _BUFFER_BYTES + 8 * 1024 * 1024
_BLOCK_TOKENS = 512


def _pages_per_block(kv, ps, hd, itemsize, maxp) -> int:
    """N, the pages one fetch and one online-softmax update cover: what
    a buffer's bytes and the token cap allow, at least one page and at
    most a slot's whole table."""
    by_vmem = _BUFFER_BYTES // (kv * ps * hd * itemsize)
    return max(1, min(by_vmem, _BLOCK_TOKENS // ps, maxp))


def _decode_kernel(bt_ref, len_ref, *refs, scale, page_size, max_pages,
                   pages_per_block, quant, window=None):
    if quant:
        (q_ref, k_hbm, v_hbm, ks_ref, vs_ref, o_ref,
         kbuf, vbuf, sems, at_ref, sc_ref, acc, m_s, l_s) = refs
    else:
        (q_ref, k_hbm, v_hbm, o_ref,
         kbuf, vbuf, sems, at_ref, sc_ref, acc, m_s, l_s) = refs
    ps, npb = page_size, pages_per_block
    tb = npb * ps                                    # tokens a block
    kv, hd = k_hbm.shape[1], k_hbm.shape[3]
    gp = q_ref.shape[2]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    length = len_ref[b]
    # over a ring (``window``): the table's pages are a ring of max_pages *
    # ps slots in which position p lies at slot p mod ring, so the slots
    # that hold anything are the first min(length, ring)
    ring = max_pages * ps

    def held(n):
        return n if window is None else jnp.minimum(n, ring)

    nblk = pl.cdiv(held(length), tb)

    def block_dma(seq, blk, slot, start):
        """Start, or wait for, the copies of one block's LIVE pages: page
        ``blk * npb + j`` of sequence ``seq`` into row ``j`` of buffer
        ``slot``, all KV heads of the page in one copy. A table entry
        past the sequence's pages is never read: a block whose pages are
        all live is N copies in straight-line code, a last block a loop
        over its live pages. (A branch a page cost the kernel 4% alone at
        2 KV heads; the branches unrolled beside the straight-line copies
        tripled the time to trace and lower it, which set-up pays.)"""
        live = pl.cdiv(held(len_ref[seq]), ps) - blk * npb

        def one(j):
            # a wait needs the copy's shape only, not its source
            page = bt_ref[seq * max_pages + blk * npb + j] if start else 0
            for hbm, buf, s in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                cp = pltpu.make_async_copy(
                    hbm.at[page], buf.at[slot, :, j], sems.at[s, slot])
                cp.start() if start else cp.wait()

        @pl.when(live >= npb)
        def _():
            for j in range(npb):
                one(j)

        def page(j, carry):
            one(j)
            return carry

        @pl.when(live < npb)
        def _():
            jax.lax.fori_loop(0, live, page, 0)

    def next_live(seq):
        """The first sequence after ``seq`` that holds a token, or nb."""
        return jax.lax.while_loop(
            lambda s: jnp.logical_and(
                s < nb, len_ref[jnp.minimum(s, nb - 1)] == 0),
            lambda s: s + 1, seq + 1)

    def issue(slot):
        """Start the copies of the block at the cursor (``at_ref[0]`` its
        sequence, ``at_ref[1]`` the block) into buffer ``slot``, and move
        the cursor to the next block of a sequence that holds a token."""
        seq, blk = at_ref[0], at_ref[1]

        @pl.when(seq < nb)
        def _():
            block_dma(seq, blk, slot, start=True)
            last = blk + 1 >= pl.cdiv(held(len_ref[seq]), tb)
            at_ref[0] = jax.lax.cond(last, lambda: next_live(seq),
                                     lambda: seq)
            at_ref[1] = jnp.where(last, 0, blk + 1)

    @pl.when(b == 0)
    def _first():
        # a row of a buffer that no copy has filled yet is multiplied by
        # p == 0 in a last block's PV product: it must not hold a NaN.
        # (K needs no such care: its scores are replaced, not scaled.)
        vbuf[...] = jnp.zeros_like(vbuf)
        at_ref[0] = next_live(-1)
        at_ref[1] = 0
        at_ref[2] = 0                    # blocks waited for

        def start(k, carry):
            issue(k)
            return carry

        jax.lax.fori_loop(0, _AHEAD, start, 0)

    if quant:
        # a block's per-page scales, [kv, npb], spread over the pages'
        # columns by a 0/1 product (exact in float32), then over the
        # query rows: [kv, gp, tb]
        col = jax.lax.broadcasted_iota(jnp.int32, (npb, tb), 1) // ps
        row = jax.lax.broadcasted_iota(jnp.int32, (npb, tb), 0)
        spread = (col == row).astype(jnp.float32)

        def cols(s_ref, i):
            c = jax.lax.dot_general(
                s_ref[0, i], spread, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            return jnp.stack([jnp.broadcast_to(c[h:h + 1], (gp, tb))
                              for h in range(kv)])

    acc[...] = jnp.zeros_like(acc)
    m_s[...] = jnp.full_like(m_s, _NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    base = at_ref[2]        # block i is in buffer (base + i) % _BUFFERS

    def scores(i):
        """Start the copies ``_AHEAD`` blocks on (into the buffer the trip
        before finished with); wait for block ``i``'s; its scaled scores,
        [kv, gp, tb] float32."""
        issue((base + i + _AHEAD) % _BUFFERS)
        cur = (base + i) % _BUFFERS
        block_dma(b, i, cur, start=False)
        q = q_ref[0]                                     # [kv, gp, hd]
        # ps is a multiple of the sublane tile: a head's pages of the
        # block are [tb, hd] without moving data
        k = kbuf[cur].reshape(kv, tb, hd)
        if quant:
            # every code of a (page, head) shares ONE scale, so
            # dot(q, codes) * ks == dot(q, deq(codes)): the pages stay
            # int8 in HBM and in VMEM
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        if quant:
            s = s * cols(ks_ref, i)
        return s

    def update(i, s):
        """Fold block ``i`` (``s`` its scores) into the softmax and the
        accumulator."""
        cur = (base + i) % _BUFFERS
        pos = i * tb + jax.lax.broadcasted_iota(jnp.int32, (1, 1, tb), 2)
        if window is None:
            valid = pos < length
        else:
            # a slot's age: how far behind the newest position (length - 1,
            # at slot (length - 1) mod ring) the position it holds lies;
            # the window's positions are the ages under min(length, window)
            age = jax.lax.rem(length - 1, jnp.int32(ring)) - pos
            age = jnp.where(age < 0, age + ring, age)
            valid = jnp.logical_and(age < jnp.minimum(length, window),
                                    pos < ring)
        v = vbuf[cur].reshape(kv, tb, hd)
        if quant:
            v = v.astype(jnp.float32)
        s = jnp.where(valid, s, _NEG_INF)                # last block's tail

        m_prev = m_s[:, :, :1]
        l_prev = l_s[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_s[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=2, keepdims=True), l_s.shape)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        if quant:
            p = p * cols(vs_ref, i)                      # V's scale, into p
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(nblk > 0)
    def _():
        sc_ref[...] = scores(0)

        def trip(i, carry):
            s_next = scores(i + 1)
            update(i, sc_ref[...])
            sc_ref[...] = s_next
            return carry

        jax.lax.fori_loop(0, nblk - 1, trip, 0)
        update(nblk - 1, sc_ref[...])

    at_ref[2] = base + nblk

    l = l_s[:, :, :1]
    l = jnp.where(l == 0.0, 1.0, l)                      # empty slot -> 0
    o_ref[0] = (acc[...] / l).astype(o_ref.dtype)


def _pages_of(layer, block_tables, *pools):
    """(table, pools) with the layer folded into the page: a pool that
    carries its layer axis, [L, P, ...], is read as [L * P, ...] (the same
    bytes: a bitcast) through a table whose entries are ``layer * P +
    page``, so a page's address is one index whatever the layer, and no
    layer is ever cut out of the pool. Table entries are clamped to the
    pool first: a live one off it would be a DMA out of bounds, and the
    dead ones are clamped with them."""
    P = pools[0].shape[0 if layer is None else 1]
    bt = jnp.clip(block_tables, 0, P - 1).astype(jnp.int32)
    if layer is None:
        return bt, pools
    return bt + layer * P, [None if p is None else
                            p.reshape((-1,) + p.shape[2:]) for p in pools]


def ragged_paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           scale=None, k_scales=None, v_scales=None,
                           layer=None, window=None, interpret=False):
    """Paged decode attention. q: [B, num_heads, head_dim]; k_pages /
    v_pages: [layers, num_pages, kv_heads, page_size, head_dim] with
    ``layer`` the int32 scalar (traced or not) that names the layer to
    attend over, the rest of the pool untouched and uncopied; or, with
    ``layer`` None, one layer alone, [num_pages, kv_heads, page_size,
    head_dim]; block_tables: [B, max_pages] page ids (entries past a
    sequence's pages may hold any value — the kernel never reads them);
    lengths: [B] valid KV positions per sequence (0 = empty slot -> zero
    output row; more than the table holds counts as the whole table).

    With ``k_scales``/``v_scales`` ([layers, num_pages, kv_heads] f32,
    or [num_pages, kv_heads] beside a 4-D pool; both or neither) the
    pages are int8 codes (FLAGS_serving_kv_quant): the block table
    gathers each sequence's scales block by block (a tiny XLA gather),
    and dequantization folds into the two dots — page traffic stays
    int8. With ``window`` a row of the table is a RING of ``max_pages *
    page_size`` slots (position p at slot p mod ring) and ``lengths`` the
    true lengths, however long: the keys read are those of the last
    ``min(length, window)`` positions, and only the ring's pages are
    fetched (the call is then named ``paged_decode_attn_window``).
    Returns [B, num_heads, head_dim]."""
    quant = k_scales is not None
    bt, (k_pages, v_pages, k_scales, v_scales) = _pages_of(
        layer, block_tables, k_pages, v_pages, k_scales, v_scales)
    B, nh, hd = q.shape
    _, kv, ps, _ = k_pages.shape
    maxp = block_tables.shape[1]
    g = nh // kv
    sub = _sublane(q.dtype)
    gp = max(sub, (g + sub - 1) // sub * sub)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    itemsize = jnp.dtype(k_pages.dtype).itemsize
    npb = _pages_per_block(kv, ps, hd, itemsize, maxp)
    name = "paged_decode_attn" + ("" if window is None else "_window")
    autotune.record_rule(
        f"{name}:{jnp.dtype(k_pages.dtype).name}:B{B}:q{nh}:kv{kv}:d{hd}:"
        f"page{ps}:table{maxp}", pages_per_block=npb, blocks_ahead=_AHEAD,
        bytes_in_flight=_AHEAD * 2 * npb * kv * ps * hd * itemsize)

    qg = q.reshape(B, kv, g, hd)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    lengths = lengths.astype(jnp.int32)
    if window is None:
        lengths = jnp.minimum(lengths, maxp * ps)

    def per_seq(b, bt_, ln_):
        return (b, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, kv, gp, hd), per_seq), hbm, hbm]
    operands = [qg, k_pages, v_pages]
    if quant:
        # the scale planes, gathered per sequence and cut into the
        # kernel's blocks here: [B, blocks, kv, npb], one block's
        # [kv, npb] tile a dynamic index on a leading dim away. A dead
        # entry's scale is whatever its clamped index names: it reads 0,
        # since the spreading product would carry a NaN to live columns
        nblocks = -(-maxp // npb)
        padded = jnp.pad(bt, ((0, 0), (0, nblocks * npb - maxp)))
        live = (jnp.arange(nblocks * npb)[None, :] * ps
                < lengths[:, None])[:, :, None]

        def rows(scales):
            per = jnp.where(live, scales.astype(jnp.float32)[padded], 0.0)
            return jnp.swapaxes(per.reshape(B, nblocks, npb, kv), 2, 3)

        row_spec = pl.BlockSpec((1, nblocks, kv, npb), per_seq)
        in_specs += [row_spec, row_spec]
        operands += [rows(k_scales), rows(v_scales)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv, gp, hd), per_seq),
        scratch_shapes=[
            pltpu.VMEM((_BUFFERS, kv, npb, ps, hd), k_pages.dtype),
            pltpu.VMEM((_BUFFERS, kv, npb, ps, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, _BUFFERS)),   # (K | V, buffer)
            pltpu.SMEM((3,), jnp.int32),         # the copies' cursor
            pltpu.VMEM((kv, gp, npb * ps), jnp.float32),   # a block's scores
            pltpu.VMEM((kv, gp, hd), jnp.float32),
            pltpu.VMEM((kv, gp, 128), jnp.float32),
            pltpu.VMEM((kv, gp, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page_size=ps,
                          max_pages=maxp, pages_per_block=npb, quant=quant,
                          window=window),
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kv, gp, hd), q.dtype),
        # the copies' cursor is handed from one sequence to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(bt.reshape(-1), lengths, *operands)
    return out[:, :, :g, :].reshape(B, nh, hd)


# ---------------------------------------------------------------------------
# pure-jnp fallback (identical math; every backend)
# ---------------------------------------------------------------------------

def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths, *,
                        scale=None, k_scales=None, v_scales=None,
                        layer=None, window=None):
    """Gather-based reference: same contract and masking semantics as the
    kernel (safe softmax — an empty sequence yields a zero row, never
    NaN), the pools with or without their layer axis as there. This is
    the path tier-1 runs on CPU. ``k_scales``/``v_scales`` mark int8
    pages: the gathered codes are dequantized in f32 before the same
    einsum math."""
    bt, (k_pages, v_pages, k_scales, v_scales) = _pages_of(
        layer, block_tables, k_pages, v_pages, k_scales, v_scales)
    B, nh, hd = q.shape
    _, kv, ps, _ = k_pages.shape
    maxp = block_tables.shape[1]
    g = nh // kv
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    bt = bt.reshape(-1)
    # flat gathers with in-bounds promise (clip above), consumed in page
    # layout directly — XLA:CPU's generic gather/transpose lowering is
    # this fallback's hot spot, so no moveaxis copies
    k = k_pages.at[bt].get(
        mode="promise_in_bounds").reshape(B, maxp, kv, ps, hd)
    v = v_pages.at[bt].get(
        mode="promise_in_bounds").reshape(B, maxp, kv, ps, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scales is not None:
        sk = k_scales.at[bt].get(
            mode="promise_in_bounds").reshape(B, maxp, kv)
        sv = v_scales.at[bt].get(
            mode="promise_in_bounds").reshape(B, maxp, kv)
        kf = kf * sk.astype(jnp.float32)[..., None, None]
        vf = vf * sv.astype(jnp.float32)[..., None, None]
    qf = q.astype(jnp.float32).reshape(B, kv, g, hd)
    s = jnp.einsum("bkgd,bmkpd->bkgmp", qf, kf) * scale
    pos = jnp.arange(maxp)[:, None] * ps + jnp.arange(ps)[None, :]
    if window is None:
        mask = pos[None] < lengths[:, None, None]      # [B, maxp, ps]
    else:                                # the table's row is a ring
        ring, n = maxp * ps, lengths[:, None, None]
        age = jnp.mod(jnp.mod(n - 1, ring) - pos[None], ring)
        mask = (age < jnp.minimum(n, window)) & (n > 0)
    s = jnp.where(mask[:, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=(-2, -1), keepdims=True)
    e = jnp.where(mask[:, None, None], jnp.exp(s - m), 0.0)
    l = jnp.sum(e, axis=(-2, -1), keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bkgmp,bmkpd->bkgd", e / l, vf)
    return out.reshape(B, nh, hd).astype(q.dtype)


def supported(q, k_pages, block_tables, quant=False) -> bool:
    """Whether the pallas kernel handles these shapes (else the
    dispatcher uses paged_attention_ref); of a pool with its layer axis
    the last four dimensions are judged. ``quant`` marks the int8-page
    arm (scale planes present)."""
    if q.ndim != 3 or k_pages.ndim not in (4, 5) or block_tables.ndim != 2:
        return False
    B, nh, hd = q.shape
    P, kv, ps, hd2 = k_pages.shape[-4:]
    if hd != hd2 or hd > 256 or nh % kv != 0:
        return False
    if jnp.dtype(q.dtype) not in (jnp.dtype(jnp.float32),
                                  jnp.dtype(jnp.bfloat16)):
        return False
    if quant:
        # int8 pages: a page's sublane tile is 32 rows (1-byte dtype),
        # and only int8 codes are a valid quantized pool
        if jnp.dtype(k_pages.dtype) != jnp.dtype(jnp.int8) or ps % 32:
            return False
    elif jnp.dtype(k_pages.dtype) == jnp.dtype(jnp.int8):
        return False     # int8 pool without scales is a contract breach
    # a page is copied out of HBM whole, so its rows fill lane tiles
    # (Mosaic: "Slice shape along dimension 3 must be aligned to tiling
    # (128)") and cover the dtype's sublane tile (16 for bf16)
    return hd % 128 == 0 and ps % _sublane(q.dtype) == 0 and P >= 1


def ring_table(layer, rows, ring_shape):
    """The block table that reads rows ``rows`` [B] of layer ``layer`` of
    a ring leaf ``[layers, rows, pages, kv, ps, hd]`` as pages of the leaf
    flattened to ``[layers * rows * pages, kv, ps, hd]`` (a bitcast)."""
    _, nrows, npages = ring_shape[:3]
    first = (jnp.asarray(layer, jnp.int32) * nrows
             + rows.astype(jnp.int32)) * npages
    return first[:, None] + jnp.arange(npages, dtype=jnp.int32)[None, :]


def ring_window_attention(q, ring_k, ring_v, layer, rows, lengths, *,
                          window, scale=None, interpret=False, ref=False):
    """Decode attention over a window kept as a ring a sequence: ``ring_k``
    / ``ring_v`` [layers, rows, pages, kv_heads, page_size, head_dim]
    (``pages * page_size`` slots a row, position p at slot p mod that),
    slot i's row ``rows[i]``, ``lengths`` the true lengths (0: an empty
    slot, a zero output row). The ring is read where it lies through
    ``ragged_paged_attention``'s ``window`` form (``ref``: the gather
    reference with the same mask)."""
    bt = ring_table(layer, rows, ring_k.shape)
    flat = [r.reshape((-1,) + r.shape[3:]) for r in (ring_k, ring_v)]
    if ref:
        return paged_attention_ref(q, *flat, bt, lengths, scale=scale,
                                   window=window)
    return ragged_paged_attention(q, *flat, bt, lengths, scale=scale,
                                  window=window, interpret=interpret)
