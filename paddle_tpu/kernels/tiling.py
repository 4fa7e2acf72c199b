"""Mosaic (Pallas TPU) block-shape legality rules.

The TPU lowering requires that the last two dimensions of every BlockSpec
block be divisible by the dtype's native tile — (8, 128) for 4-byte types,
(16, 128) for 2-byte, (32, 128) for 1-byte — OR equal the corresponding
dimension of the overall array. Rank-1 blocks need the last dim divisible
by 128 or equal to the array's. Interpret mode does not enforce this, so
a kernel can pass every CPU test and still fail to lower on the chip
(exactly what BENCH_r02 recorded); `block_legal` lets `supported()` and
the test suite check legality without a TPU.

Reference capability: the reference validates kernel launch configs at
dispatch time (phi KernelFactory); here legality is a pure shape predicate
so the XLA fallback can engage *before* a doomed pallas_call is traced.
"""
from __future__ import annotations

import numpy as np

_LANE = 128


def _sublane(dtype) -> int:
    itemsize = np.dtype(dtype).itemsize
    return {4: 8, 2: 16, 1: 32}.get(itemsize, 8)


def block_legal(block_shape, array_shape, dtype=np.float32) -> bool:
    """Whether Mosaic can lower a block of ``block_shape`` (ints, or None
    for squeezed dims) over an array of ``array_shape``.

    Note: squeezed (None) dims still count toward the trailing-two rule —
    a ``(None, bq)`` block over ``[bh, sq]`` is checked as ``(1, bq)`` and
    is illegal unless ``bh == 1`` (verified empirically on TPU v5e).
    """
    block = [1 if b is None else int(b) for b in block_shape]
    array = list(array_shape)
    if len(block) != len(array):
        return False
    if any(b < 1 or b > a for b, a in zip(block, array)):
        return False
    if len(block) == 0:
        return True
    sub = _sublane(dtype)
    if len(block) == 1:
        return block[-1] % _LANE == 0 or block[-1] == array[-1]
    ok_lane = block[-1] % _LANE == 0 or block[-1] == array[-1]
    ok_sub = block[-2] % sub == 0 or block[-2] == array[-2]
    return ok_lane and ok_sub


# The dense flash kernels' tiling (kernels/flash_attention.py). A grid step
# of the forward and dq kernels holds a *span* of K and V in VMEM and
# loops over its block_k sub-blocks; the dkv kernel holds a span of q and
# dO and loops over block_q sub-blocks. FLASH_RESIDENT_BYTES bounds the two
# arrays of a span with both their pipeline buffers (the whole sequence to
# 8,192 tokens of bf16 at head dim 128); FLASH_VMEM_BUDGET, what a kernel's
# blocks, scratch and tile temporaries may take together, is the 16 MiB a
# kernel gets by default. FLASH_MAX_BLOCKS is what the chip's sweep found
# (PERF.md section 6, PR 30): 512 x 512 was the fastest sum of the three
# kernels at every shape from 512 tokens up, larger blocks won nothing.
FLASH_RESIDENT_BYTES = 8 * 1024 * 1024
FLASH_VMEM_BUDGET = 16 * 1024 * 1024
FLASH_MAX_BLOCKS = (512, 512)


def flash_span(s, block, d, dtype) -> int:
    """Tokens of a sequence of ``s`` one grid step keeps resident: the
    largest whole number of ``block``s that divides ``s`` and keeps two
    arrays of it, double-buffered, inside FLASH_RESIDENT_BYTES."""
    n = s // block
    row = 4 * d * np.dtype(dtype).itemsize
    for parts in range(1, n + 1):
        if n % parts == 0 and (s // parts) * row <= FLASH_RESIDENT_BYTES:
            return s // parts
    return block


def flash_vmem_bytes(sq, sk, d, block_q, block_k, dtype) -> int:
    """VMEM the hungriest of the three dense kernels takes at these
    blocks: operand and result blocks twice (the pipeline's buffers), the
    resident spans, float32 accumulators, the row statistics padded to
    whole lane tiles, and about five float32 [block_q, block_k] tiles
    (scores, probabilities, dP, dS and a cast) in flight."""
    it = np.dtype(dtype).itemsize
    tiles = 5 * block_q * block_k * 4
    q_major = (4 * flash_span(sk, block_k, d, dtype) * d * it   # K, V
               + 6 * block_q * d * it                      # q, dO, dq / o
               + 4 * block_q * _LANE * 4                   # lse, delta
               + block_q * (d + 2 * _LANE) * 4)            # acc, m, l
    span_q = flash_span(sq, block_q, d, dtype)
    k_major = (4 * span_q * d * it + 4 * 8 * span_q * 4     # q, dO, stats
               + 8 * block_k * d * it                      # k, v, dk, dv
               + 2 * block_k * d * 4)                      # accumulators
    return tiles + max(q_major, k_major)


def _largest_block(s, cap) -> int:
    """The largest 128 * 2**n <= cap that divides ``s``; a sequence under
    128, or off it, gets min(s, 128) (one block, or none that divides)."""
    best = min(s, _LANE)
    b = _LANE
    while b <= min(cap, s):
        if s % b == 0:
            best = b
        b *= 2
    return best


def flash_blocks_for(sq, sk, d, dtype):
    """(block_q, block_k) of a dense flash call from its shape alone: the
    largest blocks up to FLASH_MAX_BLOCKS that divide the sequences and
    whose working set fits FLASH_VMEM_BUDGET (the larger one halves until
    it does)."""
    bq = _largest_block(sq, FLASH_MAX_BLOCKS[0])
    bk = _largest_block(sk, FLASH_MAX_BLOCKS[1])
    while (flash_vmem_bytes(sq, sk, d, bq, bk, dtype) > FLASH_VMEM_BUDGET
           and max(bq, bk) > _LANE):
        if bk >= bq:
            bk //= 2
        else:
            bq //= 2
    return bq, bk


def flash_specs_legal(bh, sq, sk, d, block_q, block_k, dtype) -> bool:
    """Legality of every BlockSpec the flash kernels emit (fwd + bwd), and
    of the sub-block slices they take of a resident span: a dynamic slice
    starts on a sublane tile (rows) or a lane tile (the dkv kernel's
    lane-major statistics)."""
    lse = np.float32
    span_k = flash_span(sk, block_k, d, dtype)
    span_q = flash_span(sq, block_q, d, dtype)
    return (
        # q/o/do/dq blocks: (1, block_q, d) over [bh, s, d]
        block_legal((1, block_q, d), (bh, sq, d), dtype)
        # dk/dv blocks, and the dkv kernel's k/v: (1, block_k, d)
        and block_legal((1, block_k, d), (bh, sk, d), dtype)
        # lse/delta blocks: (1, block_q, 1) over [bh, sq, 1] (always f32)
        and block_legal((1, block_q, 1), (bh, sq, 1), lse)
        # the resident spans: K/V, q/dO, and lse/delta over [bh, 1, sq]
        and block_legal((1, span_k, d), (bh, sk, d), dtype)
        and block_legal((1, span_q, d), (bh, sq, d), dtype)
        and block_legal((1, 1, span_q), (bh, 1, sq), lse)
        and (span_k == block_k or block_k % _sublane(dtype) == 0)
        and (span_q == block_q or block_q % _LANE == 0)
    )


def segment_specs_legal(b, sq, sk, block_q, block_k) -> bool:
    """Legality of the EXTRA BlockSpecs the segment-aware flash kernels
    add on top of flash_specs_legal: per-token segment-id / position
    arrays in the trailing-singleton layout (q side ``[B, Sq, 1]`` with
    (1, block_q, 1) blocks — the LSE trick) and the lane-major k side
    (``[B, 1, Sk]`` with (1, 1, block_k) blocks, whose last dim must hit
    the 128-lane rule or equal Sk). All int32."""
    i32 = np.int32
    return (block_legal((1, block_q, 1), (b, sq, 1), i32)
            and block_legal((1, 1, block_k), (b, 1, sk), i32))
