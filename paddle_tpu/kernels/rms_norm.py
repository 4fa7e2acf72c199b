"""Pallas fused RMSNorm (forward + backward).

Reference capability: python/paddle/incubate/nn/functional/fused_rms_norm.py
(backed by phi fused kernels). TPU-native: one row-tiled kernel per pass —
a single HBM read of x produces y (and the saved rstd), instead of the
separate mean-square/normalize/scale ops; backward fuses the two reduction
terms. XLA already fuses simple norm chains well; this kernel exists for
the long-row case (hidden >= 4096) where keeping the row resident in VMEM
beats XLA's fusion, and as the pattern for further fused kernels.

Mosaic legality (see tiling.py): rstd is carried as [n, 1] — a (br, 1)
block over it hits the "equal to the array dim" arm of the tiling rule;
rank-1 (br,) blocks over a partitioned [n] array fail to lower on real
TPU (verified v5e). The backward's dw reduction accumulates into a single
(1, d) output block with a constant index map (the canonical Pallas
reduction pattern) instead of one partial row per grid step, whose
(1, d) block over [grid, d] is illegal whenever grid > 1 — the BENCH_r02
class of bug.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_ROWS = 256
# rows*cols budget per block: ~6 live (br, d) f32 buffers double-buffered
# must fit the ~16MB scoped-vmem limit (v5e OOMs at br=256, d=4096)
_MAX_BLOCK_ELEMS = 128 * 1024


def _pick_block_rows(block_rows, n, d):
    br = min(block_rows, n, max(8, (_MAX_BLOCK_ELEMS // d) // 8 * 8))
    while br > 8 and n % br != 0:
        br -= 8
    return br


def _fwd_kernel(x_ref, w_ref, y_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y_ref[:] = (x * r * w_ref[:].astype(jnp.float32)).astype(y_ref.dtype)
    rstd_ref[:] = r                                      # [br, 1]


def _bwd_kernel(x_ref, w_ref, rstd_ref, dy_ref, dx_ref, dw_ref, *, eps):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    r = rstd_ref[:]                                      # [br, 1]
    g = dy * w
    # dx = r*g - x * r^3 * mean(g*x)
    mean_gx = jnp.mean(g * x, axis=-1, keepdims=True)
    dx_ref[:] = (r * g - x * (r ** 3) * mean_gx).astype(dx_ref.dtype)
    # dw accumulates across the row grid into one resident (1, d) block
    @pl.when(i == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)
    dw_ref[:] += jnp.sum(dy * x * r, axis=0, keepdims=True)


def _rows(x):
    return x.reshape(-1, x.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def rms_norm(x, w, eps=1e-6, block_rows=DEFAULT_BLOCK_ROWS, interpret=False):
    y, _ = _rms_fwd(x, w, eps, block_rows, interpret)
    return y


def _call_fwd(x2, w, eps, br, interpret):
    n, d = x2.shape
    grid = (pl.cdiv(n, br),)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        name="rms_norm_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, w.reshape(1, d))


def _rms_fwd(x, w, eps, block_rows, interpret):
    x2 = _rows(x)
    n, d = x2.shape
    br = _pick_block_rows(block_rows, n, d)
    if n % br != 0 or br % 8 != 0:   # fallback: plain XLA path
        xf = x2.astype(jnp.float32)
        r = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        y = (xf * r * w.astype(jnp.float32)).astype(x.dtype)
        return y.reshape(x.shape), (x, w, r, True)
    y, rstd = _call_fwd(x2, w, eps, br, interpret)
    return y.reshape(x.shape), (x, w, rstd, interpret)


def _rms_bwd(eps, block_rows, _interp_unused, res, dy):
    x, w, rstd, interpret = res                          # rstd: [n, 1]
    x2 = _rows(x)
    dy2 = _rows(dy)
    n, d = x2.shape
    br = _pick_block_rows(block_rows, n, d)
    if n % br != 0 or br % 8 != 0:
        xf = x2.astype(jnp.float32)
        g = dy2.astype(jnp.float32) * w.astype(jnp.float32)
        r = rstd
        dx = (r * g - xf * (r ** 3)
              * jnp.mean(g * xf, -1, keepdims=True)).astype(x.dtype)
        dw = jnp.sum(dy2.astype(jnp.float32) * xf * r, axis=0)
        return dx.reshape(x.shape), dw.astype(w.dtype)
    grid = (pl.cdiv(n, br),)
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        name="rms_norm_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2, w.reshape(1, d), rstd, dy2)
    return dx.reshape(x.shape), dw[0].astype(w.dtype)


rms_norm.defvjp(_rms_fwd, _rms_bwd)
