"""An expert layer that drops nothing: every row through its OWN expert's
gated MLP, whatever the imbalance.

``expert_mlp(x, expert, gate, up, down, layer)``: ``x`` [T, D] rows,
``expert`` [T] the one expert each row takes, the experts' weights whole
as the model stacks them (``gate``, ``up``, ``down`` [L, E, F, D]: their
layout is below) and the layer to read. There is no ``[E, C]``
grid and no capacity: rows are laid out expert by expert, each expert's
run padded to a multiple of a row tile (``slots``: a cumulative sum over
the one-hot picks, no sort), so that a tile of rows belongs to one
expert, and a Pallas call (``moe_expert_mlp_decode`` /
``moe_expert_mlp_prefill`` in a trace, by the caller's ``name``) walks
the grid (row tile, block of the expert's width): ``silu(x g^T) * (x
u^T)`` for the block, times the block of ``down``, summed in a float32
scratch. The tile's expert and the layer come through scalar prefetch
into the weights' index maps, so a matrix is read where it lies in the
stack; a tile past the last one in use repeats the last one's indices
and computes nothing, and AN EXPERT NOBODY PICKED IS NEVER READ. One
expert taking every row is the same walk with one run.

Weights lie ``[outputs, inputs]`` for ``gate`` and ``up`` ([F, D]: a
block of outputs is one contiguous copy) and ``[inputs, outputs]`` for
``down`` ([F, D] too: a block of its inputs).

``expert_mlp_ref`` is the same layer in plain XLA (each expert over all
rows, masked): every backend, what the dispatcher
(``kernels/__init__.py``, counter ``moe_fallback``) takes off the TPU,
and fit only for small sizes, since it computes E times the products.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_LIMIT = 96 * 1024 * 1024
_MAX_ROW_TILE = 512
_WIDTH_BLOCK = 512


def row_tile(T: int, E: int, dtype) -> int:
    """Rows a tile: about an expert's even share of ``T`` rows, a power
    of two between the type's sublane tile and ``_MAX_ROW_TILE``."""
    floor = 16 if jnp.dtype(dtype).itemsize == 2 else 8
    tm = floor
    while tm * 2 <= min(_MAX_ROW_TILE, max(floor, T // E)):
        tm *= 2
    return tm


def slots(expert, E: int, tm: int):
    """Where each row goes: (``slot`` [T] of each row in the padded
    layout, ``source`` [Mp] the row in each slot or T for padding,
    ``tile_expert`` [Mp / tm], ``used`` the tiles in use). A tile past
    ``used`` names the last tile's expert."""
    T = expert.shape[0]
    nt = -(-(T + E * (tm - 1)) // tm)
    hot = jax.nn.one_hot(expert, E, dtype=jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(hot, 0), expert[:, None],
                               1)[:, 0] - 1
    tiles = -(-hot.sum(0) // tm)                         # [E] tiles a run
    end = jnp.cumsum(tiles)
    slot = (end - tiles)[expert] * tm + rank
    source = jnp.full((nt * tm,), T, jnp.int32).at[slot].set(
        jnp.arange(T, dtype=jnp.int32), unique_indices=True)
    used = end[-1]
    tile_expert = jnp.searchsorted(
        end, jnp.minimum(jnp.arange(nt), used - 1), side="right")
    return slot, source, tile_expert.astype(jnp.int32), used.astype(jnp.int32)


def _kernel(te_ref, layer_ref, used_ref, x_ref, g_ref, u_ref, d_ref, o_ref,
            acc_ref):
    del te_ref, layer_ref                # used by the index maps alone
    t, f, nf = pl.program_id(0), pl.program_id(1), pl.num_programs(1)

    @pl.when(t < used_ref[0])
    def _():
        @pl.when(f == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        nt_dims = (((1,), (1,)), ((), ()))               # x @ w^T
        g = jax.lax.dot_general(x, g_ref[...], nt_dims,
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(x, u_ref[...], nt_dims,
                                preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        acc_ref[...] += jnp.dot(h, d_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(f == nf - 1)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def supported(x, gate) -> bool:
    """Whether the Pallas kernel takes these shapes: rows and widths
    that fill lane tiles, a width the block divides."""
    if x.ndim != 2 or gate.ndim != 4:
        return False
    D, F = x.shape[1], gate.shape[2]
    return (D % 128 == 0 and F % 128 == 0 and gate.shape[3] == D
            and jnp.dtype(x.dtype) in (jnp.dtype(jnp.bfloat16),
                                       jnp.dtype(jnp.float32)))


def expert_mlp(x, expert, gate, up, down, layer, *, name="moe_expert_mlp",
               interpret=False):
    """Each row of ``x`` [T, D] through expert ``expert[t]`` of layer
    ``layer``: [T, D] in ``x``'s type. ``gate``, ``up``, ``down``
    [L, E, F, D]."""
    T, D = x.shape
    _, E, F, _ = gate.shape
    tm = row_tile(T, E, x.dtype)
    tf = _WIDTH_BLOCK if F % _WIDTH_BLOCK == 0 else 128
    with jax.named_scope("moe.dispatch"):
        slot, source, tile_expert, used = slots(expert, E, tm)
        nt = tile_expert.shape[0]
        xs = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[source]

    def rows(t, f, te, layer, used):
        return (jnp.minimum(t, used[0] - 1), 0)

    def weights(t, f, te, layer, used):
        # past the last tile in use: the block the last step held
        return (layer[0], te[t], jnp.where(t < used[0], f, F // tf - 1), 0)

    call = pl.pallas_call(
        _kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nt, F // tf),
            in_specs=[pl.BlockSpec((tm, D), rows),
                      pl.BlockSpec((None, None, tf, D), weights),
                      pl.BlockSpec((None, None, tf, D), weights),
                      pl.BlockSpec((None, None, tf, D), weights)],
            out_specs=pl.BlockSpec((tm, D), rows),
            scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((nt * tm, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)
    with jax.named_scope("moe.experts"):
        ys = call(tile_expert, jnp.asarray(layer, jnp.int32).reshape(1),
                  used.reshape(1), xs, gate, up, down)
    with jax.named_scope("moe.combine"):
        return ys[slot]


@jax.named_scope("moe.experts")
def expert_mlp_ref(x, expert, gate, up, down, layer):
    """``expert_mlp`` in plain XLA: every expert over every row, masked.
    The same products in the same types (float32 sums, the gated product
    rounded to ``x``'s type before ``down``)."""
    g, u, d = gate[layer], up[layer], down[layer]          # [E, F, D]
    gg = jnp.einsum("td,efd->etf", x, g, preferred_element_type=jnp.float32)
    uu = jnp.einsum("td,efd->etf", x, u, preferred_element_type=jnp.float32)
    h = (gg * jax.nn.sigmoid(gg) * uu).astype(x.dtype)
    y = jnp.einsum("etf,efd->etd", h, d, preferred_element_type=jnp.float32)
    hot = jax.nn.one_hot(expert, g.shape[0], dtype=jnp.float32)   # [T, E]
    return jnp.einsum("etd,te->td", y, hot).astype(x.dtype)
