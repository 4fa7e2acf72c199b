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

A row may take k experts (``expert`` [T, k], each pick weighed by
``weight`` [T, k], the k outputs summed back a row in float32), and the
weights may be ONE CHIP'S SHARE of a layer's experts: the held range
``[first, first + E)`` of the ``routed`` experts the router picks among
(an expert-parallel deployment, ``models/axk1.py``). A pick inside the
range gets a slot in the expert-by-expert layout; a pick elsewhere gets
none, is never computed and adds nothing, so the sum is exactly this
chip's part of the deployment's. One pick a row and every expert held
(``models/zaya.py``) is the same walk as ever.

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


def slots(expert, E: int, tm: int, partial: bool = False):
    """Where each row goes: (``slot`` [T] of each row in the padded
    layout, ``source`` [Mp] the row in each slot or T for padding,
    ``tile_expert`` [Mp / tm], ``used`` the tiles in use). A tile past
    ``used`` names the last tile's expert. With ``partial`` a row whose
    expert lies outside ``[0, E)`` gets no slot: its ``slot`` is ``Mp``."""
    T = expert.shape[0]
    nt = -(-(T + E * (tm - 1)) // tm)
    hot = jax.nn.one_hot(expert, E, dtype=jnp.int32)     # outside: no 1
    e = jnp.clip(expert, 0, E - 1) if partial else expert
    rank = jnp.take_along_axis(jnp.cumsum(hot, 0), e[:, None],
                               1)[:, 0] - 1
    tiles = -(-hot.sum(0) // tm)                         # [E] tiles a run
    end = jnp.cumsum(tiles)
    slot = (end - tiles)[e] * tm + rank
    if partial:
        slot = jnp.where((expert >= 0) & (expert < E), slot, nt * tm)
    source = jnp.full((nt * tm,), T, jnp.int32).at[slot].set(
        jnp.arange(T, dtype=jnp.int32), unique_indices=True,
        mode="drop" if partial else None)
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


def _picks(expert, first: int, routed, E: int):
    """(the picks flat [T * k] relative to the held range, k, whether the
    range is partial)."""
    k = 1 if expert.ndim == 1 else expert.shape[1]
    partial = first != 0 or (routed or E) != E
    flat = expert.reshape(-1)
    return (flat - first if first else flat), k, partial


def expert_mlp(x, expert, gate, up, down, layer, *, weight=None, first=0,
               routed=None, name="moe_expert_mlp", interpret=False):
    """Each row of ``x`` [T, D] through expert ``expert[t]`` of layer
    ``layer``: [T, D] in ``x``'s type. ``gate``, ``up``, ``down``
    [L, E, F, D]. With ``expert`` [T, k] and ``weight`` [T, k]: the sum
    over a row's picks of ``weight`` times the pick's output, where the
    weights hold experts ``[first, first + E)`` of the ``routed`` the
    picks index (picks elsewhere add nothing)."""
    T, D = x.shape
    _, E, F, _ = gate.shape
    picks, k, partial = _picks(expert, first, routed, E)
    # a tile holds about an expert's expected share of the held picks
    tm = row_tile(T * k * E // (routed or E), E, x.dtype)
    tf = _WIDTH_BLOCK if F % _WIDTH_BLOCK == 0 else 128
    with jax.named_scope("moe.dispatch"):
        slot, source, tile_expert, used = slots(picks, E, tm, partial)
        nt = tile_expert.shape[0]
        xs = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[
            source // k if k > 1 else source]

    def rows(t, f, te, layer, used):
        last = used[0] - 1
        if partial:
            # a share may hold no pick of a call at all (a prompt's chunk
            # of padding; a few rows whose picks are all another chip's):
            # no tile in use, and a block index of -1 would copy from
            # before the rows (the device halts on the bounds check)
            last = jnp.maximum(last, 0)
        return (jnp.minimum(t, last), 0)

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
        if not partial and weight is None:
            return ys[slot]
        y = jnp.take(ys, slot, axis=0, mode="fill", fill_value=0)
        if weight is None:
            return y
        return jnp.einsum("tkd,tk->td", y.reshape(T, k, D).astype(
            jnp.float32), weight.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("moe.experts")
def expert_mlp_ref(x, expert, gate, up, down, layer, *, weight=None,
                   first=0, routed=None):
    """``expert_mlp`` in plain XLA: every expert over every row, masked.
    The same products in the same types (float32 sums, the gated product
    rounded to ``x``'s type before ``down``)."""
    g, u, d = gate[layer], up[layer], down[layer]          # [E, F, D]
    gg = jnp.einsum("td,efd->etf", x, g, preferred_element_type=jnp.float32)
    uu = jnp.einsum("td,efd->etf", x, u, preferred_element_type=jnp.float32)
    h = (gg * jax.nn.sigmoid(gg) * uu).astype(x.dtype)
    y = jnp.einsum("etf,efd->etd", h, d, preferred_element_type=jnp.float32)
    if weight is None:
        hot = jax.nn.one_hot(expert, g.shape[0], dtype=jnp.float32)  # [T, E]
    else:                    # a pick outside the held range: no column
        hot = jnp.einsum("tke,tk->te", jax.nn.one_hot(
            expert - first, g.shape[0], dtype=jnp.float32),
            weight.astype(jnp.float32))
    return jnp.einsum("etd,te->td", y, hot).astype(x.dtype)
