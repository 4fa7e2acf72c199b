"""The selective state-space recurrence of a Mamba-2 mixer, in the two
forms serving needs.

The recurrence (Mamba-2 / SSD, arXiv:2405.21060), a head at a time: a
state ``H`` of ``d_state x head_dim`` numbers a sequence,

    H_t = exp(dt_t * A) * H_{t-1} + B_t (x) (dt_t * x_t)
    y_t = C_t . H_t

with ``A`` a negative scalar a head, ``dt_t`` a positive scalar a head a
token, ``B_t`` and ``C_t`` vectors of ``d_state`` shared by a group of
heads. The skip ``D * x_t`` and everything round the recurrence (the
projections, the convolution, the gate) are the model's
(``models/falcon_h1.py``).

- ``ssm_state_update`` is the decode step: one token a slot, the state
  read and written IN PLACE. The state of every layer and row is one
  array ``[L, rows, heads, d_state, head_dim]`` float32 that stays in
  HBM; a Pallas call (named ``ssm_state_update`` in a trace) walks the
  grid (slot, group, block of heads), fetches each block through a
  ``BlockSpec`` whose index comes from the prefetched row table and the
  layer, and writes it back to the same place: the output aliases the
  input (``input_output_aliases``), so the state exists once. A head's
  state is stored ``[d_state, head_dim]``, head_dim on the lanes: then
  ``dt * x`` is a row that broadcasts over sublanes for nothing, ``y``
  is a sum over sublanes, and only ``B`` and ``C`` are columns, brought
  in as a ``[d_state, 2]`` block a group. A slot without a sequence
  points at the array's last row, which no sequence ever owns: its
  block is read and written like any other and nobody reads it.
- ``ssd_chunked_scan`` is the prefill: a whole prompt in chunks of
  ``chunk`` tokens, products inside a chunk and a short recurrence over
  chunk states, as XLA einsums. A token with ``dt = 0`` neither decays
  nor adds, which is how padding leaves the state alone.

``ssm_state_update_ref`` is the same update as plain XLA (gather the
rows, update, scatter): every backend, and what the dispatcher
(``kernels/__init__.py``, counter ``ssm_fallback``) takes off the TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a block of heads' states, once: the call holds it four times (in and
# out, two buffers each)
_BLOCK_BYTES = 2 * 1024 * 1024
_VMEM_LIMIT = 32 * 1024 * 1024


def _heads_per_block(hg: int, n: int, p: int) -> int:
    """The most heads of one group whose float32 states fit a block."""
    hb = max(1, min(hg, _BLOCK_BYTES // (n * p * 4)))
    while hg % hb:
        hb -= 1
    return hb


def _update_kernel(rows_ref, layer_ref, decay_ref, dtx_ref, bc_ref, s_ref,
                   so_ref, y_ref):
    del rows_ref, layer_ref              # used by the index maps alone
    hb, n, p = s_ref.shape
    bcol = jnp.broadcast_to(bc_ref[:, 0:1], (n, p))
    ccol = jnp.broadcast_to(bc_ref[:, 1:2], (n, p))

    def one_head(h, carry):
        new = s_ref[h] * decay_ref[pl.ds(h, 1), :] \
            + bcol * dtx_ref[pl.ds(h, 1), :]
        so_ref[h] = new
        y_ref[pl.ds(h, 1), :] = jnp.sum(new * ccol, axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, hb, one_head, 0)


def supported(state, dtx, b) -> bool:
    """Whether the Pallas kernel takes these shapes: a float32 state whose
    heads fill lane tiles and whose ``d_state`` fills sublane tiles."""
    if state.ndim != 5 or dtx.ndim != 3 or b.ndim != 3:
        return False
    _, _, heads, n, p = state.shape
    return (jnp.dtype(state.dtype) == jnp.dtype(jnp.float32)
            and p % 128 == 0 and n % 8 == 0 and heads % b.shape[1] == 0)


def ssm_state_update(state, layer, rows, decay, dtx, b, c, *,
                     interpret=False):
    """One token a slot through layer ``layer``'s recurrence, in place.

    ``state`` [L, R, H, N, P] float32; ``rows`` [B] int32, the row of
    each slot (a slot with nothing to update names the last row);
    ``decay`` [B, H] = exp(dt * A); ``dtx`` [B, H, P] = dt * x; ``b``,
    ``c`` [B, G, N]. Returns (state', y [B, H, P] float32): rows that no
    slot names are untouched, and ``state'`` is ``state``'s own buffer
    where the caller donates it."""
    L, R, H, N, P = state.shape
    B, G = b.shape[0], b.shape[1]
    hg = H // G
    hb = _heads_per_block(hg, N, P)
    per_group = hg // hb
    f32 = jnp.float32

    def head_block(i, g, k, rows_ref, layer_ref):
        return (i, g * per_group + k, 0)

    def state_block(i, g, k, rows_ref, layer_ref):
        return (layer_ref[0], rows_ref[i], g * per_group + k, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, G, per_group),
        in_specs=[
            pl.BlockSpec((None, hb, P), head_block),
            pl.BlockSpec((None, hb, P), head_block),
            pl.BlockSpec((None, None, N, 2),
                         lambda i, g, k, rows_ref, layer_ref: (i, g, 0, 0)),
            pl.BlockSpec((None, None, hb, N, P), state_block),
        ],
        out_specs=[
            pl.BlockSpec((None, None, hb, N, P), state_block),
            pl.BlockSpec((None, hb, P), head_block),
        ],
    )
    new, y = pl.pallas_call(
        _update_kernel,
        name="ssm_state_update",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, P), f32)],
        # operands: rows, layer, decay, dtx, bc, state -> output 0
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(rows.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.broadcast_to(decay.astype(f32)[..., None], (B, H, P)),
      dtx.astype(f32),
      jnp.stack([b.astype(f32), c.astype(f32)], axis=-1), state)
    return new, y


def ssm_state_update_ref(state, layer, rows, decay, dtx, b, c):
    """The same update in plain XLA, for a state of any float type (the
    arithmetic is float32, the state is rounded once as it is stored)."""
    H, G = state.shape[2], b.shape[1]
    f32 = jnp.float32
    bh, ch = (jnp.repeat(t.astype(f32), H // G, axis=1) for t in (b, c))
    old = state[layer, rows].astype(f32)                   # [B, H, N, P]
    new = old * decay.astype(f32)[..., None, None] \
        + bh[..., :, None] * dtx.astype(f32)[..., None, :]
    new = new.astype(state.dtype)
    y = jnp.einsum("bhnp,bhn->bhp", new.astype(f32), ch)
    return state.at[layer, rows].set(new), y


@jax.named_scope("ssm.scan")
def ssd_chunked_scan(x, dt, a, b, c, chunk: int):
    """A whole sequence through the recurrence from a zero state.

    ``x`` [G, S, H, P]; ``dt`` [G, S, H] float32, 0 at a padded token;
    ``a`` [H] (negative); ``b``, ``c`` [G, S, R, N] for R groups of
    heads. Returns (y [G, S, H, P] float32, the state after the last
    token [G, H, N, P] float32). Products take their operands in ``x``'s
    type and accumulate in float32; decays are float32 throughout."""
    G, S, H, P = x.shape
    R, N = b.shape[2], b.shape[3]
    E = H // R                                    # heads a group
    f32, cd = jnp.float32, x.dtype
    pad = -S % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                               (t.ndim - 2)) for t in (x, dt, b, c))
    nc = (S + pad) // chunk
    Q = chunk
    dt = dt.astype(f32).reshape(G, nc, Q, R, E)
    dtx = (dt[..., None] * x.astype(f32).reshape(G, nc, Q, R, E, P)
           ).astype(cd)
    b = b.reshape(G, nc, Q, R, N).astype(cd)
    c = c.reshape(G, nc, Q, R, N).astype(cd)
    cum = jnp.cumsum(dt * a.astype(f32).reshape(R, E), axis=2)

    # inside a chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i.B_j) dtx_j
    cb = jnp.einsum("gcqrn,gckrn->gcrqk", c, b, preferred_element_type=f32)
    diff = jnp.moveaxis(cum, 2, -1)                         # [G,nc,R,E,Q]
    diff = diff[..., :, None] - diff[..., None, :]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    m = (cb[:, :, :, None] * decay).astype(cd)              # [G,nc,R,E,Q,Q]
    y = jnp.einsum("gcreqk,gckrep->gcqrep", m, dtx,
                   preferred_element_type=f32)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, :, -1:] - cum)                  # [G,nc,Q,R,E]
    own = jnp.einsum("gckrn,gckrep->gcrenp", b,
                     (to_end[..., None] * dtx.astype(f32)).astype(cd),
                     preferred_element_type=f32)            # [G,nc,R,E,N,P]

    # across chunks: the state a chunk starts from
    def carry_over(h, xs):
        own_c, total_c = xs
        return h * jnp.exp(total_c)[..., None, None] + own_c, h

    last, before = lax.scan(
        carry_over, jnp.zeros((G, R, E, N, P), f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(cum[:, :, -1], 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                     # [G,nc,R,E,N,P]
    y = y + jnp.einsum("gcqrn,gcrenp->gcqrep", c, before.astype(cd),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y.reshape(G, nc * Q, H, P)[:, :S]
    return y, last.reshape(G, H, N, P)
