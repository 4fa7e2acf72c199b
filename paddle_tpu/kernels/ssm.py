"""The selective state-space recurrences of Mamba-2 and (at the end of
the file) Mamba-1 mixers, each in the two forms serving needs.

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


# ---------------------------------------------------------------------------
# Mamba-1 (S6): a decay a (channel, state) pair
#
#     s_t = exp(dt_t * A) . s_{t-1} + (dt_t x_t) (x) B_t ;  y_t = s_t C_t
#
# with ``A`` [d_state, channels] negative, ``dt_t`` and ``x_t`` a value a
# channel, ``B_t`` and ``C_t`` vectors of ``d_state`` shared by every
# channel: no heads, no groups. The state of a sequence a layer lies
# ``[d_state, channels]``, the channels on the lanes: ``dt`` and ``dt x``
# are rows that broadcast over sublanes, ``B`` and ``C`` columns, ``y`` a
# sum over sublanes. The decay ``exp(dt * A)`` is as large as the state; it
# is made inside the kernel from ``dt`` and ``A`` (``A`` is fetched once,
# its block never changes), so a call moves the state in and out and
# nothing else of its size.
# ---------------------------------------------------------------------------

_S6_LANES = 1024          # channels a pass of the kernel's body takes


def _s6_kernel(rows_ref, layer_ref, dd_ref, bc_ref, a_ref, s_ref, so_ref,
               y_ref):
    del rows_ref, layer_ref              # used by the index maps alone
    n, ch = s_ref.shape
    step = min(ch, _S6_LANES)
    for c0 in range(0, ch, step):
        sl = pl.ds(c0, min(step, ch - c0))
        dt, dtx = dd_ref[0:1, sl], dd_ref[1:2, sl]
        new = s_ref[:, sl] * jnp.exp(dt * a_ref[:, sl]) \
            + bc_ref[:, 0:1] * dtx
        so_ref[:, sl] = new
        y_ref[:, sl] = jnp.sum(new * bc_ref[:, 1:2], axis=0, keepdims=True)


def s6_supported(state, dt) -> bool:
    """Whether the Pallas kernel takes these shapes: a float32 state whose
    channels fill lane tiles and whose ``d_state`` fills sublane tiles."""
    if state.ndim != 4 or dt.ndim != 2:
        return False
    return (jnp.dtype(state.dtype) == jnp.dtype(jnp.float32)
            and state.shape[3] % 128 == 0 and state.shape[2] % 8 == 0)


def ssm_state_update_s6(state, layer, rows, dt, dtx, a, b, c, *,
                        interpret=False):
    """One token a slot through layer ``layer``'s S6 recurrence, in place.

    ``state`` [L, R, N, C] float32; ``rows`` [B] int32 (a slot with
    nothing to update names the last row); ``dt`` [B, C] the step, ``dtx``
    [B, C] = dt * x; ``a`` [N, C] = -exp(A_log); ``b``, ``c`` [B, N].
    Returns (state', y [B, C] float32, without the skip): rows that no
    slot names are untouched, and ``state'`` is ``state``'s own buffer
    where the caller donates it (the output aliases the input)."""
    L, R, N, C = state.shape
    B = dt.shape[0]
    f32 = jnp.float32

    def slot(i, rows_ref, layer_ref):
        return (i, 0, 0)

    def state_block(i, rows_ref, layer_ref):
        return (layer_ref[0], rows_ref[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, 2, C), slot),
            pl.BlockSpec((None, N, 2), slot),
            pl.BlockSpec((N, C), lambda i, rows_ref, layer_ref: (0, 0)),
            pl.BlockSpec((None, None, N, C), state_block),
        ],
        out_specs=[
            pl.BlockSpec((None, None, N, C), state_block),
            pl.BlockSpec((None, 1, C), slot),
        ],
    )
    new, y = pl.pallas_call(
        _s6_kernel,
        name="ssm_state_update_s6",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, 1, C), f32)],
        # operands: rows, layer, dd, bc, a, state -> output 0
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(rows.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.stack([dt.astype(f32), dtx.astype(f32)], axis=1),
      jnp.stack([b.astype(f32), c.astype(f32)], axis=-1),
      a.astype(f32), state)
    return new, y[:, 0]


def ssm_state_update_s6_ref(state, layer, rows, dt, dtx, a, b, c):
    """The same update in plain XLA, for a state of any float type (the
    arithmetic is float32, the state is rounded once as it is stored)."""
    f32 = jnp.float32
    old = state[layer, rows].astype(f32)                      # [B, N, C]
    new = old * jnp.exp(dt.astype(f32)[:, None, :] * a.astype(f32)) \
        + b.astype(f32)[:, :, None] * dtx.astype(f32)[:, None, :]
    new = new.astype(state.dtype)
    y = jnp.einsum("bnc,bn->bc", new.astype(f32), c.astype(f32))
    return state.at[layer, rows].set(new), y


# tokens a turn of the plain scan's loop takes (the loop's own cost a turn is
# what it saves)
_S6_UNROLL = 8
# the Pallas scan: tokens a grid step, and sublane rows of 128 channels a
# grid step (8 rows: a (channel block, state index) tile is one vreg)
_S6_CHUNK = 256
_S6_ROWS = 8


@jax.named_scope("ssm.scan")
def s6_scan_ref(x, dt, a, b, c):
    """Whole sequences through the S6 recurrence from a zero state, in
    plain XLA (every backend, any shape).

    ``x`` [G, S, C]; ``dt`` [G, S, C] float32, 0 at a padded token (which
    then neither decays the state nor adds to it); ``a`` [N, C]
    (negative); ``b``, ``c`` [G, S, N]. Returns (y [G, S, C] float32
    without the skip, the state after the last token [G, N, C] float32).
    The state is carried token by token: nothing of shape [S, N, C]
    exists."""
    f32 = jnp.float32
    G, S, C = x.shape
    N = a.shape[0]
    a = a.astype(f32)

    def token(s, t):
        dt_t, x_t, b_t, c_t = t
        dt_t = dt_t.astype(f32)
        s = s * jnp.exp(dt_t[:, None, :] * a) \
            + b_t.astype(f32)[:, :, None] \
            * (dt_t * x_t.astype(f32))[:, None, :]
        return s, jnp.einsum("gnc,gn->gc", s, c_t.astype(f32))

    last, y = lax.scan(
        token, jnp.zeros((G, N, C), f32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (dt, x, b, c)),
        unroll=min(_S6_UNROLL, S))
    return jnp.moveaxis(y, 0, 1), last


def _s6_chunk(S: int) -> int:
    """Tokens a grid step: the largest power of two up to ``_S6_CHUNK``
    that divides the sequence."""
    q = _S6_CHUNK
    while S % q:
        q //= 2
    return q


def s6_scan_supported(x, a) -> bool:
    """Whether the Pallas scan takes these shapes: channels in whole blocks
    of ``_S6_ROWS`` x 128, and chunks of at least 8 tokens."""
    return (x.ndim == 3 and x.shape[2] % (128 * _S6_ROWS) == 0
            and _s6_chunk(x.shape[1]) >= 8)


def _s6_scan_kernel(bc_ref, dt_ref, dtx_ref, a_ref, y_ref, last_ref, s_ref):
    n = a_ref.shape[0]
    q = dt_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    def token(t, carry):
        dt, dtx = dt_ref[t], dtx_ref[t]              # [rows, 128]
        y = jnp.zeros_like(dt)
        for i in range(n):
            s = s_ref[i] * jnp.exp(dt * a_ref[i]) + bc_ref[i, t] * dtx
            s_ref[i] = s
            y = y + bc_ref[n + i, t] * s
        y_ref[t] = y
        return carry

    lax.fori_loop(0, q, token, 0)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _end():
        last_ref[...] = s_ref[...]


@jax.named_scope("ssm.scan")
def s6_scan(x, dt, a, b, c, *, interpret=False):
    """``s6_scan_ref`` as a Pallas kernel (named ``s6_scan`` in a trace):
    the state of a block of channels stays in VMEM while a grid step walks
    a chunk of tokens, a (state index, channel block) tile one vreg; B and
    C ride in SMEM as scalars. Grid (sequence, channel block, chunk), the
    chunks in order."""
    f32 = jnp.float32
    G, S, C = x.shape
    N = a.shape[0]
    Q, R = _s6_chunk(S), _S6_ROWS
    rows = C // 128

    def lanes(t):                                    # [.., C] -> [.., rows, 128]
        return t.reshape(*t.shape[:-1], rows, 128)

    dt = dt.astype(f32)
    # [G, S, 2N] -> [G, chunks, 2N, Q]: a chunk's scalars, a token a column
    bc = jnp.swapaxes(jnp.concatenate([b, c], -1).astype(f32).reshape(
        G, S // Q, Q, 2 * N), 2, 3)
    tok = pl.BlockSpec((None, Q, R, 128), lambda g, r, k: (g, k, r, 0))
    y, last = pl.pallas_call(
        _s6_scan_kernel,
        name="s6_scan",
        grid=(G, rows // R, S // Q),
        in_specs=[
            pl.BlockSpec((None, None, 2 * N, Q), lambda g, r, k: (g, k, 0, 0),
                         memory_space=pltpu.SMEM),
            tok, tok,
            pl.BlockSpec((N, R, 128), lambda g, r, k: (0, r, 0)),
        ],
        out_specs=[
            tok,
            pl.BlockSpec((None, N, R, 128), lambda g, r, k: (g, 0, r, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((G, S, rows, 128), f32),
                   jax.ShapeDtypeStruct((G, N, rows, 128), f32)],
        scratch_shapes=[pltpu.VMEM((N, R, 128), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(bc, lanes(dt), lanes(dt * x.astype(f32)), lanes(a.astype(f32)))
    return y.reshape(G, S, C), last.reshape(G, N, C)
