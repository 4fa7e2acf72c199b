"""Pallas TPU kernel library — the phi/kernels/fusion equivalent.

Reference capability: paddle/phi/kernels/fusion/ (52 fused CUDA kernels) and
the flash-attn wrapper (gpu/flash_attn_kernel.cu). TPU-native: hand-written
pallas kernels for the ops where XLA's automatic fusion is not enough —
flash attention (tiled online softmax on the MXU), paged decode attention,
the in-place recurrent state update of a Mamba-2 layer
(``ssm_state_update``), a decode step's token written into both halves of
the page pool in place (``kv_token_write``) and fused RMSNorm; the
rest of the reference's fused set (bias+act, rope, swiglu) is left to XLA
fusion, which already emits single kernels for those elementwise chains.

Dispatch mirrors the reference's KernelFactory choice (SURVEY.md §7
"KernelFactory dispatch" row): `register()` installs the pallas impls into
the functional seams (attention._FLASH_IMPL, norm._FUSED_RMS_IMPL) with
shape-support guards and XLA fallback. On TPU the kernels compile natively
(``interpret=False`` is passed explicitly at every dispatch); off-TPU the
dispatchers fall back, and tests ask for pallas interpret mode by argument
(``register(interpret=True)``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import moe_experts as _moe
from . import fused_ce as _fce
from . import kv_write as _kw
from . import paged_attention as _pa
from . import rms_norm as _rn
from . import ssm as _ssm
from .ring_attention import ring_attention  # noqa

flash_attention = _fa.flash_attention
flash_attention_segments = _fa.flash_attention_segments
segment_attention_ref = _fa.segment_attention_ref
count_skipped_blocks = _fa.count_skipped_blocks
fused_rms_norm = _rn.rms_norm
fused_cross_entropy = _fce.fused_cross_entropy
ragged_paged_attention = _pa.ragged_paged_attention
paged_attention_ref = _pa.paged_attention_ref
ssm_state_update = _ssm.ssm_state_update
ssm_state_update_ref = _ssm.ssm_state_update_ref
ssd_chunked_scan = _ssm.ssd_chunked_scan
ssm_state_update_s6 = _ssm.ssm_state_update_s6
ssm_state_update_s6_ref = _ssm.ssm_state_update_s6_ref
s6_scan = _ssm.s6_scan
s6_scan_ref = _ssm.s6_scan_ref
ring_window_attention = _pa.ring_window_attention
kv_token_write = _kw.kv_token_write
kv_token_write_ref = _kw.kv_token_write_ref
expert_mlp = _moe.expert_mlp
expert_mlp_ref = _moe.expert_mlp_ref

__all__ = ["flash_attention", "fused_rms_norm", "fused_cross_entropy",
           "dispatched_fused_ce", "ring_attention",
           "ragged_paged_attention", "paged_attention_ref",
           "dispatched_paged_attention",
           "ssm_state_update", "ssm_state_update_ref", "ssd_chunked_scan",
           "dispatched_ssm_update",
           "ssm_state_update_s6", "ssm_state_update_s6_ref", "s6_scan",
           "s6_scan_ref", "dispatched_s6_update", "dispatched_s6_scan", "ring_window_attention",
           "dispatched_ring_attention", "dispatched_window_flash",
           "expert_mlp", "expert_mlp_ref", "dispatched_expert_mlp",
           "kv_token_write", "kv_token_write_ref",
           "dispatched_kv_token_write",
           "flash_attention_segments", "segment_attention_ref",
           "count_skipped_blocks", "dispatched_segment_attention",
           "register", "unregister", "dispatch_stats", "reset_dispatch_stats"]

# Trace-time dispatch counters (reference capability: the KernelFactory's
# selected-kernel visibility / FLAGS_enable_api_kernel_fallback logging,
# kernel_factory.cc:230). Incremented when the dispatcher traces the pallas
# kernel vs the XLA fallback into a program — lets benchmarks *assert* the
# fast path actually engaged at their shapes instead of silently falling
# back (a silent `supported()` miss would quietly cost MFU).
_DISPATCH_STATS = {"flash": 0, "flash_fallback": 0,
                   "rms": 0, "rms_fallback": 0,
                   "fused_ce": 0, "fused_ce_fallback": 0,
                   "fused_ce_onepass": 0,
                   "paged": 0, "paged_fallback": 0,
                   "paged_quant": 0, "paged_quant_fallback": 0,
                   "varlen": 0, "varlen_fallback": 0,
                   "ssm": 0, "ssm_fallback": 0,
                   "moe": 0, "moe_fallback": 0,
                   "kv_write": 0, "kv_write_fallback": 0}

# register(interpret=True): the dispatchers with no seam of their own to
# install into run their kernels in interpret mode on any backend
_INTERPRET = False


def dispatch_stats() -> dict:
    """How often each dispatcher traced its kernel into a program, and
    how often the plain-XLA fallback (``*_fallback``). The table, kernel
    by counter: ``flash`` flash attention forward and backward
    (``flash_attention.py``); ``rms`` fused RMSNorm; ``fused_ce`` the
    blockwise cross entropy, and ``fused_ce_onepass`` (counted by the rule
    itself, ``fused_ce.py``) each trace of its one-pass differentiation
    rule: 1 after a train step's trace, 0 after a forward-only one;
    ``paged`` / ``paged_quant`` the paged decode
    attention ``paged_decode_attn`` and its int8-page arm; ``varlen`` the
    segment (packed) flash kernels; ``ssm`` the in-place recurrent state
    update ``ssm_state_update`` (``ssm.py``), whose fallback gathers the
    rows, updates them and scatters them back. The siblings count with
    them: ``flash`` the forward with a window (``dispatched_window_flash``),
    ``paged`` the decode kernel over a window's ring
    (``paged_decode_attn_window``), ``ssm`` the Mamba-1 update
    ``ssm_state_update_s6``; ``moe`` the dropless expert layer
    ``moe_expert_mlp_*`` (``moe_experts.py``), whose fallback runs every
    expert over every row; ``kv_write`` a decode step's token written
    into both pool halves in place, ``kv_token_write``
    (``kv_write.py``: once a trace of ``inference/paged.py``'s inner
    ``jit``, so once a process a shape), whose fallback is two scatters
    of a row a (slot, head). A serving cell asserts ``paged_fallback``,
    ``ssm_fallback``, ``moe_fallback``, ``kv_write_fallback`` and (where
    its prefill has a window) ``flash_fallback`` stay 0."""
    return dict(_DISPATCH_STATS)


def reset_dispatch_stats() -> None:
    for k in _DISPATCH_STATS:
        _DISPATCH_STATS[k] = 0


def _on_tpu() -> bool:
    # Backend-init errors propagate: a chip held by another process must
    # fail the run, not turn every kernel into its reference.
    return jax.default_backend() == "tpu"


def _make_flash_dispatch(interpret: bool):
    def dispatch(q, k, v, *, causal=False, scale=None):
        from ..nn.functional import attention as _att
        if not (interpret or _on_tpu()) or not _fa.supported(q, k, v):
            _DISPATCH_STATS["flash_fallback"] += 1
            return _att.sdpa_reference(q, k, v, causal=causal, scale=scale)
        _DISPATCH_STATS["flash"] += 1
        # shapes are static at trace time -> per-shape tuned block sizes
        # (measured once, cached to disk; defaults off-TPU)
        from . import autotune as _at
        bq, bk = _at.flash_blocks(q.shape, k.shape, q.dtype, causal)
        return _fa.flash_attention(q, k, v, causal=causal, scale=scale,
                                   block_q=bq, block_k=bk,
                                   interpret=interpret)
    return dispatch


def _make_rms_dispatch(interpret: bool):
    def dispatch(x, w, eps):
        out_dtype = jnp.result_type(x.dtype, w.dtype)
        if (not (interpret or _on_tpu())
                or w.ndim != 1 or w.shape[0] != x.shape[-1]):
            # XLA path (same math as nn.functional.norm.rms_norm body)
            _DISPATCH_STATS["rms_fallback"] += 1
            xf = x.astype(jnp.float32)
            r = jax.lax.rsqrt(
                jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
            return ((xf * r).astype(x.dtype) * w).astype(out_dtype)
        _DISPATCH_STATS["rms"] += 1
        return _rn.rms_norm(x, w, eps, _rn.DEFAULT_BLOCK_ROWS,
                            interpret).astype(out_dtype)
    return dispatch


def dispatched_fused_ce(x, head, labels, *, vocab_chunk=None,
                        reduction="mean", ignore_index=-100):
    """Blockwise CE with the same counter discipline as flash/rms: the
    trace records whether the memory-efficient path engaged, and an
    unsupported shape falls back to the materialising xent (identical
    math, including ignore_index masking and valid-count mean) instead
    of erroring. Works on every backend (it is pure jnp/lax, not
    pallas), so there is no backend gate.

    ``vocab_chunk=None`` (default) resolves through the autotune cache;
    an explicit int is ALWAYS respected verbatim — a user capping
    loss-path HBM must not be overridden by a throughput-tuned winner."""
    if _fce.supported(x, head, labels):
        _DISPATCH_STATS["fused_ce"] += 1
        if vocab_chunk is None:
            from . import autotune as _at

            n_tokens = 1
            for s in x.shape[:-1]:
                n_tokens *= int(s)
            vocab_chunk = _at.ce_chunk(n_tokens, int(x.shape[-1]),
                                       int(head.shape[0]), x.dtype)
        return _fce.fused_cross_entropy(
            x, head, labels, vocab_chunk=vocab_chunk, reduction=reduction,
            ignore_index=ignore_index)
    _DISPATCH_STATS["fused_ce_fallback"] += 1
    logits = jnp.einsum("...d,vd->...v", x, head,
                        preferred_element_type=jnp.float32)
    return _fce.masked_xent_from_logits(
        logits, labels, ignore_index=ignore_index, reduction=reduction)


def dispatched_segment_attention(q, k, v, seg_q, seg_k, pos_q, pos_k, *,
                                 causal=False, scale=None):
    """Segment-masked (sequence-packed) attention with the same counter
    discipline as flash/paged: the Pallas segment kernel on TPU when the
    shapes are supported (block sizes resolved through the autotune
    cache's ``varlen`` knob), the pure-jnp grouped-GQA reference
    elsewhere (tier-1's CPU path). Both share one masking definition —
    packed-vs-unpacked training parity holds on either path."""
    # default-block support check BEFORE tuning (the dense dispatcher's
    # order): a shape the kernel can never run must not pay a
    # varlen_blocks measurement sweep just to fall back
    if _on_tpu() and _fa.segments_supported(q, k):
        from . import autotune as _at
        bq, bk = _at.varlen_blocks(q.shape, k.shape, q.dtype, causal)
        if _fa.segments_supported(q, k, block_q=bq, block_k=bk):
            _DISPATCH_STATS["varlen"] += 1
            return _fa.flash_attention_segments(
                q, k, v, seg_q, seg_k, pos_q, pos_k, causal=causal,
                scale=scale, block_q=bq, block_k=bk, interpret=False)
    _DISPATCH_STATS["varlen_fallback"] += 1
    return _fa.segment_attention_ref(q, k, v, seg_q, seg_k, pos_q, pos_k,
                                     causal=causal, scale=scale)


def dispatched_paged_attention(q, k_pages, v_pages, block_tables, lengths,
                               *, scale=None, k_scales=None,
                               v_scales=None, layer=None):
    """Ragged paged decode attention with the same counter discipline as
    flash/rms: the pallas kernel on TPU when the shapes are supported,
    the pure-jnp gather reference elsewhere (tier-1's CPU path). Both
    share one masking/softmax definition — the serving engine's
    paged-vs-ring parity holds on either path.

    The kv-dtype arm (FLAGS_serving_kv_quant): int8 page pools arrive
    with per-page per-kv-head f32 ``k_scales``/``v_scales`` [P, kv]
    planes; both the kernel and the reference dequantize inline (page
    DMA stays int8, the scale folds into the attention dot), counted
    separately (``paged_quant[_fallback]``) so benchmarks can assert
    which arm a quantized shape actually traced.

    ``layer`` names one layer of pools (and scale planes) that carry a
    leading layer axis, as the serving program's do; None is one layer's
    pools alone. Either way both paths read the pool where it lies."""
    quant = k_scales is not None
    arm = "paged_quant" if quant else "paged"
    if _on_tpu() and _pa.supported(q, k_pages, block_tables,
                                   quant=quant):
        _DISPATCH_STATS[arm] += 1
        return _pa.ragged_paged_attention(
            q, k_pages, v_pages, block_tables, lengths, scale=scale,
            k_scales=k_scales, v_scales=v_scales, layer=layer,
            interpret=False)
    _DISPATCH_STATS[arm + "_fallback"] += 1
    return _pa.paged_attention_ref(
        q, k_pages, v_pages, block_tables, lengths, scale=scale,
        k_scales=k_scales, v_scales=v_scales, layer=layer)


def dispatched_ssm_update(state, layer, rows, decay, dtx, b, c):
    """One token a slot through a Mamba-2 layer's recurrence against the
    state ``[L, rows, H, N, P]`` (``kernels/ssm.py``), with the counter
    discipline of the others: the Pallas kernel ``ssm_state_update`` on a
    TPU where the shapes are supported (the state updated in place, its
    rows found through the row table), the gather / update / scatter in
    plain XLA elsewhere (tier-1's CPU path, a state that is not float32).
    Returns (state', y [B, H, P] float32)."""
    if _on_tpu() and _ssm.supported(state, dtx, b):
        _DISPATCH_STATS["ssm"] += 1
        return _ssm.ssm_state_update(state, layer, rows, decay, dtx, b, c,
                                     interpret=False)
    _DISPATCH_STATS["ssm_fallback"] += 1
    return _ssm.ssm_state_update_ref(state, layer, rows, decay, dtx, b, c)


def dispatched_s6_update(state, layer, rows, dt, dtx, a, b, c):
    """One token a slot through a Mamba-1 layer's recurrence against the
    state ``[L, rows, N, C]`` (``kernels/ssm.py``): the Pallas kernel
    ``ssm_state_update_s6`` on a TPU where the shapes are supported (the
    state updated in place), the gather / update / scatter in plain XLA
    elsewhere; counted as ``ssm`` / ``ssm_fallback``. Returns (state',
    y [B, C] float32)."""
    if _on_tpu() and _ssm.s6_supported(state, dt):
        _DISPATCH_STATS["ssm"] += 1
        return _ssm.ssm_state_update_s6(state, layer, rows, dt, dtx, a, b,
                                        c, interpret=False)
    _DISPATCH_STATS["ssm_fallback"] += 1
    return _ssm.ssm_state_update_s6_ref(state, layer, rows, dt, dtx, a, b, c)


def dispatched_s6_scan(x, dt, a, b, c):
    """Whole sequences through a Mamba-1 layer's recurrence (prefill): the
    Pallas scan ``s6_scan`` on a TPU where the shapes are supported, the
    token-by-token ``lax.scan`` elsewhere; counted as ``ssm`` /
    ``ssm_fallback``. Returns (y [G, S, C] float32, the last state)."""
    if _on_tpu() and _ssm.s6_scan_supported(x, a):
        _DISPATCH_STATS["ssm"] += 1
        return _ssm.s6_scan(x, dt, a, b, c, interpret=False)
    _DISPATCH_STATS["ssm_fallback"] += 1
    return _ssm.s6_scan_ref(x, dt, a, b, c)


def dispatched_expert_mlp(x, expert, gate, up, down, layer, *,
                          name="moe_expert_mlp"):
    """Each row of ``x`` [T, D] through its own expert's gated MLP, none
    dropped (``kernels/moe_experts.py``): the Pallas kernel (``name`` in a
    trace) on a TPU where the shapes are supported, reading only the
    experts some row picked; every expert over every row in plain XLA
    elsewhere (tier-1's CPU path). Counted as ``moe`` / ``moe_fallback``."""
    if _on_tpu() and _moe.supported(x, gate):
        _DISPATCH_STATS["moe"] += 1
        return _moe.expert_mlp(x, expert, gate, up, down, layer, name=name,
                               interpret=False)
    _DISPATCH_STATS["moe_fallback"] += 1
    return _moe.expert_mlp_ref(x, expert, gate, up, down, layer)


def dispatched_kv_token_write(pool_k, pool_v, layer, rows, off, k, v):
    """A decode step's new keys and values ``k``, ``v`` [B, kv, hd] into
    row ``off`` of pages ``rows`` of layer ``layer`` of both pool halves
    (``kernels/kv_write.py``): the Pallas kernel ``kv_token_write`` on a
    TPU where the pool is supported (both halves updated in place, a
    sublane tile a slot), the two scatters in plain XLA elsewhere
    (tier-1's CPU path, a page under a tile, a head off the lanes);
    counted as ``kv_write`` / ``kv_write_fallback``. Returns the two
    pools."""
    if (_INTERPRET or _on_tpu()) and _kw.supported(pool_k, k):
        _DISPATCH_STATS["kv_write"] += 1
        return _kw.kv_token_write(pool_k, pool_v, layer, rows, off, k, v,
                                  interpret=_INTERPRET)
    _DISPATCH_STATS["kv_write_fallback"] += 1
    return _kw.kv_token_write_ref(pool_k, pool_v, layer, rows, off, k, v)


def dispatched_ring_attention(q, ring_k, ring_v, layer, rows, lengths, *,
                              window, scale=None):
    """Decode attention over a window kept as a ring a sequence
    (``paged_attention.ring_window_attention``): the paged kernel's window
    form on a TPU, the gather reference with the same mask elsewhere;
    counted as ``paged`` / ``paged_fallback``."""
    tpu = _on_tpu() and _pa.supported(
        q, jax.ShapeDtypeStruct(ring_k.shape[2:], ring_k.dtype),
        jax.ShapeDtypeStruct((rows.shape[0], ring_k.shape[2]), jnp.int32))
    _DISPATCH_STATS["paged" if tpu else "paged_fallback"] += 1
    return _pa.ring_window_attention(q, ring_k, ring_v, layer, rows,
                                     lengths, window=window, scale=scale,
                                     ref=not tpu)


def dispatched_window_flash(q, k, v, *, window, scale=None):
    """Causal attention in which a query sees its own position and the
    ``window - 1`` before it, on [B, S, H, D], forward only: the flash
    forward with a window on a TPU where the shapes are supported, masked
    plain attention elsewhere; counted as ``flash`` / ``flash_fallback``."""
    if _on_tpu() and _fa.supported(q, k, v):
        _DISPATCH_STATS["flash"] += 1
        return _fa.flash_attention(q, k, v, causal=True, scale=scale,
                                   window=window, interpret=False)
    from ..nn.functional import attention as _att
    _DISPATCH_STATS["flash_fallback"] += 1
    d = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])[None, :] \
        + (k.shape[1] - q.shape[1])
    return _att.sdpa_reference(q, k, v, ((d >= 0) & (d < window))[None, None],
                               scale=scale)


def register(flash: bool = True, rms: bool = True, interpret: bool = False):
    """Install pallas kernels into the op-dispatch seams.

    The dispatchers check the backend at call time (never at import —
    multi-host jax.distributed.initialize and platform selection must be
    able to run first): on a TPU the kernels compile natively, anywhere
    else they fall back to the XLA math. ``interpret=True`` (tests) runs
    the flash and rms kernels, and the decode step's KV write, in pallas
    interpret mode on any backend."""
    from ..nn.functional import attention as _att
    from ..nn.functional import norm as _norm
    global _INTERPRET
    _INTERPRET = interpret
    if flash:
        _att.register_flash_impl(_make_flash_dispatch(interpret))
        # the segment (sequence-packed) dispatcher self-gates on the
        # backend + shape support, so one registration serves both modes
        _att.register_segment_impl(dispatched_segment_attention)
    if rms:
        _norm.register_rms_impl(_make_rms_dispatch(interpret))


def unregister():
    from ..nn.functional import attention as _att
    from ..nn.functional import norm as _norm
    global _INTERPRET
    _INTERPRET = False
    _att.register_flash_impl(None)
    _att.register_segment_impl(None)
    _norm.register_rms_impl(None)


def auto_register():
    """Called from package init. Installs the lazy TPU-gated dispatchers —
    no backend probe happens until the first attention/norm call."""
    register()
