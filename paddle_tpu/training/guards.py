"""In-graph anomaly guards — the device half of the train-loop
sentinel, shared by every model family.

``models/llama.py`` and ``models/moe.py`` compose these into their
``make_train_step(guard=...)``: :func:`step_health` is the ONE anomaly
definition (finite loss, finite global grad norm, token ids in range,
norm under the host-fed cap) and :func:`gated_update` is the
all-or-nothing ``lax.cond`` gate that leaves params/opt-state
byte-identical on an anomalous step. The host half (spike detector,
escalation ladder, watchdog) lives in :mod:`.sentinel`.

Kept free of sentinel/monitor imports on purpose: these trace into the
compiled step and depend only on jax.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["grad_global_norm", "resolve_guard", "step_health",
           "gated_update", "resolve_numerics", "tensor_stats",
           "grad_numerics", "NUMERIC_STATS"]


def grad_global_norm(grads):
    """Global L2 norm of a grads pytree, accumulated in float32 — the
    guarded train step's spike signal (one fused per-leaf reduction +
    a scalar sum; negligible next to fwd+bwd)."""
    leaves = jax.tree.leaves(grads)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def resolve_guard(guard: Optional[bool]) -> bool:
    """make_train_step's guard default: ``None`` reads
    ``FLAGS_enable_sentinel`` at build time (the one flag definition
    every model family shares), so flipping the flag and rebuilding the
    step is all a training script needs."""
    from ..core import flags as _flags
    return _flags.flag_value("enable_sentinel") if guard is None else guard


def resolve_numerics(numerics: Optional[bool]) -> bool:
    """make_train_step's numerics default: ``None`` reads
    ``FLAGS_enable_numerics`` at build time. The numerics block only
    exists on the GUARDED step — callers gate the resolved value on the
    resolved guard, so the off-flag guarded program stays byte-identical
    to the pre-numerics one."""
    from ..core import flags as _flags
    return _flags.flag_value("enable_numerics") if numerics is None \
        else numerics


# The per-tensor statistic names every numerics consumer (the host
# plane, the /numerics route, the parity tests) keys on — one contract.
NUMERIC_STATS = ("absmax", "rms", "mean", "zero_frac", "overflow_frac",
                 "underflow_frac", "gnorm_sq")


def _dtype_range(dtype):
    """(overflow threshold, underflow threshold) of a float dtype: a
    value within 2x of ``finfo.max`` is one optimizer scale-up from
    saturating (inf on the next cast), a nonzero value below
    ``finfo.tiny`` is already in the subnormal flush-to-zero band.
    Integer tensors have no float range; both thresholds disable."""
    dt = jnp.dtype(dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        return jnp.inf, 0.0
    fi = jnp.finfo(dt)
    return float(fi.max) / 2.0, float(fi.tiny)


def tensor_stats(x, reduce_axes=None):
    """The ONE fused per-tensor reduction of the numerics plane:
    {absmax, rms, mean, zero_frac, overflow_frac, underflow_frac,
    gnorm_sq} of ``x`` in float32, reduced over ``reduce_axes`` (None =
    all axes -> scalars; a tuple leaves the kept axes, e.g. axis 0 of a
    [L, ...] scan-stacked weight -> per-layer [L] rows). Overflow /
    underflow fractions are measured against ``x``'s OWN dtype range
    (see ``_dtype_range``) — the dynamic-range evidence quantization
    decisions need. All reductions read ``x`` once; XLA fuses them into
    a single pass."""
    over_t, under_t = _dtype_range(x.dtype)
    xf = x.astype(jnp.float32)
    ax = reduce_axes
    absx = jnp.abs(xf)
    n = jnp.asarray(x.size if ax is None
                    else np.prod([x.shape[a] for a in ax]), jnp.float32)
    sumsq = jnp.sum(xf * xf, axis=ax)
    return {
        "absmax": jnp.max(absx, axis=ax),
        "rms": jnp.sqrt(sumsq / n),
        "mean": jnp.sum(xf, axis=ax) / n,
        "zero_frac": jnp.sum((xf == 0.0).astype(jnp.float32),
                             axis=ax) / n,
        "overflow_frac": jnp.sum((absx > over_t).astype(jnp.float32),
                                 axis=ax) / n,
        "underflow_frac": jnp.sum(
            ((absx < under_t) & (xf != 0.0)).astype(jnp.float32),
            axis=ax) / n,
        "gnorm_sq": sumsq,
    }


def grad_numerics(grads):
    """Per-tensor numerics of a grads pytree — the in-graph summarizer
    the GUARDED train steps attach to their health aux output. Leaves
    under the top-level ``"layers"`` key are scan-stacked ``[L, ...]``
    weights: their stats keep axis 0, so every statistic (and the
    grad-norm breakdown ``gnorm_sq``) is PER LAYER. Every other leaf
    reduces to scalars. The squared norms tile the global norm exactly:
    ``sqrt(sum of all gnorm_sq entries) == grad_global_norm(grads)``
    (pinned by test) — this is the refinement that lets a spike name a
    layer instead of a scalar.

    Returns ``{"layers": {name: {stat: [L]}}, "tensors": {name: {stat:
    scalar}}}`` — small f32 arrays that ride to the host as aux
    outputs of the one compiled step (no extra dispatch, no sync beyond
    the health coercion the sentinel loop already does)."""
    out = {"layers": {}, "tensors": {}}
    for name, g in grads.items():
        if name == "layers":
            for lname, lg in g.items():
                out["layers"][lname] = tensor_stats(
                    lg, reduce_axes=tuple(range(1, lg.ndim)))
        else:
            out["tensors"][name] = tensor_stats(g)
    return out


@jax.named_scope("optim")
def step_health(loss, grads, inp, vocab_size: int, gnorm_cap):
    """(ok, health) of one guarded train step — the ONE anomaly
    definition shared by every family's guarded step. ``ok`` is True
    when the update may apply: finite loss, finite global grad norm,
    every input token id in [0, vocab) (a corrupt data pipeline would
    otherwise train on clip-gathered garbage SILENTLY), and grad norm
    under the host-fed ``gnorm_cap`` (the sentinel's EMA spike
    threshold; pass +inf to disable). ``health`` rides back to the host
    as two aux scalars: the applied flag and the grad norm the spike
    detector feeds on."""
    gnorm = grad_global_norm(grads)
    ids_ok = jnp.all((inp >= 0) & (inp < vocab_size))
    ok = jnp.isfinite(loss) & jnp.isfinite(gnorm) & ids_ok \
        & (gnorm <= gnorm_cap)
    return ok, {"finite": ok, "grad_norm": gnorm}


def gated_update(ok, update_fn, params, opt_state, grads):
    """Apply ``update_fn(params, opt_state, grads)`` only when ``ok`` —
    the all-or-nothing device gate: on an anomalous step the false
    branch returns params/opt-state byte-identical (same values through
    the cond; donation and GSPMD shardings are branch-invariant), so
    the host can keep training as if the batch never happened."""
    return lax.cond(
        ok, update_fn, lambda p, o, g: (p, o), params, opt_state, grads)
