"""Model-FLOPs-utilization accounting from XLA cost analysis.

The headline number of GSPMD-style scaling work (PAPERS.md: GSPMD) is
MFU: the fraction of the chip's peak FLOP/s the model actually
sustains. Two inputs:

- **Program FLOPs**: XLA's own ``cost_analysis()`` of the compiled
  program — the MEASURED flop count of one step, not the 6ND
  estimate (which misses remat recompute, attention, and fused-loss
  flops). On the TPU the analysis answers for none of the repo's
  programs, so the benchmark counts FLOPs from shapes (PERF.md
  section 3, ``prog.mfu.*``).
- **Peak FLOP/s**: a per-backend table (bf16 peak per chip by TPU
  generation), env-overridable with ``PADDLE_TPU_PEAK_FLOPS`` — which
  is also how the CPU smoke path gets a meaningful denominator.

Capture seams:

- ``jit/api.py`` calls :func:`record_program_flops` on every program-
  cache miss (monitor-gated), accumulating ``jit.program.flops`` so a
  snapshot shows the total analyzed FLOPs footprint of the process's
  compiled programs and ``jit.program.last_flops`` the newest one.

``lowered_flops`` costs one re-trace + lowering (NO XLA compile:
``jax.stages.Lowered.cost_analysis`` runs the HLO-level analyzer), so
the capture is pennies next to the compile it rides behind.
"""
from __future__ import annotations

import os
from typing import Optional

__all__ = ["peak_flops", "resolve_peak", "lowered_flops",
           "lowered_cost", "cost_analysis_flops", "cost_analysis_value",
           "record_program_flops", "mfu", "ones_cotangent"]

# bf16 peak FLOP/s per chip by TPU generation (public datasheet figures;
# v5p is the BASELINE.json north-star part).
PEAK_FLOPS_TABLE = {
    "v6e": 918e12,
    "v5p": 459e12,
    "v5e": 197e12,
    "v4": 275e12,
    "v3": 123e12,
}

# ``jax.Device.device_kind`` -> generation key of the peak tables (this
# one and the bandwidth tables in ``monitor/roofline.py``). Matched
# exactly: a v5e reports "TPU v5 lite", which no substring of the
# generation names finds, and a TPU kind missing here is an error, never
# another part's peaks. Only kinds read off a real chip are listed (the
# v5e, PR 21); the other generations' rows in the peak tables wait for
# the string their part reports.
DEVICE_KIND_GENERATION = {
    "TPU v5 lite": "v5e",
}

# Nominal denominator for CPU test runs with no override: keeps MFU
# finite and comparable across smoke runs without claiming to measure
# the host.
_CPU_NOMINAL = 1e12


def resolve_peak(env_name: str, table: dict, nominal: float,
                 device=None, scale: float = 1.0) -> dict:
    """The one peak-denominator resolver shared by the FLOPs table
    here and the bandwidth tables in ``monitor/roofline.py`` (two
    copies of the generation-matching rules would let FLOP and
    bandwidth denominators silently resolve to different generations
    for the same device). Order: env override (``scale`` applied — the
    CPU-test escape hatch) -> ``DEVICE_KIND_GENERATION[device_kind]``
    into the per-generation table -> ``nominal`` (already in absolute
    units) for a non-TPU device. A TPU whose ``device_kind`` is not in
    the table raises ``KeyError``. Returns ``{"value", "source",
    "generation"}`` so consumers can assert provenance (chip_smoke.py
    requires a table hit)."""
    env = os.environ.get(env_name)
    if env:
        try:
            v = float(env)
            if v > 0:
                return {"value": v * scale, "source": "env",
                        "generation": None}
        except ValueError:
            pass
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "") or ""
    gen = DEVICE_KIND_GENERATION.get(kind)
    if gen is not None:
        return {"value": table[gen] * scale, "source": "table",
                "generation": gen}
    if getattr(device, "platform", "") == "tpu" or "tpu" in kind.lower():
        raise KeyError(
            f"no peak-table entry for TPU device_kind {kind!r}: add it "
            f"to monitor.mfu.DEVICE_KIND_GENERATION with its published "
            f"peaks (known: {sorted(DEVICE_KIND_GENERATION)})")
    return {"value": nominal, "source": "nominal", "generation": None}


def peak_flops(device=None) -> float:
    """Peak FLOP/s for ``device`` (default: first jax device) —
    ``PADDLE_TPU_PEAK_FLOPS`` env override -> ``device_kind`` table
    (unknown TPU kinds raise) -> a 1e12 nominal for CPU hosts (see
    :func:`resolve_peak`)."""
    return resolve_peak("PADDLE_TPU_PEAK_FLOPS", PEAK_FLOPS_TABLE,
                        _CPU_NOMINAL, device)["value"]


def cost_analysis_value(cost, key: str) -> Optional[float]:
    """Pull a named property out of a jax cost-analysis result, which
    is a dict on current jax and a list of per-computation dicts on
    some versions. None when NO computation reports the key (a backend
    that omits it, or XLA's -1 "unknown" sentinel) — callers must not
    see a fabricated 0."""
    if cost is None:
        return None
    if isinstance(cost, (list, tuple)):
        vals = [cost_analysis_value(c, key) for c in cost]
        vals = [v for v in vals if v is not None]
        return float(sum(vals)) if vals else None
    try:
        v = cost.get(key)
    except AttributeError:
        return None
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    # XLA reports -1 for "unknown" on some backends; an answered 0
    # (a pure data-movement program) passes through — only a missing/
    # unknown read may look "unavailable"
    return f if f >= 0 else None


def cost_analysis_flops(cost) -> float:
    """0.0-defaulting flops read (legacy shape; ``lowered_cost`` is
    the hardened Optional-returning capture seam)."""
    return cost_analysis_value(cost, "flops") or 0.0


def _note_unavailable():
    from . import inc as _inc
    _inc("monitor.cost_analysis.unavailable",
         doc="cost_analysis() reads that raised or omitted the "
             "requested key (flops / bytes accessed)")


def lowered_cost(jitted_fn, *args, **kwargs) -> dict:
    """``{"flops": Optional[float], "bytes_accessed": Optional[float]}``
    of one invocation per XLA's HLO cost analysis. Re-traces and lowers
    (cheap, wrapped in ``monitor.suppress_accounting`` so trace-time
    counters don't see the internal re-trace) but does NOT compile.

    Hardened for the jit cache-miss seam: a backend whose
    ``cost_analysis()`` raises or omits keys yields ``None`` fields and
    bumps ``monitor.cost_analysis.unavailable`` — a KeyError here must
    never take down the compile it rides behind."""
    from . import suppress_accounting as _suppress
    try:
        with _suppress():
            lowered = jitted_fn.lower(*args, **kwargs)
            cost = lowered.cost_analysis()
    except Exception:
        _note_unavailable()
        return {"flops": None, "bytes_accessed": None}
    out = {"flops": cost_analysis_value(cost, "flops"),
           "bytes_accessed": cost_analysis_value(cost, "bytes accessed")}
    if out["flops"] is None or out["bytes_accessed"] is None:
        _note_unavailable()
    return out


def lowered_flops(jitted_fn, *args, **kwargs) -> Optional[float]:
    """FLOPs of one invocation of ``jitted_fn(*args, **kwargs)`` per
    XLA's HLO cost analysis. None when the backend/analysis can't say
    (counted under ``monitor.cost_analysis.unavailable``)."""
    return lowered_cost(jitted_fn, *args, **kwargs)["flops"]


def ones_cotangent(x):
    """Cotangent seed for a full fwd+bwd FLOPs lowering (jit/api.py
    lowers forward-plus-vjp so training programs record the FLOPs they
    actually execute): ones for inexact outputs, float0 zeros for
    integer/bool outputs — the only cotangent dtype jax.vjp accepts
    for non-differentiable leaves."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jnp.issubdtype(jnp.result_type(x), jnp.inexact):
        return jnp.ones_like(x)
    return np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)


def record_program_flops(flops: Optional[float], source: str = "jit"):
    """Accumulate an analyzed program's FLOPs into the registry
    (``jit.program.flops`` counter + ``jit.program.last_flops`` gauge).
    Callers gate on ``monitor.enabled()``; None (analysis unavailable)
    records nothing."""
    if not flops or flops <= 0:
        return
    from . import inc as _inc
    from . import set_gauge as _set_gauge
    _inc("jit.program.flops", int(flops),
         doc="total XLA-cost-analysis FLOPs of compiled programs "
             "(one invocation each), accumulated per cache miss")
    _set_gauge("jit.program.last_flops", int(flops),
               doc="XLA-cost-analysis FLOPs of the most recently "
                   "compiled program")


def mfu(flops_per_step: float, steps_per_sec: float,
        device=None, peak: Optional[float] = None) -> float:
    """Model FLOPs utilization: achieved FLOP/s over peak FLOP/s."""
    p = peak if peak is not None else peak_flops(device)
    if p <= 0 or flops_per_step <= 0 or steps_per_sec <= 0:
        return 0.0
    return flops_per_step * steps_per_sec / p
