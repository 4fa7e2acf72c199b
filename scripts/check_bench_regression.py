#!/usr/bin/env python
"""Bench-trajectory regression guard.

The repo checks in one ``BENCH_r<NN>.json`` per round (the driver's
end-of-round capture: a dict with the bench stdout in ``tail`` /
``parsed``), but nothing ever READ the trajectory — a PR could halve
decode throughput and tier-1 would stay green. This script is the
guard:

1. parse every ``BENCH_r*.json`` in round order, extracting the
   allowlisted rungs (headline tokens/s plus the named sub-rungs the
   bench embeds under ``extra`` — MoE, decode, serving, packing,
   trace replay);
   runs that failed (``value`` <= 0 or an ``error`` field) are
   SKIPPED, not treated as zeros;
2. the NEWEST successful run is the candidate; each rung's baseline is
   the best of (a) every EARLIER successful run's value and (b) a
   numeric entry in ``BASELINE.json``'s ``published`` map, when one
   exists;
3. fail (exit 1) when a candidate rung undercuts its baseline by more
   than the noise tolerance (default 15% — container/bench spread is
   ~10% per ROADMAP.md).

All rungs are higher-is-better by construction of the allowlist; a
rung missing from the newest run (bench evolved) is reported but not a
failure, and with fewer than one successful prior run the guard
passes trivially — it engages as the trajectory grows. Runs from
tier-1 (tests/test_operator_plane.py) on the checked-in files and
standalone::

    python scripts/check_bench_regression.py [--tolerance 0.15] [-v]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# rung name -> dotted path into the parsed headline record. Only rungs
# listed here are guarded (all higher-is-better); new bench rungs are
# opted in deliberately, not guarded by accident.
ALLOWLIST = {
    "llama_train_tokens_per_sec_per_chip": "value",
    "moe_train_tokens_per_sec": "extra.moe.tokens_per_sec",
    "decode_tokens_per_sec": "extra.decode.decode_tokens_per_sec",
    "int8_decode_tokens_per_sec": "extra.decode.int8_decode_tokens_per_sec",
    "prefill_tokens_per_sec": "extra.decode.prefill_tokens_per_sec",
    "int4_decode_tokens_per_sec": "extra.decode.int4_decode_tokens_per_sec",
    "serving_tokens_per_sec": "extra.serving_paged.serving_tokens_per_sec",
    # quantized memory plane (FLAGS_serving_kv_quant): the serving rung's
    # int8-pool arm must keep pace with its own trajectory
    "serving_kv_quant_tokens_per_sec":
        "extra.serving_paged.kv_quant.tokens_per_sec",
    "packed_tokens_per_sec": "extra.training_packed.packed_tokens_per_sec",
    # trace-replay goodput (loadgen harness): useful decode tokens per
    # wall second across the seeded overload trace — a PR that sheds
    # more work or slows the engine under burst load fails here
    "serving_replay_goodput_tokens_per_sec":
        "extra.serving_trace_replay.goodput_tokens_per_sec",
}

# LOWER-is-better rungs (measured exec-ms distributions from the
# performance plane's extra.metrics.exec block). Guarded separately:
# the floor is the BEST (minimum) prior value and a candidate fails by
# EXCEEDING it beyond tolerance. Absence on old BENCH_r*.json files
# (the block predates them) simply contributes no floor — skipped,
# never zero-floored.
ALLOWLIST_LOWER = {
    "headline_exec_ms_p50": "extra.metrics.exec.headline.p50_ms",
    "decode_exec_ms_p50": "extra.metrics.exec.decode.p50_ms",
    # serving SLO p99s (extra.metrics.slo, fed by the serving rung's
    # post-warmup latency histograms): a PR that regresses tail
    # latency without touching throughput now fails the guard
    "serving_ttft_ms_p99": "extra.metrics.slo.ttft_p99_ms",
    "serving_tpot_ms_p99": "extra.metrics.slo.tpot_p99_ms",
    # trace-replay p99 TTFT (per-request cost samples of the replay's
    # completed requests, via the scorecard's timing plane)
    "serving_replay_ttft_ms_p99":
        "extra.serving_trace_replay.ttft_p99_ms",
    # failover-on kill replay: p99 strand -> survivor-terminal wall
    # seconds (the exactly-once layer's recovery tail)
    "serving_failover_recovery_s_p99":
        "extra.serving_failover_replay.recovery_s_p99",
    # shared-prefix replay (radix KV cache on, pinned prefix trace):
    # completed-request p50 TTFT and the deterministic prefill-FLOPs-
    # per-request proxy (2·N_params·tokens_prefilled/completed) — a PR
    # that erodes the prefix cache's prefill skipping fails here even
    # if throughput elsewhere holds
    "serving_prefix_ttft_ms_p50":
        "extra.serving_prefix_replay.ttft_p50_ms",
    "serving_prefix_prefill_flops_per_request":
        "extra.serving_prefix_replay.prefill_flops_per_request",
}

# must-be-ZERO invariants, checked on the NEWEST successful run only
# (there is no trajectory to compare — the value is a contract, not a
# measurement). Absence is a skip (the rung didn't run); any positive
# value is a regression. The failover replay's `lost` count is the
# whole point of the durability layer: with FLAGS_serving_failover on,
# a scripted kill must strand work into recovery, never into `lost`.
ALLOWLIST_ZERO = {
    "serving_failover_lost": "extra.serving_failover_replay.lost",
}

# static MINIMUM floors, checked on the NEWEST successful run only —
# like ALLOWLIST_ZERO these are contracts, not trajectories: the value
# must meet the named floor outright (no tolerance — the floor already
# leaves headroom below the theoretical value). Absence is a skip.
# The kv-quant concurrency ratio is pure pool arithmetic (f32 pools are
# ~4x int8+scales, bf16 ~2x), so 1.8x holds on every backend the bench
# runs on.
ALLOWLIST_MIN = {
    "serving_kv_quant_concurrency_at_fixed_pool_bytes": (
        "extra.serving_paged.kv_quant"
        ".servable_concurrency_at_fixed_pool_bytes", 1.8),
}

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def _dig(record: dict, path: str):
    cur = record
    for seg in path.split("."):
        if not isinstance(cur, dict) or seg not in cur:
            return None
        cur = cur[seg]
    return cur if isinstance(cur, (int, float)) else None


def _headline_record(blob: dict):
    """The headline bench JSON line of one BENCH_r file: ``parsed``
    when the driver stored it, else the first parseable ``{"metric":
    ...}`` line of ``tail``."""
    parsed = blob.get("parsed")
    if isinstance(parsed, dict) and "metric" in parsed:
        return parsed
    for line in (blob.get("tail") or "").splitlines():
        line = line.strip()
        if line.startswith("{") and '"metric"' in line:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and "metric" in rec:
                return rec
    return None


def extract_rungs(blob: dict, allowlist=None):
    """{rung: value} for one BENCH_r blob, or None when the run failed
    (no headline, an error field, or a non-positive headline value)."""
    allowlist = allowlist if allowlist is not None else ALLOWLIST
    rec = _headline_record(blob)
    if rec is None or rec.get("error"):
        return None
    headline = rec.get("value")
    if not isinstance(headline, (int, float)) or headline <= 0:
        return None
    out = {}
    for rung, path in allowlist.items():
        v = _dig(rec, path)
        if v is not None and v > 0:
            out[rung] = float(v)
    return out or None


def load_trajectory(root=REPO, allowlist=None):
    """[(round_number, {rung: value})] for every successful checked-in
    run, round-ascending. Self-measured / eager files are excluded by
    the BENCH_r<NN>.json pattern."""
    out = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = _ROUND_RE.search(os.path.basename(path))
        if not m:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                blob = json.load(f)
        except (OSError, ValueError):
            continue
        rungs = extract_rungs(blob, allowlist)
        if rungs:
            out.append((int(m.group(1)), rungs))
    out.sort()
    return out


def published_baselines(root=REPO, allowlist=None):
    """Numeric entries of BASELINE.json's ``published`` map that name
    an allowlisted rung (the map is empty today; the hook exists so a
    hand-published number becomes part of the floor)."""
    allowlist = allowlist if allowlist is not None else ALLOWLIST
    try:
        with open(os.path.join(root, "BASELINE.json"),
                  encoding="utf-8") as f:
            base = json.load(f)
    except (OSError, ValueError):
        return {}
    pub = base.get("published") or {}
    return {k: float(v) for k, v in pub.items()
            if k in allowlist and isinstance(v, (int, float)) and v > 0}


def _newest_record(root=REPO):
    """(round, headline_record) of the NEWEST successful run, or
    (None, None)."""
    best = None
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = _ROUND_RE.search(os.path.basename(path))
        if not m:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                blob = json.load(f)
        except (OSError, ValueError):
            continue
        rec = _headline_record(blob)
        if rec is None or rec.get("error"):
            continue
        v = rec.get("value")
        if not isinstance(v, (int, float)) or v <= 0:
            continue
        rnd = int(m.group(1))
        if best is None or rnd > best[0]:
            best = (rnd, rec)
    return best if best is not None else (None, None)


def newest_zero_rungs(root=REPO):
    """(round, {rung: value}) of the ALLOWLIST_ZERO paths on the
    NEWEST successful run — zeros KEPT, unlike :func:`extract_rungs`
    (this check exists precisely to tell 0 from >0)."""
    rnd, rec = _newest_record(root)
    if rec is None:
        return None, {}
    out = {}
    for rung, p in ALLOWLIST_ZERO.items():
        v = _dig(rec, p)
        if v is not None:
            out[rung] = float(v)
    return rnd, out


def newest_min_rungs(root=REPO):
    """(round, {rung: (value, floor)}) of the ALLOWLIST_MIN paths on
    the NEWEST successful run."""
    rnd, rec = _newest_record(root)
    if rec is None:
        return None, {}
    out = {}
    for rung, (p, floor) in ALLOWLIST_MIN.items():
        v = _dig(rec, p)
        if v is not None:
            out[rung] = (float(v), float(floor))
    return rnd, out


def check(root=REPO, tolerance=0.15, allowlist=None, verbose=False):
    """Returns (ok, report_lines)."""
    traj = load_trajectory(root, allowlist)
    lines = []
    if not traj:
        lines.append("bench guard: no successful BENCH_r*.json run yet "
                     "— nothing to guard (pass)")
        return True, lines
    newest_round, newest = traj[-1]
    prior = traj[:-1]
    floors: dict = dict(published_baselines(root, allowlist))
    for _, rungs in prior:
        for rung, v in rungs.items():
            floors[rung] = max(floors.get(rung, 0.0), v)
    # lower-is-better rungs (measured exec ms): best prior = MINIMUM.
    # Runs predating the exec block contribute nothing here — their
    # absence is a skip, never a 0 ceiling that every candidate would
    # "exceed".
    lower_allow = ALLOWLIST_LOWER if allowlist is None else {}
    traj_lower = load_trajectory(root, lower_allow) if lower_allow \
        else []
    lower_by_round = dict(traj_lower)
    newest_lower = lower_by_round.get(newest_round, {})
    ceilings: dict = dict(published_baselines(root, lower_allow))
    for rnd, rungs in traj_lower:
        if rnd == newest_round:
            continue
        for rung, v in rungs.items():
            prev = ceilings.get(rung)
            ceilings[rung] = v if prev is None else min(prev, v)
    # must-be-zero invariants ride the NEWEST run alone — no baseline
    # needed, so they apply even on the first successful run
    zero_ok = True
    zero_lines = []
    if allowlist is None:
        _, zvals = newest_zero_rungs(root)
        for rung, v in sorted(zvals.items()):
            if v > 0:
                zero_ok = False
                zero_lines.append(
                    f"  ✗ {rung}: {v:g} — must-be-zero invariant "
                    "violated: REGRESSION")
            elif verbose:
                zero_lines.append(
                    f"  ✓ {rung}: 0 (invariant holds)")
        # static minimum floors: same newest-run-only discipline
        _, mvals = newest_min_rungs(root)
        for rung, (v, floor) in sorted(mvals.items()):
            if v < floor:
                zero_ok = False
                zero_lines.append(
                    f"  ✗ {rung}: {v:g} undercuts the static floor "
                    f"{floor:g}: REGRESSION")
            elif verbose:
                zero_lines.append(
                    f"  ✓ {rung}: {v:g} >= static floor {floor:g}")
    if not floors and not ceilings:
        lines.append(f"bench guard: r{newest_round:02d} is the first "
                     "successful run — baseline established, nothing "
                     "to compare"
                     f"{' (pass)' if zero_ok else ''}")
        lines.extend(zero_lines)
        return zero_ok, lines
    ok = True
    for rung, floor in sorted(floors.items()):
        v = newest.get(rung)
        if v is None:
            lines.append(f"  ~ {rung}: absent from r{newest_round:02d} "
                         f"(baseline {floor:.2f}) — not a failure")
            continue
        limit = floor * (1.0 - tolerance)
        ratio = v / floor
        if v < limit:
            ok = False
            lines.append(
                f"  ✗ {rung}: {v:.2f} is {ratio:.3f}x of baseline "
                f"{floor:.2f} — below the {1 - tolerance:.2f}x noise "
                "floor: REGRESSION")
        elif verbose:
            lines.append(f"  ✓ {rung}: {v:.2f} vs baseline {floor:.2f} "
                         f"({ratio:.3f}x)")
    for rung, ceiling in sorted(ceilings.items()):
        v = newest_lower.get(rung)
        if v is None:
            lines.append(f"  ~ {rung}: absent from r{newest_round:02d} "
                         f"(baseline {ceiling:.2f} ms) — not a failure")
            continue
        limit = ceiling * (1.0 + tolerance)
        ratio = v / ceiling
        if v > limit:
            ok = False
            lines.append(
                f"  ✗ {rung}: {v:.2f} ms is {ratio:.3f}x of baseline "
                f"{ceiling:.2f} ms — above the {1 + tolerance:.2f}x "
                "noise ceiling (lower is better): REGRESSION")
        elif verbose:
            lines.append(f"  ✓ {rung}: {v:.2f} ms vs baseline "
                         f"{ceiling:.2f} ms ({ratio:.3f}x, lower is "
                         "better)")
    lines.extend(zero_lines)
    ok = ok and zero_ok
    lines.insert(0, f"bench guard: r{newest_round:02d} vs "
                    f"{len(prior)} prior run(s) + published floors, "
                    f"tolerance {tolerance:.0%}: "
                    f"{'ok' if ok else 'REGRESSION'}")
    return ok, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=REPO)
    ap.add_argument("--tolerance", type=float, default=0.15,
                    help="allowed fractional shortfall vs baseline "
                         "(default 0.15)")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)
    ok, lines = check(args.root, args.tolerance, verbose=args.verbose)
    print("\n".join(lines))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
