"""The benchmark's one command.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds the cell in ``BENCHMARK.json``, its configuration, traffic mix
and per-layer metrics in the data files beside this one, makes weights
and inputs from ``--seed``, checks the program against the plain
reference, warms up every shape, measures for ``--seconds`` and prints
one JSON object as the last line of its output: with ``--trace 0`` the
cell's end-to-end metrics (profiler and monitor off), with ``--trace 1``
its per-layer metrics and the breakdown of a traced slice. Its last key,
``compared``, holds each number ``correct`` was decided by beside its
limit; the same are the last lines on standard error.

Off the chip it exits non-zero and prints no result. ``--rehearse`` runs
the same code at the tiny sizes of each file's ``rehearse`` block on the
CPU, to find wrong paths before a chip call: its metrics carry the
prefix ``rehearse.`` and are never device metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from benchmark.harness import session
from benchmark.harness.session import T_PROCESS, say


class Run:
    """One run's context, handed to the driver."""

    def __init__(self, args, manifest, t_device: float):
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.rehearse = args.rehearse
        self.manifest = manifest
        self.cell = manifest.cell(args.workload)
        self.conf = manifest.config(self.cell["config"])
        # the configuration's own reference, counts, cache calls, kernel work
        self.arch = manifest.architecture(self.conf)
        self.mix = manifest.traffic(self.cell["traffic"])
        if self.rehearse:
            self.conf = rehearsal_of(self.conf)
            self.mix = {**self.mix, **self.mix.get("rehearse", {})}
        self.tracer = session.Tracer(
            bool(args.trace) and not self.rehearse,   # no profiler on the CPU
            self.mix["trace"], self.seconds, self.cell["name"])
        self.compiles = session.CompileCount()
        # Set-up is counted from the instant JAX has the device, before
        # the program is imported. What comes before it is the interpreter
        # and the TPU runtime's own bring-up, which nothing in this repo
        # can move and which drifts by whole sets of runs on the v5e's
        # host (medians of 11.8 s and 15.2 s in two sets of five on one
        # tree, PERF.md, PR 23): counted in, it alone made one set's
        # set-up 16% worse than the other's, over the bound. It is printed
        # as runtime_s on an earlier line.
        self.t_device = t_device
        self.phases, self._last = {}, t_device
        self.setup_s = None

    def mark(self, phase: str) -> None:
        now = time.perf_counter()
        self.phases[phase] = round(now - self._last, 3)
        self._last = now

    def open_window(self) -> None:
        """Set-up ends here, at the window's first instant."""
        self.mark("to_window")
        self.setup_s = time.perf_counter() - self.t_device
        self.compiles_at_open = self.compiles.snapshot()


def rehearsal_of(conf: dict) -> dict:
    r = conf["rehearse"]
    out = {**conf, **r["override"]}
    for block in ("serve", "train"):
        if block in r:
            out[block] = r[block]
    if "derived" in r:
        out["build"] = {**conf["build"], "derived": r["derived"]}
    return out


def device_block(devices, trace) -> dict:
    stats = [d.memory_stats() or {} for d in devices]
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": max(
               (s.get("peak_bytes_in_use", 0) for s in stats), default=0)}
    if trace is not None:
        out["busy_s"], out["window_s"] = trace.busy_s(), trace.window_s
    return out


def layer_metrics(run: Run, result: dict, trace, devices) -> dict:
    from benchmark.harness import work
    from benchmark.harness.manifest import plugin

    ctx = {"counters": result["counters"], "trace": trace,
           "config": run.conf, "mix": run.mix, "architecture": run.arch,
           "peaks": None if run.rehearse
           else work.peaks(devices[0].device_kind)}
    out = {}
    for m in run.manifest.metrics_of(run.cell["name"], "per_layer"):
        spec = run.manifest.layer_metric(m["name"])
        if run.rehearse and spec["reader"] in ("trace_ops", "trace_idle"):
            continue
        value = plugin("readers", spec["reader"]).read(spec["params"], ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--dump-trace", default=None, metavar="DIR",
                    help="with --trace 1: also write what the trace holds "
                         "(planes, lines, names) and its reduced events "
                         "there, to look at by hand")
    args = ap.parse_args(argv)

    session.prepare_environment(args.rehearse)
    from benchmark.harness.manifest import ROOT, Manifest, plugin

    manifest = Manifest(args.root or ROOT)
    cell = manifest.cell(args.workload)
    # (a rehearsal keeps no cache: CPU programs are quick to build)
    cache = None if args.rehearse else session.enable_compile_cache()
    devices = session.require_device(cell["chips"], args.rehearse)
    t_device = time.perf_counter()
    import paddle_tpu  # noqa: F401  (the system under test; absent, no run)

    run = Run(args, manifest, t_device)
    say(f"cell {cell['name']}: config {cell['config']} (architecture "
        f"{run.conf['architecture']}: {run.arch.__name__}), traffic "
        f"{cell['traffic']} ({run.mix['kind']}), seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}; compile cache {cache}"
        + ("; REHEARSAL on the CPU, no device metric" if args.rehearse
           else ""))
    run.mark("import")

    result = plugin("drivers", run.mix["kind"]).run(run)

    built, hits = run.compiles.snapshot()
    b0, h0 = run.compiles_at_open
    say(f"runtime_s {t_device - T_PROCESS:.2f} (process start to JAX having "
        f"the device; not in setup_s)")
    say(f"set-up {run.setup_s:.2f} s by phase {run.phases}; programs "
        f"compiled in set-up {b0}, taken from the cache {h0}; INSIDE THE "
        f"WINDOW: compiled {built - b0}, from the cache {hits - h0}")
    say(f"counters: {result['counters']}")
    from paddle_tpu.kernels import autotune

    say(f"kernel blocks as the program chose them (autotune, cached mode): "
        f"{json.dumps(autotune.used_blocks())}")
    say(f"info: {json.dumps(result['info'], default=str)}")

    trace = None
    if args.trace and not args.rehearse:
        from benchmark.harness.trace_reduce import Trace, load_xplane

        path = run.tracer.xplane()
        if path is None:
            sys.exit("benchmark: --trace 1 and the profiler wrote no trace")
        trace = Trace(load_xplane(path))
        if args.dump_trace:
            from benchmark.harness.trace_reduce import dump

            dump(path, trace, args.dump_trace, cell["name"])
        say(f"trace: {len(trace.events)} events kept from {path}; window "
            f"{trace.window_s:.3f} s, busy {trace.busy_s():.3f} s")

    prefix = "rehearse." if args.rehearse else ""
    if args.trace:
        metrics = layer_metrics(run, result, trace, devices)
    else:
        values = {**result["end_to_end"], "setup_s": run.setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in manifest.metrics_of(cell["name"], "end_to_end")}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": {prefix + k: v for k, v in metrics.items()},
            "device": device_block(devices, trace)}
    if trace is not None:
        line["breakdown"] = trace.breakdown()
    if args.rehearse:
        line["rehearsal"] = True
    # each number ``correct`` compared, beside its limit: the result's
    # last key, and the last lines on standard error
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in result["compared"].items()}
    for k, c in line["compared"].items():
        print(f"compared {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
