"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # one process, a (1, 2, 2) mesh

Drives the main path once, through the entry points a user calls, at the
published widths of Llama-3-8B (hidden 4096, 32 query / 8 KV heads of
128, FFN 14336, vocabulary 128,256, rope theta 500k), cut in depth only:

1. a trainer — ``init_params`` + ``adamw_init`` in one jit, the donated
   ``make_train_step`` with the fused cross-entropy — takes a few steps
   on one repeated batch: the loss starts where a random init's must
   (ln(vocabulary) plus half the logit variance), stays finite and falls;
2. a ``ServingEngine`` answers requests of mixed prompt lengths through
   ``submit``/``step``/``run``: each ends ``completed`` with the token
   count it asked for, and the decode attention the engine dispatches is
   checked against ``kernels.paged_attention_ref`` on the engine's own
   page pool in the middle of the run.

It then asserts, from ``kernels.dispatch_stats()``, that the Pallas
kernels — not their references — were traced into those programs, and
that the device's peaks resolve from the table entry of its
``device_kind``. There is no off-chip mode: without a TPU it exits
non-zero and prints no result, and any phase that raises ends the run
with its traceback. The seconds and bytes it prints are set-up
information about this run, not performance records.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu import kernels
from paddle_tpu.core.compile_cache import enable_compile_cache
from paddle_tpu.inference import Request, ServingEngine
from paddle_tpu.kernels import autotune
from paddle_tpu.models import llama as L
from paddle_tpu.monitor import roofline

# Depth cuts for a 16 GB chip (widths are never cut). Trainer: parameters,
# gradients and bf16 AdamW moments are 8 bytes a parameter; embedding plus
# head are 1.05 B parameters and a layer 0.218 B, and the fused CE pads a
# second ~1 GB copy of the head (128,256 is a multiple of no chunk size).
# Server: weights only, 2 bytes a parameter, beside a small page pool.
TRAIN_LAYERS = 2
SERVE_LAYERS = 4
# bf16 attention against the f32 reference on the same pool: the kernel
# rounds the probabilities to bf16 before the PV product, the reference
# does not — two hundredths of the largest output magnitude cover it.
ATTN_TOL = 2e-2
# one-chip loss (fused CE, whole-batch kernel) against the mesh loss
# (vocab-parallel einsum CE, per-shard kernel): bf16 reassociation only
MESH_LOSS_TOL = 2e-2


def say(msg: str) -> None:
    print(msg, flush=True)


def memory_line(devices) -> str:
    stats = [d.memory_stats() or {} for d in devices]
    if not any(stats):
        return "memory_stats: not reported by this backend"
    return "  ".join(
        f"dev{d.id}: in_use={s.get('bytes_in_use', 0) / 2**30:.2f}GiB "
        f"peak={s.get('peak_bytes_in_use', 0) / 2**30:.2f}GiB"
        for d, s in zip(devices, stats))


def stats_delta(before: dict) -> dict:
    """Non-zero dispatch counters since ``before``."""
    after = kernels.dispatch_stats()
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


# ---------------------------------------------------------------------------
# phase 1: trainer
# ---------------------------------------------------------------------------

def train_phase(cfg, *, batch: int, seq: int, steps: int = 3,
                mesh: Mesh | None = None) -> dict:
    """A few donated AdamW steps on one repeated batch, on one device or
    (``mesh``) sharded dp/fsdp/tp with sequence parallelism. Raises unless
    the loss starts where a random init's must, stays finite and falls.
    Returns the losses, the dispatch counters its traces moved and its
    timings."""
    before = kernels.dispatch_stats()
    devices = list(mesh.devices.flat) if mesh is not None \
        else jax.devices()[:1]

    @jax.jit
    def init():
        p = L.init_params(cfg, jax.random.PRNGKey(0))
        return p, L.adamw_init(p, moment_dtype=jnp.bfloat16)

    params, opt_state = init()
    if mesh is not None:
        # parameters and both moments share one tree shape and placement
        params, m, v = (L.shard_params(t, cfg, mesh) for t in
                        (params, opt_state["m"], opt_state["v"]))
        opt_state = dict(opt_state, m=m, v=v)
        for tree in (m, v, params):
            placement = check_placement(tree, cfg, mesh)
    step = L.make_train_step(cfg, mesh, sp=mesh is not None, guard=False)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq + 1)), jnp.int32)

    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, ids)
        jax.block_until_ready((params, opt_state, loss))
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))

    # a random init's loss: init_params draws every weight from
    # normal(0, 0.02), so the logits of a unit-RMS hidden state are
    # normal(0, var) with var = hidden * 0.02^2, and the expected cross
    # entropy is ln(V) + var / 2
    expect = math.log(cfg.vocab_size) + cfg.hidden_size * 0.02 ** 2 / 2
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"trainer: non-finite loss {losses}")
    if abs(losses[0] - expect) > 0.25:
        raise AssertionError(
            f"trainer: first loss {losses[0]:.4f} is not near ln(V) + "
            f"var/2 = {expect:.4f} for a random init")
    if steps > 1 and not losses[-1] < losses[0]:
        raise AssertionError(f"trainer: loss did not fall: {losses}")

    out = {"losses": losses, "dispatch": stats_delta(before),
           "first_step_s": times[0], "step_s": times[1:],
           "memory": memory_line(devices)}
    if mesh is not None:
        out["placement"] = placement
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        if None not in in_use and max(in_use) > 2 * min(in_use):
            raise AssertionError(
                f"mesh trainer: memory piled up on one device: {in_use}")
    return out


def check_placement(tree, cfg, mesh) -> str:
    """Every leaf of a parameter-shaped tree has its shards on all of the
    mesh's devices, each holding the bytes its PartitionSpec implies."""
    specs = L.param_specs(cfg)
    n_dev = mesh.devices.size
    leaves = jax.tree.leaves_with_path(tree)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    sharded = 0
    for (path, leaf), spec in zip(leaves, spec_leaves):
        ways = 1
        for axis in spec:
            for name in (axis if isinstance(axis, tuple) else (axis,)):
                if name is not None:
                    ways *= mesh.shape[name]
        shards = leaf.addressable_shards
        where = {s.device for s in shards}
        sizes = {s.data.nbytes for s in shards}
        if len(where) != n_dev or sizes != {leaf.nbytes // ways}:
            raise AssertionError(
                f"{jax.tree_util.keystr(path)}: spec {spec} implies "
                f"{leaf.nbytes // ways} bytes on each of {n_dev} devices, "
                f"found {sorted(sizes)} on {len(where)}")
        sharded += ways > 1
    return (f"{len(leaves)} leaves on {n_dev} devices, {sharded} sharded, "
            f"per-device bytes as their specs imply")


# ---------------------------------------------------------------------------
# phase 2: server
# ---------------------------------------------------------------------------

def serve_phase(cfg, *, prompt_lens, new_tokens, num_slots: int,
                page_size: int) -> dict:
    """Serve ``len(prompt_lens)`` greedy requests cold (compiling), check
    the dispatched decode attention against the reference mid-run, then
    serve the same traffic again warm. Raises unless every request ends
    ``completed`` with the token count it asked for."""
    before = kernels.dispatch_stats()
    params = jax.jit(lambda: L.init_params(cfg, jax.random.PRNGKey(1)))()
    jax.block_until_ready(params)
    eng = ServingEngine(L, params, cfg, num_slots=num_slots,
                        max_len=max(prompt_lens) + max(new_tokens),
                        page_size=page_size)
    rng = np.random.default_rng(1)

    def requests(base_rid):
        return [Request(rid=base_rid + i, max_new_tokens=g,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            (p,)).astype(np.int32))
                for i, (p, g) in enumerate(zip(prompt_lens, new_tokens))]

    def check(outs, reqs):
        for r in reqs:
            o = outs[r.rid]
            if (o.finish_reason != "completed"
                    or len(o.tokens) != r.max_new_tokens):
                raise AssertionError(
                    f"request {r.rid}: {o.finish_reason} with "
                    f"{len(o.tokens)} of {r.max_new_tokens} tokens")
            t = np.asarray(o.tokens)
            if t.min() < 0 or t.max() >= cfg.vocab_size:
                raise AssertionError(f"request {r.rid}: token out of range")

    # -- cold pass: the scheduler loop of ServingEngine.run, opened up so
    # that each newly compiled prefill program can be attributed its
    # kernel counters and the attention checked between two steps
    cold = requests(0)
    t0 = time.perf_counter()
    for r in cold:
        eng.submit(r)
    prefill_dispatch, programs, attn_err, probe = {}, set(), None, {}
    mark = kernels.dispatch_stats()
    while eng.step():
        fresh = set(eng._prefill_fns) - programs
        if fresh:
            programs |= fresh
            name = " ".join(f"g{g}xs{s}" for g, s, _ in sorted(fresh))
            prefill_dispatch[name] = {
                k: v for k, v in stats_delta(mark).items()
                if k.startswith("flash")}
        if attn_err is None and eng.stats.decode_steps > 0:
            attn_err, probe = check_decode_attention(eng, cfg)
        mark = kernels.dispatch_stats()
    outs = eng.run()
    cold_s = time.perf_counter() - t0
    check(outs, cold)
    if attn_err is None:
        raise AssertionError("server: no decode step ran")
    # the engine's own traces: the attention probe's count taken out
    dispatch = {k: v - probe.get(k, 0)
                for k, v in stats_delta(before).items()}

    warm = requests(len(cold))
    t0 = time.perf_counter()
    check(eng.run(warm), warm)
    warm_s = time.perf_counter() - t0

    return {"requests": len(cold) + len(warm), "dispatch": dispatch,
            "prefill_dispatch": prefill_dispatch, "attn_err": attn_err,
            "buckets": sorted({s for _, s, _ in programs}),
            "cold_s": cold_s, "warm_s": warm_s,
            "memory": memory_line(jax.devices()[:1])}


def check_decode_attention(eng, cfg):
    """The attention one decode step dispatches — same dispatcher, same
    block tables and lengths, the engine's live page pool — against
    ``paged_attention_ref``. Returns (the error as a fraction of the
    largest reference magnitude, the dispatch counters the probe itself
    moved); raises above ``ATTN_TOL``."""
    live = [s is not None and not s.done for s in eng.slots]
    if not any(live):
        return None, {}
    bt = jnp.asarray(eng.cache.block_tables(
        [s.req.rid if on else None for s, on in zip(eng.slots, live)]))
    lengths = jnp.asarray([s.kv_len if on else 0
                           for s, on in zip(eng.slots, live)], jnp.int32)
    q = jax.random.normal(
        jax.random.PRNGKey(2),
        (eng.num_slots, cfg.num_attention_heads, cfg.head_dim), cfg.dtype)
    kp, vp = eng.cache.pool["k"][0], eng.cache.pool["v"][0]
    before = kernels.dispatch_stats()
    got = jax.jit(kernels.dispatched_paged_attention)(q, kp, vp, bt, lengths)
    probe = stats_delta(before)
    want = jax.jit(kernels.paged_attention_ref)(q, kp, vp, bt, lengths)
    got, want = (np.asarray(a).astype(np.float32) for a in (got, want))
    scale = float(np.max(np.abs(want)))
    err = float(np.max(np.abs(got - want))) / scale if scale else math.inf
    if not (np.isfinite(got).all() and err <= ATTN_TOL):
        raise AssertionError(
            f"decode attention differs from paged_attention_ref by "
            f"{err:.3e} of max |ref| {scale:.3e} (tolerance {ATTN_TOL})")
    return err, probe


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (first device is {dev.platform}, "
                 f"{dev.device_kind}); there is no off-chip mode")
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    # Blocks come from the tracked autotune_cache.json or, where it has
    # no entry, from the shape (flash) or the default, and are never
    # measured here, so what runs is a function of the committed tree.
    os.environ["PADDLE_TPU_AUTOTUNE"] = "cached"
    cache_dir = enable_compile_cache()
    dev = require_tpu()
    devices = jax.devices()
    import jaxlib
    from importlib.metadata import version
    say(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)}  jax {jax.__version__} jaxlib "
        f"{jaxlib.__version__} libtpu {version('libtpu')}")
    say(f"compile cache: {cache_dir}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, found {len(devices)}")

    peaks = roofline.resolve_peaks(dev)
    say(f"peaks for {dev.device_kind!r}: "
        f"{peaks['peak_flops_per_sec'] / 1e12:.0f} TFLOP/s "
        f"({peaks['flops_source']}), "
        f"{peaks['peak_hbm_bytes_per_sec'] / 1e9:.0f} GB/s "
        f"({peaks['hbm_source']})")
    require(peaks["flops_source"] == peaks["hbm_source"] == "table",
            f"peaks of {dev.device_kind!r} are not a table hit: {peaks}")

    batch, seq = 4, 2048
    cfg = L.llama_3_8b(num_hidden_layers=TRAIN_LAYERS)
    say(f"trainer: llama_3_8b at published widths, vocabulary "
        f"{cfg.vocab_size}, depth cut {L.llama_3_8b().num_hidden_layers} "
        f"-> {TRAIN_LAYERS} layers so that parameters, gradients and bf16 "
        f"AdamW moments ({L.count_params(cfg) / 1e9:.2f} B parameters x 8 "
        f"bytes) fit one 16 GB chip; {batch} x {seq} tokens")
    # (the phase's parameters and optimizer state die with its frame:
    # nothing of the trainer is held when the next phase allocates)
    one = train_phase(cfg, batch=batch, seq=seq,
                      steps=3 if args.chips == 1 else 1)
    report_train("trainer", one)
    d = one["dispatch"]
    require(d.get("flash", 0) >= 1 and not d.get("flash_fallback")
            and d.get("fused_ce", 0) >= 1 and not d.get("fused_ce_fallback"),
            f"trainer did not trace the Pallas flash kernel and the fused "
            f"CE: {d}")

    if args.chips == 4:
        mesh = Mesh(np.asarray(devices[:4]).reshape(1, 2, 2),
                    ("dp", "fsdp", "tp"))
        say("mesh trainer: the same model and batch, ('dp','fsdp','tp') = "
            "(1, 2, 2), sequence parallel, one process")
        four = train_phase(cfg, batch=batch, seq=seq, steps=3, mesh=mesh)
        report_train("mesh trainer", four)
        say(f"  placement: {four['placement']}")
        d = four["dispatch"]
        require(d.get("flash", 0) >= 1 and not d.get("flash_fallback"),
                f"mesh trainer did not trace the Pallas flash kernel: {d}")
        gap = abs(four["losses"][0] - one["losses"][0])
        say(f"  first-step loss: one chip {one['losses'][0]:.5f}, mesh "
            f"{four['losses'][0]:.5f}, |gap| {gap:.2e} "
            f"(tolerance {MESH_LOSS_TOL})")
        require(gap <= MESH_LOSS_TOL, "mesh loss differs from one-chip loss")
    else:
        cfg = L.llama_3_8b(num_hidden_layers=SERVE_LAYERS, remat=False)
        prompt_lens = (1024, 300, 700, 512, 900, 450, 600, 384,
                       1000, 350, 800, 480)
        new_tokens = (32, 128, 64, 96, 48, 32, 128, 64, 96, 48, 64, 32)
        say(f"server: llama_3_8b at published widths, depth cut to "
            f"{SERVE_LAYERS} layers (a smoke, not a deployment: bf16 "
            f"weights {L.count_params(cfg) * 2 / 2**30:.1f} GiB); "
            f"{len(prompt_lens)} requests, prompts {min(prompt_lens)}-"
            f"{max(prompt_lens)} tokens, {min(new_tokens)}-"
            f"{max(new_tokens)} new tokens, 8 slots, page 16")
        served = serve_phase(cfg, prompt_lens=prompt_lens,
                             new_tokens=new_tokens, num_slots=8,
                             page_size=16)
        n = served["requests"] // 2
        say(f"  {served['requests']} requests completed; cold pass "
            f"{served['cold_s']:.1f}s (compiles included), warm pass "
            f"{served['warm_s']:.1f}s = {served['warm_s'] / n:.2f}s a "
            f"request")
        say(f"  prefill buckets {served['buckets']}; kernel counters by "
            f"prefill program: {served['prefill_dispatch']}")
        say(f"  decode attention vs paged_attention_ref on the live pool: "
            f"{served['attn_err']:.2e} of max |ref| (tolerance {ATTN_TOL})")
        say(f"  dispatch: {served['dispatch']}")
        say(f"  {served['memory']} (peak is the process's, trainer "
            f"included)")
        d = served["dispatch"]
        require(d.get("paged", 0) >= 1 and not d.get("paged_fallback"),
                f"server decode did not trace the Pallas paged kernel: {d}")
        require(d.get("kv_write", 0) >= 1 and not d.get("kv_write_fallback"),
                f"server decode did not trace the in-place KV write: {d}")
        require(len(served["buckets"]) <= 2,
                f"more than two prefill buckets: {served['buckets']}")

    say("autotune (mode 'cached'; source 'shape-rule' or 'default' = no "
        "entry in autotune_cache.json):")
    for key, used in sorted(autotune.used_blocks().items()):
        say(f"  {key}: {used}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


def report_train(name: str, r: dict) -> None:
    later = r["step_s"]
    say(f"  {name}: losses {[round(x, 4) for x in r['losses']]}")
    say(f"  first step {r['first_step_s']:.1f}s (compile included)"
        + (f", then {', '.join(f'{t:.3f}' for t in later)}s a step"
           if later else ""))
    say(f"  dispatch: {r['dispatch']}")
    say(f"  {r['memory']}")


if __name__ == "__main__":
    sys.exit(main())
