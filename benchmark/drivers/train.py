"""Training: donated AdamW steps on a new batch each, made on the host
while the last step runs. Tokens a second is taken over the whole
window, after a ``block_until_ready`` on its last step."""
from __future__ import annotations

import json
import math
import os
import time
from collections import deque

import jax
import jax.numpy as jnp

from ..harness import check, traffic
from ..harness.manifest import build_config, seeded_params
from ..harness.session import SCRATCH, say, span


def run(r) -> dict:
    mix, blk = r.mix, r.conf["train"]
    family, cfg = build_config(r.conf, "train")
    from paddle_tpu.models import llama as L

    params = seeded_params(family, cfg, r.seed)
    r.mark("weights")
    # checked before the moments exist, so that the reference's float32
    # copies have the room
    chk = check.train_check(r.arch, family, cfg, r.conf, params, r.seed)
    say(f"reference check: {chk}")
    r.mark("reference_check")
    opt = jax.jit(lambda p: L.adamw_init(
        p, moment_dtype=getattr(jnp, blk["moment_dtype"])))(params)
    step = family.make_train_step(cfg, None, guard=False)
    batches = traffic.train_batches(mix, cfg.vocab_size, r.seed)

    def one(params, opt):
        with span("bench.next_batch"):
            batch = jnp.asarray(next(batches))
        with span("bench.train_step"):
            return step(params, opt, batch)

    for _ in range(mix["warm_steps"]):
        params, opt, loss = one(params, opt)
    jax.block_until_ready(loss)
    r.mark("warm_up")

    losses, pending = [], deque()
    r.open_window()
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        r.tracer.tick(now)
        if now >= r.seconds:
            break
        params, opt, loss = one(params, opt)
        losses.append(loss)
        pending.append(loss)
        if len(pending) > mix["in_flight"]:
            # the host runs at most in_flight steps ahead of the device
            with span("bench.wait_step"):
                jax.block_until_ready(pending.popleft())
    jax.block_until_ready((params, loss))
    elapsed = time.perf_counter() - t0
    r.tracer.stop(elapsed)

    losses = [float(x) for x in jax.device_get(losses)]
    steps = len(losses)
    finite = all(math.isfinite(x) for x in losses)
    tokens = steps * mix["batch"] * mix["seq_len"]
    os.makedirs(SCRATCH, exist_ok=True)
    path = os.path.join(SCRATCH, f"{r.cell['name']}.seed{r.seed}.losses.json")
    with open(path, "w") as f:
        json.dump(losses, f)
    say(f"train: {steps} steps, {tokens} tokens in {elapsed:.3f} s; loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}; losses in {path}")
    if not r.rehearse:
        flops = r.conf["model_flops_per_token"]["flops"]
        say(f"model FLOPs a token {flops:.4g} (at {mix['seq_len']} tokens): "
            f"{tokens / elapsed * flops / 1e12:.2f} TFLOP/s a job")
    failed = sum(not math.isfinite(x) for x in losses)
    return {
        "correct": chk["ok"] and finite and steps > 0,
        "attempted": steps, "failed": failed,
        "compared": {**chk["numbers"], "losses_not_finite": [failed, 0]},
        "end_to_end": {"train_tok_s": tokens / elapsed},
        "counters": {"train.steps": steps, "train.tokens": tokens,
                     "window_s": elapsed},
        "info": {"check": chk},
    }
