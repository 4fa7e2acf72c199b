"""Pipeline parallelism — TPU-native staged execution.

Reference capability: fleet/meta_parallel/pipeline_parallel.py (1F1B
`forward_backward_pipeline:459`, interleaved `:1008`) + the FleetExecutor
actor runtime (fleet_executor.h:36) + P2P layer (p2p_communication.py).

TPU-native design: XLA has no native pipeline parallelism, so the schedule
is built *inside one jitted program* as a collective-permute pipeline over a
mesh axis (SURVEY.md §7 "PP" row): every device holds one stage's weights
(stacked leading axis sharded over 'pp'), and a `lax.scan` over
`num_micro + num_stages - 1` ticks shifts activations stage-to-stage with
`lax.ppermute` (ICI collective-permute — the p2p primitive). Stage 0
injects a fresh micro-batch each tick; the last stage emits into the output
buffer. Differentiating the scanned program yields the reversed pipeline
(backward micro-batch schedule) automatically — GPipe semantics with
per-stage rematerialisation bounding activation memory.

This module is the fully-compiled homogeneous-stage pipeline. The general
schedule family — 1F1B, interleaved VPP, zero-bubble, heterogeneous
embedding/head stages — lives in fleet/pipeline_schedules.py (schedules as
data) + fleet/pipeline_runtime.py (the stage-program interpreter).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["pipeline_spmd", "make_pipeline_train_step",
           "shard_stage_params", "split_microbatches"]


def split_microbatches(batch, num_micro: int):
    """[B, ...] -> [M, B/M, ...]."""
    return jax.tree.map(
        lambda x: x.reshape(num_micro, x.shape[0] // num_micro,
                            *x.shape[1:]), batch)


def pipeline_spmd(stage_fn: Callable, params, micro_inputs, mesh: Mesh,
                  *, axis: str = "pp", remat: bool = True):
    """Run a GPipe collective-permute pipeline over mesh axis ``axis``.

    stage_fn(stage_params, x) -> y, same activation shape in/out (the
    classic homogeneous-stage transformer assumption).
    params: pytree with leading axis = num_stages (sharded over ``axis``).
    micro_inputs: [M, mb, ...] micro-batched activations (replicated).
    Returns [M, mb, ...] outputs of the final stage.
    """
    num_stages = mesh.shape[axis]
    num_micro = jax.tree.leaves(micro_inputs)[0].shape[0]
    ticks = num_micro + num_stages - 1

    fn = jax.checkpoint(stage_fn, prevent_cse=False) if remat else stage_fn

    def per_device(stage_params, micros):
        # stage_params: [1, ...] slice for this device; micros: full [M,...]
        stage_params = jax.tree.map(lambda p: p[0], stage_params)
        sid = lax.axis_index(axis)
        zero = jax.tree.map(lambda x: jnp.zeros_like(x[0]), micros)
        outputs = jax.tree.map(
            lambda x: jnp.zeros_like(x), micros)

        def tick(carry, t):
            state, outputs = carry
            # receive previous stage's activation (ring shifted by one)
            perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
            shifted = jax.tree.map(
                lambda s: lax.ppermute(s, axis, perm), state)
            # stage 0 ingests micro-batch t (or zeros when drained)
            inject = jax.tree.map(
                lambda m, z: jnp.where(t < num_micro, m[jnp.minimum(
                    t, num_micro - 1)], z), micros, zero)
            x = jax.tree.map(
                lambda inj, sh: jnp.where(sid == 0, inj, sh),
                inject, shifted)
            y = fn(stage_params, x)
            # last stage emits micro-batch index t - (S-1)
            out_idx = jnp.clip(t - (num_stages - 1), 0, num_micro - 1)
            emit = (sid == num_stages - 1) & (t >= num_stages - 1)
            outputs = jax.tree.map(
                lambda buf, yy: lax.dynamic_update_index_in_dim(
                    buf, jnp.where(emit, yy, buf[out_idx]), out_idx, 0),
                outputs, y)
            return (y, outputs), None

        (last, outputs), _ = lax.scan(
            tick, (zero, outputs), jnp.arange(ticks))
        # outputs live on the last stage; broadcast to all (psum of the
        # one non-zero contribution)
        outputs = jax.tree.map(
            lambda o: lax.psum(
                jnp.where(sid == num_stages - 1, o, jnp.zeros_like(o)),
                axis), outputs)
        return outputs

    pspec = jax.tree.map(lambda _: P(axis), params)
    return shard_map(
        per_device, mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )(params, micro_inputs)


def make_pipeline_train_step(stage_fn: Callable, loss_fn: Callable,
                             mesh: Mesh, *, num_micro: int,
                             axis: str = "pp", lr: float = 1e-3,
                             remat: bool = True):
    """Jitted pipeline-parallel SGD train step.

    stage_fn(stage_params, x) -> y; loss_fn(y, labels) -> scalar (applied
    to final-stage output per micro-batch, averaged).
    Returns step(params, batch, labels) -> (params, loss), with params'
    leading axis sharded over the pp mesh axis.
    """

    def loss_of(params, batch, labels):
        micro_x = split_microbatches(batch, num_micro)
        micro_y = pipeline_spmd(stage_fn, params, micro_x, mesh,
                                axis=axis, remat=remat)
        micro_l = split_microbatches(labels, num_micro)
        losses = jax.vmap(loss_fn)(micro_y, micro_l)
        return jnp.mean(losses)

    def step(params, batch, labels):
        loss, grads = jax.value_and_grad(loss_of)(params, batch, labels)
        params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                              params, grads)
        return params, loss

    return jax.jit(step)


def shard_stage_params(params, mesh: Mesh, axis: str = "pp"):
    """Place stage-stacked params (leading axis = stages) on the pp axis."""
    return jax.tree.map(
        lambda p: jax.device_put(p, NamedSharding(mesh, P(axis))), params)
