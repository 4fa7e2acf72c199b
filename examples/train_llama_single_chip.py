"""Train a Llama slice on one chip, as a readable example.

Run:  python examples/train_llama_single_chip.py  (TPU or CPU)

Shows the functional training path: config -> init_params ->
make_train_step (jitted, donated buffers) -> loop. On TPU the Pallas
flash-attention kernel engages automatically (kernels.auto_register).
With FLAGS_enable_sentinel=1 the step is built GUARDED (in-graph
NaN/spike gate, paddle_tpu/training/sentinel.py) and this loop drives
it — an anomalous batch is skipped with params untouched.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import llama as L

on_tpu = jax.default_backend() == "tpu"
if on_tpu:
    cfg = L.llama_3_8b(num_hidden_layers=4, vocab_size=32000,
                       remat_policy="full")
    batch, seq = 4, 2048
else:
    cfg = L.llama_tiny(num_hidden_layers=2, dtype=jnp.bfloat16)
    batch, seq = 4, 128

print(f"params: {L.count_params(cfg) / 1e6:.1f}M  device: "
      f"{jax.devices()[0].device_kind}")

params = L.init_params(cfg, jax.random.PRNGKey(0))
opt_state = L.adamw_init(params)
step = L.make_train_step(cfg, lr=3e-4)   # guard follows the sentinel flag

sentinel = None
if L.resolve_guard(None):
    from paddle_tpu.training.sentinel import AnomalySentinel
    sentinel = AnomalySentinel()
    print("sentinel: guarded step (skip-on-anomaly)")

rng = np.random.default_rng(0)
for i in range(10):
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq + 1)),
                      jnp.int32)
    t0 = time.perf_counter()
    if sentinel is None:
        params, opt_state, loss = step(params, opt_state, ids)
    else:
        cap = jnp.asarray(sentinel.gnorm_cap(), jnp.float32)
        params, opt_state, loss, health = step(params, opt_state, ids, cap)
        if sentinel.observe(finite=health["finite"],
                            grad_norm=health["grad_norm"],
                            loss=loss) != "ok":
            print(f"step {i}: anomalous batch SKIPPED")
            continue
    lv = float(loss)                       # hard sync
    dt = time.perf_counter() - t0
    print(f"step {i}: loss {lv:.4f}  ({batch * seq / dt:,.0f} tok/s)")
