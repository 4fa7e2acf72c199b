"""Autoregressive generation with the static KV cache — jit once,
decode at HBM-bandwidth speed.

Run:  python examples/generate_llama.py  (TPU or CPU)

Shows the serving path: prefill fills a static [L, B, max_len, kv, hd]
ring cache, then the whole greedy loop runs as ONE compiled program
(lax.scan over decode steps) — no per-token retrace, no concat-grown
cache. The eager Layer model reaches the same path via
``LlamaForCausalLM.generate``.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import llama as L

on_tpu = jax.default_backend() == "tpu"
if on_tpu:
    cfg = L.llama_3_8b(num_hidden_layers=4, vocab_size=32000, remat=False)
    batch, prompt_len, new = 8, 128, 64
else:
    cfg = L.llama_tiny(num_hidden_layers=2, dtype=jnp.bfloat16)
    batch, prompt_len, new = 2, 16, 8

print(f"params: {L.count_params(cfg) / 1e6:.1f}M  device: "
      f"{jax.devices()[0].device_kind}")

params = jax.jit(lambda: L.init_params(cfg, jax.random.PRNGKey(0)))()
ids = jnp.asarray(np.random.default_rng(0).integers(
    0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)

# greedy — temperature=0.7 + key=PRNGKey(..) would sample instead
gen = jax.jit(lambda p, i: L.generate(p, i, cfg, max_new_tokens=new))
toks = gen(params, ids)                       # compile + warmup
float(toks[0, -1])                            # hard sync

t0 = time.perf_counter()
toks = gen(params, ids)
float(toks[0, -1])
dt = time.perf_counter() - t0
print(f"decoded {batch}x{new} tokens in {dt * 1e3:.0f} ms "
      f"({batch * new / dt:.0f} tok/s, {dt / new * 1e3:.2f} ms/token)")
print("greedy:", np.asarray(toks[0])[:16])

# nucleus sampling and beam search ride the same compiled-loop design
sampled = L.generate(params, ids[:2], cfg, max_new_tokens=16,
                     temperature=0.8, top_p=0.95,
                     key=jax.random.PRNGKey(42))
print("top-p 0.95:", np.asarray(sampled[0]))
beams, scores = L.beam_search(params, ids[:2], cfg, max_new_tokens=16,
                              num_beams=4, length_penalty=0.6)
print(f"beam-4 (score {float(scores[0]):.2f}):", np.asarray(beams[0]))

# weight-only int8 serving: the quantized pytree drops into the same
# jitted loop (decode is HBM-bound — int8 weights measured 1.4x on-chip)
qparams = jax.jit(L.quantize_weights)(params)
toks8 = jax.jit(lambda p, i: L.generate(p, i, cfg, max_new_tokens=new))(
    qparams, ids)
print("int8 greedy:", np.asarray(toks8[0])[:16])
