"""Serving example: batched prefill + decode with a KV cache.

A miniature of the decode_32k dry-run cell, actually executed on CPU with a
reduced config: 8 concurrent requests, one prefill, then token-by-token
batched decode with greedy sampling.

Run:  PYTHONPATH=src python examples/serve_decode.py
"""

import time

import jax
import jax.numpy as jnp

from repro.configs import get_smoke
from repro.models import build_model

ARCH = "qwen3-14b"
BATCH, PROMPT, GEN = 8, 48, 16

cfg = get_smoke(ARCH)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))

prompts = jax.random.randint(jax.random.PRNGKey(1), (BATCH, PROMPT), 0,
                             cfg.vocab_size)
S_max = PROMPT + GEN

print(f"[{ARCH}] prefill {BATCH} requests x {PROMPT} tokens ...")
prefill = jax.jit(lambda p, b: model.prefill(p, b, S_max))
t0 = time.perf_counter()
logits, cache = prefill(params, {"tokens": prompts})
logits.block_until_ready()
print(f"prefill: {time.perf_counter() - t0:.2f}s (incl. compile)")

decode = jax.jit(model.decode_step, donate_argnums=(1,))
tok = jnp.argmax(logits, axis=-1)
generated = [tok]
t0 = time.perf_counter()
for i in range(GEN - 1):
    logits, cache = decode(params, cache, {"token": tok})
    tok = jnp.argmax(logits, axis=-1)
    generated.append(tok)
tok.block_until_ready()
dt = time.perf_counter() - t0
out = jnp.stack(generated, axis=1)
print(f"decoded {GEN - 1} steps x {BATCH} seqs in {dt:.2f}s "
      f"({(GEN - 1) * BATCH / dt:.1f} tok/s on CPU, incl. compile)")
print("sample continuation (request 0):", out[0].tolist())
assert out.shape == (BATCH, GEN)
assert int(cache["pos"]) == PROMPT + GEN - 1
print("OK")
