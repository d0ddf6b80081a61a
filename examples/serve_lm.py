"""Serve a small LM with batched requests: sharded prefill + decode loop.

Demonstrates the serving stack on 8 simulated devices (4 request shards x
2-way tensor parallel) with greedy sampling from the vocab-sharded logits.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import tiny_lm
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.train import serve as serve_mod

cfg = tiny_lm(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=512,
              vocab_size=1024)
mesh = make_mesh((4, 2), ("data", "model"))
rt = T.RuntimeConfig(dtype="float32", remat=False)

B, PROMPT, GEN = 8, 24, 16
params = T.init_params(jax.random.key(0), cfg, tp=2)
scfg = serve_mod.ServeConfig(runtime=rt, target_len=PROMPT + GEN)
prefill, (pspecs, _, _) = serve_mod.build_prefill_step(
    cfg, mesh, scfg, global_batch=B)
decode, _ = serve_mod.build_decode_step(
    cfg, mesh, scfg, global_batch=B, target_len=PROMPT + GEN)

pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                      is_leaf=lambda x: isinstance(x, P))
params = jax.tree.map(lambda x, sh: jax.device_put(x, sh), params, pshard)

rng = np.random.default_rng(0)
prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, PROMPT)), jnp.int32)
logits, cache = prefill(params, {"tokens": prompts})
print(f"prefilled {B} requests x {PROMPT} tokens; logits {logits.shape}")

out = []
tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
for t in range(PROMPT, PROMPT + GEN):
    out.append(np.asarray(tok)[:, 0])
    logits, cache = decode(params, tok, cache, jnp.int32(t))
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]

gen = np.stack(out, axis=1)
print("greedy continuations (token ids):")
for b in range(min(4, B)):
    print(f"  request {b}: {gen[b].tolist()}")
