"""Subprocess worker: sharded serve (prefill + decode) == tp=1 oracle."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import tiny_lm
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.layers import TPContext
from repro.train import serve as serve_mod

cfg = tiny_lm(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
              vocab_size=256)
mesh = make_mesh((4, 2), ("data", "model"))
rt = T.RuntimeConfig(dtype="float32", remat=False)
B, S = 8, 32

params = T.init_params(jax.random.key(0), cfg, tp=2)
rng = np.random.default_rng(0)
toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + 1)), jnp.int32)

# tp=1 oracle
tp1 = TPContext(size=1)
params1 = params  # same logical params; tp only affects padding (none here)
lg_or, cache_or = jax.jit(
    lambda p, b: T.prefill(p, b, cfg, tp1, rt, target_len=S + 4)
)(params1, {"tokens": toks[:, :S]})
lg_or2, _ = jax.jit(
    lambda p, t, c: T.decode_step(p, t, c, jnp.int32(S), cfg, tp1, rt,
                                  target_len=S + 4)
)(params1, toks[:, S:S + 1], cache_or)

# sharded path
scfg = serve_mod.ServeConfig(runtime=rt, target_len=S + 4)
pre, (pspecs, bspec, cspecs) = serve_mod.build_prefill_step(
    cfg, mesh, scfg, global_batch=B)
dec, _ = serve_mod.build_decode_step(cfg, mesh, scfg, global_batch=B,
                                     target_len=S + 4)
pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                      is_leaf=lambda x: isinstance(x, P))
pp = jax.tree.map(lambda x, sh: jax.device_put(x, sh), params, pshard)
lg_d, cache_d = pre(pp, {"tokens": toks[:, :S]})
lg_d2, _ = dec(pp, toks[:, S:S + 1], cache_d, jnp.int32(S))

for name, a, b in [("prefill", lg_or, lg_d), ("decode", lg_or2, lg_d2)]:
    err = np.max(np.abs(np.asarray(a) - np.asarray(b)))
    rel = err / (np.max(np.abs(np.asarray(a))) + 1e-9)
    assert rel < 5e-4, (name, rel)
    print(f"{name}: OK rel={rel:.2e}")

# --- replicated fallback: global_batch=1 is indivisible by the 4-way node
# axis, so the batch stays replicated (_batch_axes -> None) while params
# remain model-sharded — both prefill and decode must still match the
# oracle's first request ---
_, _, _, ba1 = serve_mod.serve_specs(cfg, mesh, global_batch=1)
assert ba1 is None, ba1
pre1, _ = serve_mod.build_prefill_step(cfg, mesh, scfg, global_batch=1)
dec1, _ = serve_mod.build_decode_step(
    cfg, mesh, scfg, global_batch=1, target_len=S + 4, per_slot_t=True)
lg_r, cache_r = pre1(pp, {"tokens": toks[:1, :S]})
lg_r2, _ = dec1(pp, toks[:1, S:S + 1], cache_r, jnp.full((1,), S, jnp.int32))
for name, a, b in [("prefill-b1", lg_or[:1], lg_r), ("decode-b1", lg_or2[:1], lg_r2)]:
    err = np.max(np.abs(np.asarray(a) - np.asarray(b)))
    rel = err / (np.max(np.abs(np.asarray(a))) + 1e-9)
    assert rel < 5e-4, (name, rel)
    print(f"{name}: OK rel={rel:.2e}")
