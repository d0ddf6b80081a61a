"""Subprocess worker: SlowMo with grad clip and weight decay takes a finite
train step on two nodes, with the default TrainConfig.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=2 (set by the
pytest wrapper).
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import tiny_lm
from repro.core.optimizers import make_optimizer
from repro.launch.mesh import make_mesh
from repro.train.step import TrainConfig, build_train_step
from repro.train.train_state import init_train_state

N = 2
cfg = tiny_lm(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
              vocab_size=256)
tcfg = TrainConfig(algorithm="slowmo", topology="ring", grad_clip=1.0,
                   weight_decay=0.01)
mesh = make_mesh((N, 1), ("data", "model"))
step, _, _, channel = build_train_step(cfg, tcfg, mesh)
state = init_train_state(
    jax.random.key(0), cfg, make_optimizer(tcfg.opt_config()), N, 1,
    mesh=mesh, node_axes=("data",), channel=channel,
)
tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2 * N, 16))
batch = {"tokens": jnp.asarray(tokens, jnp.int32),
         "targets": jnp.asarray(np.roll(tokens, -1, 1), jnp.int32)}
state, metrics = step(state, batch)
loss = float(metrics["loss"])
assert np.isfinite(loss), loss
for leaf in jax.tree.leaves((state["params"], state["opt"])):
    assert bool(jnp.isfinite(leaf).all())
print(f"slowmo clip+wd: OK loss={loss:.4f}")
