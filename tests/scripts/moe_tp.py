"""The expert layer cut over two devices of a ``model`` axis, against the
uncut layer on one: the expert mode (4 experts, 2 a device, each running
its groups by the kernel's group offset) and the ffn mode (3 experts, each
expert's d_ff cut in half).  Output and gradients of every weight and of
the input must agree after the layer's one psum.  Prints ``<mode>: OK``.
Needs two devices: XLA_FLAGS=--xla_force_host_platform_device_count=2."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import moe as moe_mod
from repro.models.layers import Initializer, TPContext

TP = 2
mesh = make_mesh((TP,), ("model",))
base = get_config("granite-moe-1b-a400m", smoke=True)  # d 64, d_ff 32, top-2

for mode, E in (("expert", 4), ("ffn", 3)):
    cfg = dataclasses.replace(base, n_experts=E, capacity_factor=E / base.top_k)
    assert moe_mod._expert_sharding(cfg, TP) == mode
    p = moe_mod.moe_init(Initializer(jax.random.key(3)), cfg)
    x = jax.random.normal(jax.random.key(5), (2, 16, cfg.d_model), jnp.float32)
    ct = jax.random.normal(jax.random.key(6), x.shape, jnp.float32)

    def loss(x, p, tp_ctx):
        return jnp.sum(moe_mod.moe_forward(x, p, cfg, tp_ctx)[0] * ct)

    want = jax.value_and_grad(loss, (0, 1))(x, p, TPContext())
    specs = moe_mod.moe_specs(cfg, TP)
    cut = jax.jit(jax.shard_map(
        lambda x, p: jax.value_and_grad(loss, (0, 1))(
            x, p, TPContext(axis="model", size=TP, in_shard_map=True)),
        mesh=mesh, in_specs=(P(), specs), out_specs=(P(), (P(), specs)),
        axis_names={"model"}))
    got = cut(x, p)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    print(f"{mode}: OK")
