"""Quickstart: DecentLaM vs DmSGD on 8 simulated nodes in ~1 minute.

Trains a tiny LM with both algorithms on heterogeneous synthetic shards and
prints the loss + consensus distance — DecentLaM reaches a lower loss floor
because its inconsistency bias is not momentum-amplified (paper Prop. 2-3).

Communication goes through the ``GossipChannel`` transport API: the train
step gossips via an edge-class ppermute channel whose state (compression
error feedback, delay buffers, telemetry) lives in the TrainState's
``"channel"`` bucket, and the channel's introspection prices the wire
traffic (``bytes_per_step``).

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import tiny_lm
from repro.core.optimizers import make_optimizer
from repro.core.schedules import ScheduleConfig
from repro.data.synthetic import SyntheticLM, SyntheticLMConfig
from repro.launch.mesh import make_mesh
from repro.models.transformer import RuntimeConfig
from repro.train.step import TrainConfig, build_train_step
from repro.train.train_state import init_train_state

N_NODES, TP, STEPS, SEQ = 8, 1, 60, 64
cfg = tiny_lm(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=512,
              vocab_size=1024)
mesh = make_mesh((N_NODES, TP), ("data", "model"))

for algo in ("dmsgd", "decentlam"):
    tcfg = TrainConfig(
        algorithm=algo, topology="exp", momentum=0.9,
        schedule=ScheduleConfig(kind="constant", peak_lr=5e-3),
        runtime=RuntimeConfig(dtype="float32", remat=False),
        track_consensus=True,
    )
    opt = make_optimizer(tcfg.opt_config())
    step_fn, _, bspecs, channel = build_train_step(
        cfg, tcfg, mesh, node_axes=("data",)
    )
    state = init_train_state(jax.random.key(0), cfg, opt, N_NODES, TP,
                             mesh=mesh, node_axes=("data",), channel=channel)
    n_params = sum(x.size for x in jax.tree.leaves(state["params"])) // N_NODES
    comm = channel.bytes_per_step(4.0 * n_params)
    print(f"{algo}: {channel.name} channel on {channel.topology.name}, "
          f"{comm['egress_bytes'] / 2**20:.1f} MiB egress/node/step "
          f"over {comm['hops']:.0f} hops")
    data = SyntheticLM(SyntheticLMConfig(
        vocab_size=cfg.vocab_size, seq_len=SEQ, per_node_batch=4,
        n_nodes=N_NODES, heterogeneity=0.5))
    bshard = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs,
                          is_leaf=lambda x: isinstance(x, P))
    for k in range(STEPS):
        batch = jax.tree.map(lambda x, sh: jax.device_put(jnp.asarray(x), sh),
                             data.batch(k), bshard)
        state, m = step_fn(state, batch)
        if k % 20 == 0 or k == STEPS - 1:
            print(f"{algo:10s} step {k:3d} loss {float(m['loss']):.4f} "
                  f"consensus {float(m['consensus_sq']):.3e}")
    tele = state["channel"]["t"]
    print(f"{algo:10s} channel telemetry: {int(tele['rounds'][0])} gossip "
          f"rounds, {float(tele['bytes'][0]) / 2**20:.1f} MiB egress/node\n")
