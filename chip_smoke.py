"""Chip smoke test: the DecentLaM train step on a TPU, through the normal path.

    python chip_smoke.py            # one chip, one gossip node: phases a-e, g
    python chip_smoke.py --chips 4  # four chips, four gossip nodes: phase f

Every phase runs in this one process (a chip belongs to one process), and
the train step runs through ``repro.launch.train.run`` with an argument
list, exactly as ``python -m repro.launch.train`` would run it.

a. Device check: JAX must find a TPU, else exit 1 before anything runs.
b. qwen3-0.6b at its published widths (random weights from seed 0),
   DecentLaM on flat planes with the Pallas fused update, STEPS steps.
   Prints compile and steady step seconds, the losses, the device's peak
   bytes and the number of ``tpu_custom_call``s in the compiled step.
c. The same steps with the jnp reference update tail; the params after the
   last step must match (b) within PARAM_ULPS (reason at the constant).
d. The flash-attention kernel at qwen3-0.6b head widths against the jnp
   reference (reason for the error bound at FLASH_REL).
e. Last line: ``{"ok": true, "device": {...}}``.
f. ``--chips 4`` only: the step on a (4, 1) mesh, four nodes on a ring with
   heterogeneous data, each node's params on its own chip, and one
   ``PpermuteChannel`` round on the mesh against ``StackedChannel`` on one
   device.
g. The finite guard tripped inside the Pallas update kernels: the tiny LM's
   DecentLaM step on flat planes, its loss scaled by inf on a batch made of
   one token (non-finite grads) and by 0 on another (the ``g = 0`` update).
   The poisoned step must count ``skipped_nonfinite == 1``, leave the
   momentum planes as they were bit for bit, give the params exactly the
   ``g = 0`` step's, and the next step must train.

Any failed check exits 1 and prints no result line.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen3-0.6b"
STEPS = 5
# The step keeps f32 params, momentum and the plane copies, and
# repro.launch.train runs with remat off; at seq 512 a per-node batch of 2 needs 14.3 GB on one
# v5e (compile-only memory analysis, tests/test_tpu_compile.py) and a batch
# of 4 does not fit in the chip's 16 GB, in f32 or in bf16 compute.
SEQ_LEN = 512
PER_NODE_BATCH = 2
SEED = 0

# (c) The Pallas and the reference tails compute the same elementwise f32
# ops on the same inputs; the compilers may still round a division or a
# fused multiply-add differently, by an ulp per op per step, and
# DecentLaM's (x - mix) / lr feeds that into the momentum.  Over STEPS
# steps that stays within a few ulps of the leaf's largest value; a tail in
# bf16 would be ~2^16 ulps off.
PARAM_ULPS = 8
# (d) The kernel's p @ v matmul may run at Mosaic's default precision, one
# bf16 pass, so each softmax weight p_j may be rounded to bf16 (unit
# roundoff 2^-8) before it meets v_j; the reference runs in f32 throughout.
# That error is at most 2^-8 * sum_j p_j |v_j|, i.e. 2^-8 of the attention
# of |v| (computed by the same reference): FLASH_REL.  Both outputs are then
# rounded to bf16, 2^-8 of |out| each: FLASH_OUT_REL.  One bf16 ulp of
# difference always passes.  With 16 query heads over 8 KV heads the
# weights differ per query head, so a wrong GQA head mapping fails this
# bound by orders of magnitude.
FLASH_REL = 2.0**-8
FLASH_OUT_REL = 2.0**-7
FLASH_SHAPE = dict(batch=2, seq=2048, heads=16, kv_heads=8, head_dim=128)
# (f) One gossip round mixes 3 f32 terms per element on a ring; the mesh
# and the einsum oracle add them in different orders.
MIX_ULPS = 4


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def step_argv(impl: str, *, topology: str = "exp",
              heterogeneity: float = 0.2) -> list[str]:
    """The ``repro.launch.train`` command line of phases b, c and f."""
    return [
        "--arch", ARCH, "--algorithm", "decentlam", "--topology", topology,
        "--flat-planes", "--fused-update", "--fused-impl", impl,
        "--steps", str(STEPS), "--seq-len", str(SEQ_LEN),
        "--per-node-batch", str(PER_NODE_BATCH),
        "--heterogeneity", str(heterogeneity),
        "--log-every", "1",
    ]


def count_custom_calls(hlo_text: str) -> int:
    return hlo_text.count('custom_call_target="tpu_custom_call"')


def max_ulps(got, want) -> tuple[float, str]:
    """Worst leaf error in f32 ulps of that leaf's largest |value|."""
    import jax
    import numpy as np

    worst, where = 0.0, ""
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    for (path, a), b in zip(flat_got, jax.tree.leaves(want)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = float(np.max(np.abs(b))) * float(np.finfo(np.float32).eps)
        err = float(np.max(np.abs(a - b))) / max(scale, np.finfo(np.float32).tiny)
        if err > worst:
            worst, where = err, jax.tree_util.keystr(path)
    return worst, where


def run_step(train, impl: str, **kw):
    """Phase b/c/f body: train STEPS steps, report, return the result."""
    import jax

    res = train.run(step_argv(impl, **kw))
    check(len(res.losses) == STEPS, f"{impl}: {len(res.losses)} steps ran")
    check(all(math.isfinite(x) for x in res.losses),
          f"{impl}: non-finite loss {res.losses}")
    n_custom = count_custom_calls(res.compiled.as_text())
    stats = jax.devices()[0].memory_stats() or {}
    ma = res.compiled.memory_analysis()
    print(f"{impl}: compile_s={res.compile_s:.3f} step_s={res.step_s:.5f} "
          f"losses={res.losses} peak_bytes={stats.get('peak_bytes_in_use')} "
          f"program_bytes(args/out/temp/alias)={ma.argument_size_in_bytes}/"
          f"{ma.output_size_in_bytes}/{ma.temp_size_in_bytes}/"
          f"{ma.alias_size_in_bytes} tpu_custom_calls={n_custom}", flush=True)
    return res, n_custom


def phase_one_chip(train) -> None:
    import jax

    # (b) the kernel path
    res, n_custom = run_step(train, "pallas")
    check(n_custom > 0, "no tpu_custom_call in the compiled step: the fused "
          "Pallas update is not in the program")
    params_pallas = jax.device_get(res.state["params"])
    del res
    gc.collect()

    # (c) the reference tail, same seed and batches
    res, _ = run_step(train, "ref")
    params_ref = jax.device_get(res.state["params"])
    del res
    gc.collect()
    ulps, leaf = max_ulps(params_pallas, params_ref)
    print(f"params pallas vs ref: max {ulps:.3f} ulps of the leaf max "
          f"(at {leaf}; tolerance {PARAM_ULPS})", flush=True)
    check(ulps <= PARAM_ULPS, f"pallas vs ref params differ by {ulps} ulps")

    # (d) flash attention
    phase_flash()


def phase_flash() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import reference_attention

    s = FLASH_SHAPE
    kq, kk, kv = jax.random.split(jax.random.key(SEED), 3)
    q = jax.random.normal(
        kq, (s["batch"], s["seq"], s["heads"], s["head_dim"]), jnp.bfloat16)
    k = jax.random.normal(
        kk, (s["batch"], s["seq"], s["kv_heads"], s["head_dim"]), jnp.bfloat16)
    v = jax.random.normal(
        kv, (s["batch"], s["seq"], s["kv_heads"], s["head_dim"]), jnp.bfloat16)
    out = np.asarray(flash_attention(q, k, v, causal=True), np.float32)
    attend = jax.jit(reference_attention)
    ref = np.asarray(attend(q, k, v), np.float32)
    check(out.shape == ref.shape and bool(np.isfinite(out).all()),
          f"flash output shape {out.shape} or non-finite values")
    # sum_j p_j |v_j| per output element, bf16-rounded like the outputs
    abs_ref = np.asarray(attend(q, k, jnp.abs(v)), np.float32)
    err = np.abs(out - ref)
    bound = (FLASH_REL * abs_ref + FLASH_OUT_REL * np.abs(ref)
             + np.finfo(np.float32).tiny)
    worst = np.unravel_index(np.argmax(err / bound), err.shape)
    excess = float(err[worst] / bound[worst])
    print(f"flash_attention vs ref: max_abs_err={float(err.max()):.6g} "
          f"max_ref={float(np.abs(ref).max()):.6g} worst: err={float(err[worst]):.6g} "
          f"ref={float(ref[worst]):.6g} attn|v|={float(abs_ref[worst]):.6g} "
          f"bound_used={excess:.4f} (<= 1 passes)", flush=True)
    check(excess <= 1.0, f"flash attention off by {excess}x its bound")


def phase_four_chips(train) -> None:
    import jax

    res, _ = run_step(train, "pallas", topology="ring", heterogeneity=0.5)
    placement = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(res.state["params"]):
        for shard in leaf.addressable_shards:
            node = shard.index[0].start or 0
            placement.setdefault(node, set()).add(shard.device.id)
    print(f"node -> device ids: {dict(sorted(placement.items()))}", flush=True)
    check(sorted(placement) == [0, 1, 2, 3]
          and all(len(d) == 1 for d in placement.values())
          and len({d for ds in placement.values() for d in ds}) == 4,
          f"the four nodes are not on four distinct devices: {placement}")
    mesh = res.mesh
    del res
    gc.collect()
    phase_gossip_round(mesh)


def phase_gossip_round(mesh) -> None:
    """One PpermuteChannel round on the mesh vs the stacked einsum oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import StackedChannel, build_topology
    from repro.core.gossip import PpermuteChannel

    n = mesh.shape["data"]
    topology = build_topology("ring", n)
    keys = jax.random.split(jax.random.key(SEED), 2)
    tree = {
        "plane": jax.random.normal(keys[0], (n, 2048, 1024), jnp.float32),
        "odd": jax.random.normal(keys[1], (n, 13, 7), jnp.float32),
    }
    chan = PpermuteChannel(topology, ("data",))

    def node_round(t):
        local = jax.tree.map(lambda x: x[0], t)
        _, mixed = chan.apply(chan.init(local), local, 0)
        return jax.tree.map(lambda x: x[None], mixed)

    spec = {k: P("data", *([None] * (v.ndim - 1))) for k, v in tree.items()}
    on_mesh = jax.device_put(
        tree, {k: NamedSharding(mesh, s) for k, s in spec.items()})
    mixed = jax.jit(jax.shard_map(
        node_round, mesh=mesh, in_specs=(spec,), out_specs=spec))(on_mesh)

    oracle = StackedChannel(topology)
    one = jax.device_put(tree, jax.devices()[0])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda t: oracle.apply(oracle.init(t), t, 0)[1])(one)
    ulps, leaf = max_ulps(jax.device_get(mixed), jax.device_get(want))
    print(f"ppermute round vs stacked oracle: max {ulps:.3f} ulps of the "
          f"leaf max (at {leaf}; tolerance {MIX_ULPS})", flush=True)
    check(ulps <= MIX_ULPS, f"mesh gossip differs from the oracle by {ulps}")
    check(all(bool(np.isfinite(np.asarray(x)).all())
              for x in jax.tree.leaves(jax.device_get(mixed))),
          "non-finite gossip output")


POISON_TOKEN, ZERO_TOKEN = 1, 2  # (g) batches made of one token id


def phase_guard(impl: str = "pallas") -> None:
    """(g) One non-finite step through the guarded update kernels."""
    import jax.numpy as jnp

    from repro.configs import tiny_lm
    from repro.core.schedules import ScheduleConfig
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as T
    from repro.train.step import TrainConfig

    cfg = tiny_lm()
    tcfg = TrainConfig(
        algorithm="decentlam", topology="ring", momentum=0.9,
        flat_planes=True, fused_update=True, fused_impl=impl,
        schedule=ScheduleConfig(kind="constant", peak_lr=1e-2),
    )
    forward_loss = T.forward_loss

    def scaled_loss(params, batch, *a, **kw):
        loss, metrics = forward_loss(params, batch, *a, **kw)
        tok = batch["tokens"]
        scale = jnp.where(jnp.all(tok == POISON_TOKEN), jnp.inf,
                          jnp.where(jnp.all(tok == ZERO_TOKEN), 0.0, 1.0))
        return loss * scale, metrics

    mesh = make_mesh((1, 1), ("data", "model"))
    T.forward_loss = scaled_loss  # read when the step is traced
    try:
        _guard_steps(cfg, tcfg, mesh, impl)
    finally:
        T.forward_loss = forward_loss


def _guard_steps(cfg, tcfg, mesh, impl: str) -> None:
    """(g) body: the steps and the checks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.optimizers import make_optimizer
    from repro.train.step import build_train_step
    from repro.train.train_state import init_train_state, model_plane_layout

    step, _, _, channel = build_train_step(cfg, tcfg, mesh)
    state = init_train_state(
        jax.random.key(SEED), cfg, make_optimizer(tcfg.opt_config()), 1, 1,
        mesh=mesh, node_axes=("data",), channel=channel,
        plane_layout=model_plane_layout(cfg, 1),
    )
    rng = np.random.default_rng(SEED)

    def batch(tokens):
        return {"tokens": jnp.asarray(tokens, jnp.int32),
                "targets": jnp.asarray(np.roll(tokens, -1, 1), jnp.int32)}

    def random_batch():
        return batch(rng.integers(3, cfg.vocab_size, (PER_NODE_BATCH, 128)))

    def const_batch(tok):
        return batch(np.full((PER_NODE_BATCH, 128), tok))

    def copy(tree):  # the step donates its state
        return jax.tree.map(jnp.copy, tree)

    def bits(tree):
        return [np.asarray(a).tobytes() for a in jax.tree.leaves(tree)]

    n_custom = count_custom_calls(
        step.lower(state, random_batch()).compile().as_text())
    state, m0 = step(state, random_batch())  # momentum != 0 from here
    check(float(m0["skipped_nonfinite"]) == 0.0, "guard tripped on finite grads")
    m_before = bits(state["opt"]["m"])
    zero_state, mz = step(copy(state), const_batch(ZERO_TOKEN))
    bad_state, mb = step(copy(state), const_batch(POISON_TOKEN))
    skipped = float(mb["skipped_nonfinite"])
    m_kept = bits(bad_state["opt"]["m"]) == m_before
    x_g0 = bits(bad_state["params"]) == bits(zero_state["params"])
    next_state, mn = step(bad_state, random_batch())
    trains = (float(mn["skipped_nonfinite"]) == 0.0
              and math.isfinite(float(mn["loss"]))
              and bits(next_state["opt"]["m"]) != m_before)
    print(f"guard ({impl}): tpu_custom_calls={n_custom} "
          f"skipped_nonfinite={skipped} momentum_unchanged={m_kept} "
          f"params_equal_g0_step={x_g0} next_step_trains={trains} "
          f"next_loss={float(mn['loss']):.6g}", flush=True)
    check(impl != "pallas" or n_custom > 0, "no tpu_custom_call in the step")
    check(skipped == 1.0, f"skipped_nonfinite = {skipped} on non-finite grads")
    check(m_kept, "the momentum planes changed on a skipped step")
    check(x_g0, "the params of the skipped step differ from the g = 0 step's")
    check(trains, "the step after the skipped one did not train")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="1: phases a-e and g on one chip; 4: phase f only")
    args = p.parse_args(argv)

    # (a) device check, before anything else runs
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: FAIL: JAX found no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: FAIL: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    print(f"device: {devices[0].device_kind} x {len(devices)}", flush=True)

    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.launch import train
        from repro.launch.compile_cache import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: FAIL: the repro package is not beside this "
              f"script ({e})", file=sys.stderr)
        return 1
    print(f"compile cache: {configure_compile_cache()}", flush=True)

    try:
        if args.chips == 1:
            phase_one_chip(train)
            phase_guard()
        else:
            phase_four_chips(train)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1

    # (e) the result line
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
