"""Checkpoint/restart + elastic rescale tests (incl. channel-state resume)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import tiny_lm
from repro.core import (
    DelayedStackedChannel,
    OptimizerConfig,
    build_topology,
    make_linear_regression,
    make_optimizer,
    make_stacked_mean,
)
from repro.core.planes import LANES, PlaneLayout
from repro.train.checkpoint import (
    elastic_reshape,
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.train.train_state import ensure_channel_state, init_train_state


def _state(n_nodes=4, step=7):
    cfg = tiny_lm(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                  vocab_size=128)
    opt = make_optimizer(OptimizerConfig(algorithm="decentlam"))
    st = init_train_state(jax.random.key(0), cfg, opt, n_nodes, tp=1)
    st["step"] = jnp.int32(step)
    # make replicas distinct so restore/collapse are meaningful
    st["params"] = jax.tree.map(
        lambda x: x + jnp.arange(x.shape[0], dtype=x.dtype).reshape(
            (-1,) + (1,) * (x.ndim - 1)
        ),
        st["params"],
    )
    return st


def _layout_of(st):
    """The plane layout of a stacked state's per-node params."""
    return PlaneLayout.build(jax.tree.map(lambda a: a[0], st["params"]))


def _save(d, st, **kw):
    return save_checkpoint(d, st, plane_layout=_layout_of(st), **kw)


def test_save_restore_bit_exact(tmp_path):
    st = _state()
    d = str(tmp_path / "ckpt")
    _save(d, st, metadata={"topology": "exp"})
    assert latest_step(d) == 7
    restored, manifest = restore_checkpoint(d)
    assert manifest["step"] == 7
    assert manifest["topology"] == "exp"
    assert manifest["plane_tp"] == 1  # every manifest carries its layout
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_v3_dtype_manifest_roundtrip(tmp_path):
    """V3 checkpoints declare every bucket's dtype in the manifest and
    restore non-npz-native dtypes (bf16 plane buffers, bool sparse-gossip
    row masks) by declaration, bit-exact."""
    import ml_dtypes

    st = _state()
    st["params"]["bf16_plane"] = jnp.arange(24, dtype=jnp.bfloat16).reshape(4, 6)
    st["channel"] = {"rows": {"dirty": jnp.asarray(
        np.arange(12).reshape(4, 3) % 2 == 0
    )}}
    d = str(tmp_path / "ckpt")
    _save(d, st)
    restored, manifest = restore_checkpoint(d)
    assert manifest["format"] == 3
    assert manifest["dtypes"]["params/bf16_plane"] == "bfloat16"
    assert manifest["dtypes"]["channel/rows/dirty"] == "bool"
    got = np.asarray(restored["params"]["bf16_plane"])
    assert got.dtype == np.dtype(ml_dtypes.bfloat16)
    np.testing.assert_array_equal(got, np.asarray(st["params"]["bf16_plane"]))
    np.testing.assert_array_equal(
        np.asarray(restored["channel"]["rows"]["dirty"]),
        np.asarray(st["channel"]["rows"]["dirty"]),
    )


def test_v3_fp8_plane_bucket_roundtrip(tmp_path):
    """fp8 plane buckets (float8_e4m3fn / float8_e5m2 — quantized gossip
    payload planes) survive the npz void round-trip bit-exactly: the V3
    manifest declares the dtype by name and restore reinterprets the
    1-byte voids, never sniffing."""
    import ml_dtypes

    st = _state()
    rng = np.random.default_rng(3)
    e4m3 = rng.standard_normal((4, 6)).astype(ml_dtypes.float8_e4m3fn)
    e5m2 = rng.standard_normal((64,)).astype(ml_dtypes.float8_e5m2)
    st["channel"] = {
        "comp": {
            "float8_e4m3fn": jnp.asarray(e4m3),
            "float8_e5m2": jnp.asarray(e5m2),
        }
    }
    d = str(tmp_path / "ckpt")
    _save(d, st)
    restored, manifest = restore_checkpoint(d)
    assert manifest["dtypes"]["channel/comp/float8_e4m3fn"] == "float8_e4m3fn"
    assert manifest["dtypes"]["channel/comp/float8_e5m2"] == "float8_e5m2"
    for name, want in (("float8_e4m3fn", e4m3), ("float8_e5m2", e5m2)):
        got = np.asarray(restored["channel"]["comp"][name])
        assert got.dtype == want.dtype
        # bit-exact: compare raw bytes (fp8 NaN payloads don't ==)
        np.testing.assert_array_equal(
            got.view(np.uint8), want.view(np.uint8)
        )


def test_unknown_manifest_dtype_rejected(tmp_path):
    """A manifest declaring a dtype neither numpy nor ml_dtypes knows is a
    corrupt/future checkpoint: restore fails with a clean ValueError
    instead of silently misreading the bytes."""
    import json
    import os

    import pytest

    st = _state(step=2)
    d = str(tmp_path / "ckpt")
    _save(d, st)
    mpath = os.path.join(d, "step_00000002", "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    key = next(iter(manifest["dtypes"]))
    manifest["dtypes"][key] = "float6_e3m2"
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(ValueError, match="unknown dtype 'float6_e3m2'"):
        restore_checkpoint(d)


def test_v2_checkpoint_migration(tmp_path):
    """A V2-era checkpoint (manifest without "format"/"dtypes", bf16 stored
    as numpy's opaque 2-byte void) must still restore its bf16 buffers —
    the legacy sniff stays in place behind the V3 declaration path."""
    import json
    import os

    import ml_dtypes

    st = _state(step=5)
    st["params"]["bf16_plane"] = (
        jnp.arange(24, dtype=jnp.bfloat16).reshape(4, 6) / 3
    )
    d = str(tmp_path / "ckpt")
    _save(d, st)
    # strip the checkpoint back to the V2 manifest shape on disk
    mpath = os.path.join(d, "step_00000005", "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    del manifest["format"], manifest["dtypes"]
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    restored, manifest = restore_checkpoint(d)
    assert "dtypes" not in manifest
    got = np.asarray(restored["params"]["bf16_plane"])
    assert got.dtype == np.dtype(ml_dtypes.bfloat16)
    np.testing.assert_array_equal(got, np.asarray(st["params"]["bf16_plane"]))
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_atomic_overwrite(tmp_path):
    st = _state(step=3)
    d = str(tmp_path / "ckpt")
    _save(d, st)
    st["step"] = jnp.int32(9)
    _save(d, st)
    assert latest_step(d) == 9
    restored, _ = restore_checkpoint(d, step=3)
    assert int(restored["step"]) == 3


def test_elastic_shrink_and_grow():
    st = _state(n_nodes=4)
    shrunk = elastic_reshape(st, 2)
    grown = elastic_reshape(st, 8)
    for src, s2, s8 in zip(
        jax.tree.leaves(st["params"]),
        jax.tree.leaves(shrunk["params"]),
        jax.tree.leaves(grown["params"]),
    ):
        assert s2.shape[0] == 2 and s8.shape[0] == 8
        mean = np.asarray(src, np.float32).mean(axis=0)
        # every new replica equals the consensus average
        np.testing.assert_allclose(np.asarray(s2[0], np.float32), mean, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(s8[-1], np.float32), mean, rtol=1e-5)


def test_elastic_then_restart_roundtrip(tmp_path):
    st = _state(n_nodes=4)
    d = str(tmp_path / "c")
    _save(d, st)
    restored, _ = restore_checkpoint(d)
    resized = elastic_reshape(restored, 8)
    _save(str(tmp_path / "c2"), resized)
    again, _ = restore_checkpoint(str(tmp_path / "c2"))
    assert jax.tree.leaves(again["params"])[0].shape[0] == 8


# ---------------------------------------------------------------------------
# GossipChannel state: save/restore round-trip + resume equality
# ---------------------------------------------------------------------------


def _delayed_run(n_steps, state=None):
    """A stacked DmSGD run whose channel carries BOTH state kinds: delay
    ring buffers (delay=2) and top-k error feedback, plus telemetry."""
    n = 4
    prob = make_linear_regression(n=n, m=6, d=5, noise=0.01, seed=2)
    topo = build_topology("ring", n)
    opt = make_optimizer(OptimizerConfig(algorithm="dmsgd", momentum=0.8))
    channel = DelayedStackedChannel(
        topo, 2, compression="topk:0.5", telemetry=True
    )
    mean = make_stacked_mean(n)

    @jax.jit
    def one(params, opt_state, chstate, k):
        grads = prob.grad(params)
        return opt.step(
            params, grads, opt_state, lr=jnp.float32(1e-2), step_idx=k,
            gossip=channel, mean=mean, comp_state=chstate,
        )

    if state is None:
        params = jnp.zeros((n, prob.dim), jnp.float32)
        opt_state = opt.init(params)
        chstate = channel.init(params)
        start = 0
    else:
        params, opt_state, chstate = (
            state["params"], state["opt"], state["channel"],
        )
        start = int(state["step"])
    for k in range(start, start + n_steps):
        params, opt_state, chstate = one(params, opt_state, chstate, jnp.int32(k))
    return {
        "step": jnp.int32(start + n_steps),
        "params": params,
        "opt": opt_state,
        "channel": chstate,
    }


def test_channel_state_roundtrip_bit_exact(tmp_path):
    st = _delayed_run(3)
    assert set(st["channel"]) == {"t", "comp", "delay"}
    d = str(tmp_path / "ck")
    _save(d, st)
    restored, _ = restore_checkpoint(d)
    assert jax.tree.structure(restored["channel"]) == jax.tree.structure(
        st["channel"]
    )
    for a, b in zip(jax.tree.leaves(st["channel"]), jax.tree.leaves(restored["channel"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_channel_state_resume_equality(tmp_path):
    """Resume from a checkpoint mid-run == the uninterrupted run, bit-exact
    — delay ring buffers and error-feedback residuals survive the restart."""
    st3 = _delayed_run(3)
    d = str(tmp_path / "ck")
    _save(d, st3)
    restored, _ = restore_checkpoint(d)
    resumed = _delayed_run(3, state=restored)
    straight = _delayed_run(6)
    for a, b in zip(jax.tree.leaves(resumed), jax.tree.leaves(straight)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ensure_channel_state_reconciles_legacy_and_fresh():
    """Resume reconciliation for the distributed TrainState layout: old
    checkpoints (no/partial channel bucket) zero-init cleanly, matching
    leaves are preserved, reshaped ones re-init."""
    from repro.core import DelayedPpermuteChannel, PpermuteChannel

    n, d = 4, 5
    topo = build_topology("ring", n)
    params = {"w": jnp.zeros((n, d), jnp.float32)}
    lay = PlaneLayout.build({"w": jnp.zeros((d,), jnp.float32)})
    plane = (lay.rows["float32"], LANES)  # the channel's payload: planes
    channel = PpermuteChannel(
        topo, ("data",), compression="topk:0.5", telemetry=True
    )
    fixed = ensure_channel_state(
        {"params": params, "channel": {}}, channel, n, lay
    )
    assert set(fixed["channel"]) == {"t", "comp"}
    assert fixed["channel"]["comp"]["float32"].shape == (n, *plane)
    assert fixed["channel"]["t"]["rounds"].shape == (n,)

    # a populated matching bucket survives reconciliation untouched
    populated = {
        "t": {
            "rounds": jnp.arange(n, dtype=jnp.int32),
            "bytes": jnp.ones((n,), jnp.float32),
        },
        "comp": {"float32": jnp.ones((n, *plane), jnp.float32)},
    }
    kept = ensure_channel_state(
        {"params": params, "channel": populated}, channel, n, lay
    )
    for a, b in zip(jax.tree.leaves(populated), jax.tree.leaves(kept["channel"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # switching on a delay re-inits the (new) ring buffers but keeps nothing
    # stale: the delayed channel has fresh zeroed history + counts
    delayed = DelayedPpermuteChannel(topo, ("data",), 2, telemetry=True)
    fixed2 = ensure_channel_state(
        {"params": params, "channel": populated}, delayed, n, lay
    )
    assert set(fixed2["channel"]) == {"t", "delay"}
    assert fixed2["channel"]["delay"]["s0"]["hist"]["float32"].shape == (
        n, 3, *plane)
    assert int(np.max(np.asarray(fixed2["channel"]["delay"]["s0"]["count"]))) == 0

    # delay slots resume ATOMICALLY: a checkpoint from --gossip-delay 2
    # restored under --gossip-delay 3 must not keep the old count while the
    # resized hist re-inits (that would skip warmup and mix zero payloads)
    old_slot = {
        "delay": {
            "s0": {
                "hist": {"float32": jnp.ones((n, 3, *plane), jnp.float32)},
                "count": jnp.full((n,), 7, jnp.int32),
            }
        }
    }
    delayed3 = DelayedPpermuteChannel(topo, ("data",), 3, telemetry=True)
    fixed3 = ensure_channel_state(
        {"params": params, "channel": old_slot}, delayed3, n, lay
    )
    slot = fixed3["channel"]["delay"]["s0"]
    assert slot["hist"]["float32"].shape == (n, 4, *plane)
    assert int(np.max(np.asarray(slot["count"]))) == 0  # count reset with hist
    # same-shape slots survive untouched (count AND hist together)
    fixed2b = ensure_channel_state(
        {"params": params, "channel": old_slot},
        DelayedPpermuteChannel(topo, ("data",), 2, telemetry=True), n, lay,
    )
    slot2 = fixed2b["channel"]["delay"]["s0"]
    assert int(np.max(np.asarray(slot2["count"]))) == 7
    np.testing.assert_array_equal(np.asarray(slot2["hist"]["float32"]), 1.0)
