"""Multi-device integration tests via subprocess (the main pytest process
keeps 1 CPU device; these workers get 8 simulated devices)."""

import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.distributed, pytest.mark.slow]

SCRIPTS = os.path.join(os.path.dirname(__file__), "scripts")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str, *args, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, script), *args],
        capture_output=True,
        text=True,
        timeout=900,
        env=env,
    )
    assert proc.returncode == 0, f"\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


@pytest.mark.parametrize(
    "mode", ["baseline", "allgather", "compressed", "one-peer", "fused", "topk"]
)
def test_distributed_train_equivalence(mode):
    out = _run("distributed_equivalence.py", mode)
    assert "OK" in out


@pytest.mark.parametrize("mode", ["planes", "planes-delayed", "planes-tp"])
def test_flat_planes_shard_map_parity_and_collective_count(mode):
    """The flat-plane step's trajectory matches the stacked per-leaf
    ``opt.step`` oracle on a real 8-device mesh, and its lowered jaxpr
    carries exactly O(dtype-buckets x edge-classes) ppermutes — the
    collective-count claim, measured on the actual program.  "planes-tp"
    reruns the claim on a 4-node x 2-way-TP mesh with the *sharded* layout
    (decentlam + delay-2 decentlam-sa): per-rank local buckets, ppermute
    count equal to the tp=1 collapse."""
    out = _run("distributed_equivalence.py", mode)
    assert f"{mode}: OK" in out


def test_sparse_gossip_train_step_end_to_end():
    """Row-sparse gossip on the production train step (granite-moe SMOKE,
    flat planes): forced dense-fallback is bit-exact with the dense channel
    end-to-end, and tracked sparsity ships measurably fewer bytes."""
    out = _run("distributed_equivalence.py", "sparse")
    assert "sparse: OK bit-exact under forced fallback" in out


def test_sparse_mesh_channels_match_dense_parents():
    """Channel-level mesh pins: all 11 algorithms all-dirty == dense parents
    (exact + delta + int8 + delayed), partial masks match the stacked sparse
    reference with clean rows bit-frozen, collective accounting."""
    out = _run("sparse_distributed.py")
    from repro.core.optimizers import ALGORITHMS

    assert out.count("A ") == len(ALGORITHMS) + 3  # + drift line, int8, delayed
    assert "B exact: OK" in out and "B delta: OK" in out
    assert "B exact-delay2: OK" in out
    assert "C collectives: OK" in out
    assert "sparse-distributed: OK" in out


def test_delayed_ppermute_channel():
    """The redesign's headline capability: a stale_gossip_k2 scenario through
    the shard_map DelayedPpermuteChannel matches the simulator's SSP
    trajectory (DSGD + DmSGD + staleness-aware DecentLaM), and delay-0
    channels are bit-exact with the pre-redesign ppermute gossip for all 11
    algorithms."""
    out = _run("distributed_delayed.py")
    assert "A dsgd: OK" in out and "A dmsgd: OK" in out
    assert "A decentlam-sa: OK" in out
    from repro.core.optimizers import ALGORITHMS

    assert out.count("(bit-exact)") == len(ALGORITHMS)
    # part C: consensus gate off the live mesh channel's fleet_node_gaps —
    # only the warmup rounds (gap <= threshold) ship, nothing after
    assert "C gate: OK (published 2/6 warmup rounds only)" in out
    assert f"delayed-ppermute: OK ({3 + len(ALGORITHMS)} cases)" in out


def test_resilience_fault_tolerant_runtime():
    """The fault-tolerant gossip runtime on a live mesh: (A) a mesh that
    loses nodes 0-1 and rescales per plan_recovery tracks the simulator's
    failstop_quarter trajectory, (B) ResilientChannel(ChaosChannel(ch,
    empty-schedule)) is bit-exact with the bare channel for all 11
    algorithms, (C) a seeded drop + NaN-inject + churn soak stays finite,
    quarantines the poison, declares/resurrects the silent peer through
    the HealthMonitor, rejoins it checkpoint-free from a WeightPublisher
    snapshot, and converges with bounded bias."""
    out = _run("resilience_distributed.py")
    assert "A dsgd: OK" in out and "A dmsgd: OK" in out
    assert "A decentlam-sa: OK" in out
    from repro.core.optimizers import ALGORITHMS

    assert out.count("(bit-exact)") == len(ALGORITHMS)
    assert "C soak: OK" in out
    assert f"resilience-distributed: OK ({3 + len(ALGORITHMS) + 1} cases)" in out


def test_distributed_serve_matches_oracle():
    out = _run("distributed_serve.py")
    assert out.count("OK") == 4


def test_train_driver_checkpoint_resume():
    """The CLI driver end-to-end: train 8 steps, checkpoint, resume to 16."""
    out = _run("driver_resume.py", devices=4)
    assert "driver resume OK" in out
