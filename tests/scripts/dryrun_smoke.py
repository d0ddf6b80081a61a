"""Subprocess worker: one real dry-run cell end-to-end (guards deliverable e).

Runs with 512 simulated devices (set by the pytest wrapper's XLA_FLAGS);
whisper-tiny is the cheapest arch, so one train and one decode cell compile
in ~30 s total.  Asserts the roofline record is well-formed.
"""

import os

assert "512" in os.environ.get("XLA_FLAGS", ""), "wrapper must set 512 devices"

import types

from repro.launch.dryrun import run_cell


def args(**kw):
    base = dict(
        algorithm="decentlam", topology="exp", gossip_impl="ppermute",
        compression=None, grad_accum=0, remat=True, remat_policy="full",
        mlstm_chunk=128, ssm_chunk=128, fused_update=False,
        decode_grouped_gqa=False, gossip_serialize=True,
    )
    base.update(kw)
    return types.SimpleNamespace(**base)


for shape, mesh in [("train_4k", "pod1"), ("decode_32k", "pod2")]:
    rec = run_cell("whisper-tiny", shape, mesh, args())
    assert rec["status"] == "ok", rec
    t = rec["roofline"]
    assert t["compute_s"] > 0 and t["memory_s"] > 0
    assert t["dominant"] in ("compute", "memory", "collective")
    assert rec["memory"]["temp_bytes"] > 0
    assert rec["collectives"]["egress_bytes"] > 0
    print(f"{shape}@{mesh}: dominant={t['dominant']} OK")

skip = run_cell("whisper-tiny", "long_500k", "pod1", args())
assert skip["status"] == "skipped"
print("skip rule OK")
