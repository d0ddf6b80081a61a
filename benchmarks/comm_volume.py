"""Paper Fig. 6 analogue: per-iteration communication cost per algorithm.

Fig. 6 measures wall-clock with 10/25 Gbps Ethernet between 8-GPU servers;
here the hardware is a TPU pod, so we report the *analytic* per-node egress
bytes + latency hops of each algorithm's communication pattern (reported
from ``GossipChannel.bytes_per_step`` and cross-checked against the legacy
``core.gossip.gossip_bytes_per_step`` model).

Model sizes: ResNet-50 (25.5M, the paper's) + the assigned qwen3-0.6b /
qwen3-8b.  Emits CSV rows: name, payload_mb, egress_mb, hops, est_ms_at_25gbps.
"""

from __future__ import annotations

from repro.core import PpermuteChannel, build_topology


def _channel_bytes(topo, payload, compression=None):
    """Bytes from the channel API, cross-checked against an independent
    re-derivation of the Fig. 6 analytic model.

    ``Channel.bytes_per_step`` delegates to ``gossip_bytes_per_step``, so
    comparing those two would be vacuous; instead the expectation is
    rebuilt here from first principles (mean edge-class sends per phase x
    wire bytes per payload).  A divergence means the channel's byte
    accounting — its impl/compression plumbing or the shared formula —
    regressed, and raises instead of silently reporting either number.
    """
    import numpy as np

    from repro.core import wire_bytes

    ch = PpermuteChannel(topo, ("data",), compression=compression)
    got = ch.bytes_per_step(payload)
    sends = float(np.mean(
        [len(topo.edge_classes(t)) for t in range(topo.period)]
    ))
    expected = {
        "egress_bytes": sends * wire_bytes(payload, compression),
        "hops": sends,
    }
    for key in ("egress_bytes", "hops"):
        if abs(got[key] - expected[key]) > 1e-6 * max(1.0, abs(expected[key])):
            raise AssertionError(
                f"channel bytes_per_step diverged from the analytic model on "
                f"{topo.name}/{key}: {got[key]} != {expected[key]}"
            )
    return got


MODELS = {
    "resnet50": 25.5e6,
    "qwen3-0.6b": 0.6e9,
    "qwen3-8b": 8.0e9,
}
N = 16
BW = 25e9 / 8  # 25 Gbps in bytes/s (the paper's fabric)


def run(csv: bool = True):
    rows = []
    for mname, params in MODELS.items():
        payload = params * 4.0  # fp32 payload
        # PmSGD: ring all-reduce of gradients
        ar_bytes = 2 * (N - 1) / N * payload
        rows.append((f"{mname}/pmsgd-allreduce", payload, ar_bytes, 2 * (N - 1)))
        for topo_name in ("ring", "exp", "one-peer-exp"):
            topo = build_topology(topo_name, N)
            g = _channel_bytes(topo, payload)
            rows.append(
                (f"{mname}/decentlam-{topo_name}", payload, g["egress_bytes"], g["hops"])
            )
        g = _channel_bytes(
            build_topology("one-peer-exp", N), payload, compression="int8"
        )
        rows.append((f"{mname}/decentlam-one-peer+int8", payload, g["egress_bytes"], g["hops"]))

    if csv:
        print("name,payload_mb,egress_mb,hops,est_ms_at_25gbps")
        for name, payload, egress, hops in rows:
            print(
                f"comm/{name},{payload/2**20:.1f},{egress/2**20:.1f},{hops},"
                f"{egress/BW*1e3:.1f}"
            )

    return rows


if __name__ == "__main__":
    run()
