"""Gossip transports: the :class:`GossipChannel` API (+ legacy closures).

All communication of the paper's partial-averaging operator
``x_i <- sum_j w_ij x_j`` (eq. (3)) goes through one protocol — a *channel*
is a static, registered-pytree object bundling topology, compression, and
staleness config, whose dynamic state (compression error-feedback, delay
ring buffers, telemetry) is a single checkpointable pytree::

    channel.init(template)              -> state          # zeros / residuals
    channel.apply(state, tree, step)    -> (state, tree)  # one gossip round
    channel.bytes_per_step(payload[, state]) -> {egress_bytes, hops}
    channel.version_gaps(state)         -> (n, n) int32   # per-edge staleness
    channel.state_specs(param_specs)    -> per-node PartitionSpec tree

Implementations:

* :class:`StackedChannel`        — reference oracle: leaves carry a leading
  node axis ``(n, ...)`` and gossip is a dense ``W @`` einsum.  Optional
  per-node message compression (encode/decode around the mix) so the sim
  can sweep compression x staleness without a mesh.
* :class:`DelayedStackedChannel` — stacked gossip with per-edge delay ring
  buffers (``x_i <- w_ii x_i(t) + sum_j w_ij x_j(t - d_ij)``), the bounded-
  staleness model the cluster simulator's ``stale_gossip_k*`` scenarios use.
  At uniform delay 0 it runs the exact :class:`StackedChannel` code path.
* :class:`PpermuteChannel`       — production: runs *inside* a fully-manual
  ``jax.shard_map``; each topology edge class is one ``jax.lax.ppermute``
  (TPU collective-permute).  Optional bf16 / int8 / top-k+EF compression.
* :class:`DelayedPpermuteChannel`— the same wire path with a per-node ring
  buffer of past payloads held ``k`` steps in device memory, so the sim's
  SSP staleness scenarios run on real meshes.  Delay 0 runs the exact
  :class:`PpermuteChannel` code path.
* :class:`AllgatherChannel`      — the naive distributed baseline (what
  GSPMD would do): all-gather the payload, reduce with this node's W row.
  O(n) bandwidth instead of O(degree); kept as the §Perf baseline.

The pre-redesign closure *protocol* (``gossip(tree, step, comp_state) ->
(tree, comp_state)``) is still accepted by ``run_update`` for ad-hoc
transports (test oracles); the deprecated factory shims that produced such
closures (``make_*_gossip``, ``init_compression_state``) were removed after
their one-release grace period — construct a channel instead.

**Flat plane payloads.**  Every channel is payload-structure generic, so
the train step's flat-plane update needs no separate transport: hand
``apply`` a :class:`~repro.core.planes.PlaneLayout` payload (one
contiguous f32 buffer per dtype bucket) and each gossip round issues
one collective per **bucket** per edge class instead of one per pytree leaf
— and ``init`` on a plane template moves the delay ring buffers and the
compression error-feedback residuals into the same contiguous layout (one
ring / one residual per bucket).  ``collectives_per_round`` is the analytic
count the benchmarks/CI gate against.  Note that per-tensor compressors
(int8 absmax, top-k) then operate on the whole bucket rather than per leaf:
int8 uses one global scale and top-k selects across the entire plane — a
deliberate semantic change of the packed wire format (error feedback still
applies, now over plane residuals).

On a *sharded* plane layout (tensor parallelism, tp > 1) the payload a
channel sees inside shard_map is the mesh column's LOCAL bucket set —
``(local_rows, LANES)`` per dtype — so gossip ships per-rank shards over
the **node axes only** (the model axis never enters a channel collective)
and the shape-derived accounting (``_payload_nbytes`` ->
``bytes_per_step`` and ``collectives_per_round``) is automatically
*per-rank*: bytes scale with the local shard rows, collective counts stay
O(buckets x edge classes) per rank, identical to the tp == 1 collapse.

Time-varying topologies (one-peer exponential, bipartite random match) cycle
through their period with ``lax.switch`` so the step stays a single jitted
computation.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .compression import Compressor, get_compressor, wire_bytes
from .topology import Topology

Tree = Any
GossipFn = Callable[[Tree, jax.Array, Tree], tuple[Tree, Tree]]

__all__ = [
    "GossipChannel",
    "StackedChannel",
    "DelayedStackedChannel",
    "PpermuteChannel",
    "DelayedPpermuteChannel",
    "AllgatherChannel",
    "build_channel",
    "delay_matrix",
    "fleet_node_gaps",
    "make_stacked_mean",
    "make_psum_mean",
    "gossip_bytes_per_step",
]


def delay_matrix(n: int, delay) -> np.ndarray:
    """Normalize a delay spec (int or ``(n, n)`` array) to an int matrix with
    a zero diagonal (self-contributions are never stale)."""
    if np.isscalar(delay):
        D = np.full((n, n), int(delay), dtype=np.int64)
    else:
        D = np.asarray(delay, dtype=np.int64).copy()
        assert D.shape == (n, n), f"delay matrix must be ({n}, {n})"
    assert (D >= 0).all(), "delays must be non-negative"
    np.fill_diagonal(D, 0)
    return D


def _register_static(cls):
    """Channels are static config: flatten to no leaves, carry self as aux."""
    jax.tree_util.register_pytree_node(cls, lambda c: ((), c), lambda aux, _: aux)
    return cls


def _fresh_slot(template: Tree, ring: int) -> dict:
    hist = jax.tree.map(
        lambda x: jnp.zeros((ring,) + x.shape, jnp.float32), template
    )
    return {"hist": hist, "count": jnp.int32(0)}


def _rotate_slots(slots: dict, n_slots: int, new_slot: dict) -> dict:
    """Consume slot s0, shift the rest down, append the updated slot last —
    each gossip call within a step keeps its own independent history."""
    keys = [f"s{i}" for i in range(n_slots)]
    rotated = {keys[i]: slots[keys[i + 1]] for i in range(n_slots - 1)}
    rotated[keys[-1]] = new_slot
    return rotated


def _delayed_version_gaps(state: Tree, masked_D: np.ndarray) -> jax.Array:
    """Shared warmup-gap rule: count is post-apply, so the round just
    executed used ``d_eff = min(d, count - 1)`` (warmup reads the oldest
    recorded payload; round 0 is fresh)."""
    last = jnp.maximum(jnp.int32(state["delay"]["s0"]["count"]) - 1, 0)
    return jnp.minimum(jnp.asarray(masked_D, jnp.int32), last)


def _incident_gaps(gaps: jax.Array) -> jax.Array:
    """Per-node worst *incident*-edge gap from an ``(n, n)`` gap matrix —
    both directions (see :meth:`GossipChannel.node_gaps` for why the
    out-edge direction counts)."""
    return jnp.maximum(jnp.max(gaps, axis=1), jnp.max(gaps, axis=0))


def fleet_node_gaps(channel: "GossipChannel", state: Tree) -> np.ndarray:
    """Host-side ``(n,)`` per-node consensus gaps for the whole fleet.

    :meth:`GossipChannel.node_gaps` indexes the incident-gap vector by
    ``axis_index`` and is therefore only callable *inside* the shard_map
    region.  The serving publisher gates on the same signal from the
    training loop on the host, where the channel state is at hand either
    in stacked layout (the sim / oracle channels) or as the TrainState's
    ``"channel"`` bucket whose leaves carry a leading node axis.  This
    helper accepts both: distributed-channel states are un-stacked by
    taking node 0's replica (the ring-buffer ``count`` advances in
    lockstep on every node — it is the only leaf the gap rule reads).

    Returns the exact vector ``node_gaps`` would distribute: entry ``i``
    is the worst version gap on any edge incident to node ``i``, in
    either direction.  Staleness-free channels return all zeros.
    """
    n = channel.topology.n
    if not channel.has_staleness():
        return np.zeros(n, np.int32)
    if not channel._stacked_layout:
        state = jax.tree.map(lambda x: np.asarray(x)[0], state)
    return np.asarray(_incident_gaps(channel.version_gaps(state)), dtype=np.int32)


def _edge_mask(topology: Topology) -> np.ndarray:
    """Union over period phases of the off-diagonal gossip support."""
    mask = np.zeros((topology.n, topology.n), dtype=np.int64)
    for t in range(topology.period):
        W = topology.W(t)
        mask |= (np.abs(W - np.diag(np.diag(W))) > 0).astype(np.int64)
    return mask


class GossipChannel:
    """Stateful gossip transport (see module docstring for the protocol).

    Subclasses set ``topology``, ``compression`` / ``_compressor``,
    ``_telemetry`` and the byte-model ``_impl``, and implement ``apply`` +
    ``_init_extra``.  ``state`` is always a (possibly empty) dict pytree so
    it checkpoints through ``train.checkpoint`` unchanged.
    """

    name = "gossip"
    _impl = "ppermute"  # byte-accounting model (gossip_bytes_per_step impl)

    topology: Topology
    compression: str | None

    # -- shared plumbing ----------------------------------------------------

    def _setup(self, topology: Topology, compression: str | None, telemetry: bool):
        self.topology = topology
        self.compression = compression
        self._compressor: Compressor = get_compressor(compression)
        self._telemetry = bool(telemetry)
        # stateful compressors (top-k error feedback) carry a per-leaf
        # residual mirroring the payload; stateless ones return ()
        probe = self._compressor.init(np.zeros((1,), np.float32))
        self._stateful_comp = bool(jax.tree.leaves(probe))

    @staticmethod
    def _payload_nbytes(tree: Tree) -> float:
        """f32 wire size of one payload copy (static, from traced shapes)."""
        return 4.0 * sum(float(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    def _tick(self, state: dict, step, egress_bytes) -> dict:
        if "t" not in state:
            return state
        t = state["t"]
        state = dict(state)
        state["t"] = {
            "rounds": t["rounds"] + jnp.int32(1),
            "bytes": t["bytes"] + jnp.float32(egress_bytes),
        }
        return state

    _stacked_layout = False  # True when payload leaves carry the (n, ...) axis

    def _phase_bytes(self, tree: Tree) -> jax.Array:
        """Per-phase per-node egress bytes, indexable by ``step % period``."""
        nbytes = self._payload_nbytes(tree)
        if self._stacked_layout:
            nbytes /= self.topology.n
        per_payload = wire_bytes(nbytes, self.compression)
        sends = [
            len(self.topology.edge_classes(t)) for t in range(self.topology.period)
        ]
        return jnp.asarray([s * per_payload for s in sends], jnp.float32)

    # -- protocol -----------------------------------------------------------

    def init(self, template: Tree) -> dict:
        """Zero state for payloads shaped like ``template`` (per-node leaves
        for the distributed channels, stacked ``(n, ...)`` for the stacked
        ones)."""
        state: dict = {}
        if self._telemetry:
            state["t"] = {"rounds": jnp.int32(0), "bytes": jnp.float32(0.0)}
        state.update(self._init_extra(template))
        return state

    def _init_extra(self, template: Tree) -> dict:
        return {}

    def apply(self, state: Tree, tree: Tree, step) -> tuple[Tree, Tree]:
        raise NotImplementedError

    def _finish(self, state: Tree, tree: Tree, step, comp: Tree | None = None) -> Tree:
        """Shared post-round writeback: updated compression state (when the
        incoming state carries a ``"comp"`` node) + telemetry tick.  Non-dict
        states (legacy ``()`` passthrough) return unchanged."""
        if not isinstance(state, dict):
            return state
        if "comp" in state and comp is not None:
            state = dict(state)
            state["comp"] = comp
        if "t" in state:
            period = self.topology.period
            state = self._tick(state, step, self._phase_bytes(tree)[step % period])
        return state

    def bytes_per_step(
        self, payload_bytes: float, state: Tree | None = None
    ) -> dict[str, float]:
        """Per-node egress bytes + latency hops of one round.

        ``state`` is the channel state after some number of ``apply``
        rounds: channels whose wire volume is *state-dependent* (the
        row-sparse channels — dirty-row counts change every round) report
        the measured per-round average from it; fixed-payload channels
        ignore it and return the analytic count, which for them is exact.
        With ``state=None`` every channel returns the dense analytic
        volume — an upper bound for sparse channels, exact otherwise.
        """
        return gossip_bytes_per_step(
            self.topology, payload_bytes, impl=self._impl,
            compression=self.compression,
        )

    def collectives_per_round(self, payload: Tree, state: Tree | None = None) -> float:
        """Collective ops one ``apply`` issues for this payload (period mean).

        ``state`` follows the same contract as :meth:`bytes_per_step`:
        fixed-schedule channels ignore it; state-dependent channels may use
        it to report the realized count.

        The wire path ships one message *component* per payload leaf per
        edge class (compressors with multi-part messages — int8's
        ``{q, scale}``, top-k's ``{v, i}`` — permute each part), so the
        count is ``edge_classes x leaves x parts``.  This is what the flat
        plane path collapses: a :class:`~repro.core.planes.PlaneLayout`
        payload has one leaf per dtype bucket, making the count
        O(buckets x edge-classes) instead of O(leaves x edge-classes) —
        ``tests/scripts/distributed_equivalence.py`` cross-checks this
        number against the ppermutes actually present in the lowered
        jaxpr.  Stacked channels mix with a dense einsum (no collectives).
        """
        if self._stacked_layout:
            return 0.0
        n_leaves = len(jax.tree.leaves(payload))
        probe = jax.eval_shape(
            lambda x: self._compressor.encode(x, self._compressor.init(x))[0],
            jnp.zeros((2, 2), jnp.float32),
        )
        parts = len(jax.tree.leaves(probe))
        sends = np.mean(
            [len(self.topology.edge_classes(t)) for t in range(self.topology.period)]
        )
        return float(sends) * n_leaves * parts

    def has_staleness(self) -> bool:
        """Whether this transport can ever report a nonzero version gap.
        The base rule covers the built-in channels (a configured delay
        ring); wrappers that track liveness (the resilience layer's
        chaos-induced miss counters) override it so the gap plumbing —
        :meth:`node_gaps`, :func:`fleet_node_gaps`, the serving gate, the
        health monitor — sees their staleness without faking a delay."""
        return getattr(self, "_depth", 0) > 0

    def version_gaps(self, state: Tree) -> jax.Array:
        """``(n, n)`` int32 of per-edge iterate-version gaps: entry (i, j) is
        how many rounds old the payload node i mixed from node j in the most
        recent ``apply`` (``min(d_ij, rounds - 1)`` — round 0 mixes fresh
        payloads by the warmup rule).  Zero off the gossip support, for
        undelayed channels, and before the first round."""
        return jnp.zeros((self.topology.n, self.topology.n), jnp.int32)

    def node_gaps(self, state: Tree) -> jax.Array:
        """Per-node view of :meth:`version_gaps` — the worst version gap on
        any edge *incident* to the node, in either direction: payloads it
        consumed stale (row) AND the age at which its own payloads reach
        its readers (column).  The out-direction matters: the momentum
        feedback a staleness-aware algorithm damps runs through the round
        trip my payload -> neighbor's stale mix -> neighbor's payload -> my
        mix, so a node whose *readers* lag (or lead) is as exposed as one
        whose inputs do.  Delayed stacked channels return ``(n,)``; delayed
        distributed channels (which only ever run inside shard_map) return
        *this* node's scalar, indexed by the mesh axis; staleness-free
        transports return scalar 0.  This is what staleness-aware
        algorithms fold into their update
        (:func:`repro.core.update_spec.staleness_damping`)."""
        if not self.has_staleness():
            return jnp.int32(0)
        incident = _incident_gaps(self.version_gaps(state))
        if self._stacked_layout:
            return incident
        return incident[jax.lax.axis_index(self.node_axes)]

    def state_specs(self, param_specs: Tree) -> Tree:
        """Per-node PartitionSpec tree matching :meth:`init`'s structure
        (the TrainState stacker prepends the node axis)."""
        from jax.sharding import PartitionSpec as P

        is_p = lambda s: isinstance(s, P)
        specs: dict = {}
        if self._telemetry:
            specs["t"] = {"rounds": P(), "bytes": P()}
        if self._stateful_comp:
            specs["comp"] = param_specs
        if getattr(self, "_depth", 0) > 0:
            hist = jax.tree.map(lambda s: P(None, *s), param_specs, is_leaf=is_p)
            specs["delay"] = {
                f"s{i}": {"hist": hist, "count": P()} for i in range(self._slots)
            }
        return specs


# ---------------------------------------------------------------------------
# Stacked channels — leaves are (n_nodes, ...); no mesh required
# ---------------------------------------------------------------------------


@_register_static
class StackedChannel(GossipChannel):
    """Dense ``W @`` reference transport (the tests/bias-experiment oracle).

    With ``compression`` set, each node's payload is encoded/decoded
    (per-node, vmapped) before the off-diagonal mix — the stacked analogue
    of the wire compression on the ppermute path, enabling mesh-free
    compression x staleness sweeps.  Uncompressed, the mix is the exact
    einsum of the original ``make_stacked_gossip``.
    """

    name = "stacked"
    _stacked_layout = True

    def __init__(
        self,
        topology: Topology,
        *,
        compression: str | None = None,
        telemetry: bool = False,
    ):
        self._setup(topology, compression, telemetry)
        period = topology.period
        self._Ws = [jnp.asarray(topology.W(t), jnp.float32) for t in range(period)]
        self._diag = [jnp.asarray(np.diag(topology.W(t)), jnp.float32) for t in range(period)]
        self._Woff = [
            jnp.asarray(topology.W(t) - np.diag(np.diag(topology.W(t))), jnp.float32)
            for t in range(period)
        ]

    def _init_extra(self, template: Tree) -> dict:
        if self._stateful_comp:
            return {"comp": jax.tree.map(self._compressor.init, template)}
        return {}

    # exact legacy mix (bit-exact with the pre-redesign closure)
    def _mix_plain(self, t: int, tree: Tree) -> Tree:
        W = self._Ws[t]

        def leaf(x):
            y = jnp.einsum("ij,j...->i...", W, x.astype(jnp.float32))
            return y.astype(x.dtype)

        return jax.tree.map(leaf, tree)

    def _mix_compressed(self, t: int, tree: Tree, comp: Tree) -> tuple[Tree, Tree]:
        diag, Woff = self._diag[t], self._Woff[t]
        leaves, treedef = jax.tree.flatten(tree)
        states = (
            treedef.flatten_up_to(comp) if self._stateful_comp else [()] * len(leaves)
        )
        outs, new_states = [], []
        for x, st in zip(leaves, states):
            x32 = x.astype(jnp.float32)
            if self._stateful_comp:
                msg, st = jax.vmap(self._compressor.encode)(x32, st)
            else:
                msg = jax.vmap(lambda xi: self._compressor.encode(xi, ())[0])(x32)
            xhat = jax.vmap(self._compressor.decode)(msg, x32)
            d = diag.reshape((-1,) + (1,) * (x32.ndim - 1))
            y = d * x32 + jnp.einsum("ij,j...->i...", Woff, xhat.astype(jnp.float32))
            outs.append(y.astype(x.dtype))
            new_states.append(st)
        comp_out = treedef.unflatten(new_states) if self._stateful_comp else comp
        return treedef.unflatten(outs), comp_out

    def _plain_apply(self, state: Tree, tree: Tree, step) -> tuple[Tree, Tree]:
        period = self.topology.period
        if self._compressor.name == "none":
            if period == 1:
                mixed = self._mix_plain(0, tree)
            else:
                branches = [functools.partial(self._mix_plain, t) for t in range(period)]
                mixed = jax.lax.switch(step % period, branches, tree)
            comp = None
        else:
            comp = state.get("comp", ()) if isinstance(state, dict) else ()
            if period == 1:
                mixed, comp = self._mix_compressed(0, tree, comp)
            else:
                branches = [
                    functools.partial(self._mix_compressed, t) for t in range(period)
                ]
                mixed, comp = jax.lax.switch(step % period, branches, tree, comp)
        return self._finish(state, tree, step, comp=comp), mixed

    def apply(self, state: Tree, tree: Tree, step) -> tuple[Tree, Tree]:
        return self._plain_apply(state, tree, step)


@_register_static
class DelayedStackedChannel(StackedChannel):
    """Stacked gossip with per-edge delay ring buffers (bounded staleness).

    ``x_i <- w_ii x_i(t) + sum_j w_ij x_j(t - d_ij)``: every edge carries a
    fixed integer delay and the receiver mixes the sender's payload from
    ``d_ij`` gossip rounds ago — the synchronous model of AD-PSGD-style
    asynchrony.  Before the buffers warm up every edge uses the oldest
    payload recorded so far, so round 0 is identical to fresh gossip.

    ``delay`` is an int (uniform) or an ``(n, n)`` matrix.  For algorithms
    with more than one gossip per step (da-dmsgd) pass
    ``calls_per_step=opt.gossips_per_step``: the state keeps one rotating
    ring-buffer slot per call so each gossip phase has independent history.

    At uniform delay 0 ``apply`` runs the exact :class:`StackedChannel`
    code path, so the zero-staleness simulator degrades to the lockstep
    oracle bit-exactly.  With compression, history stores the *decoded*
    transmitted payloads (what the wire would have delivered) and the
    self-contribution stays raw and current.
    """

    name = "delayed-stacked"

    def __init__(
        self,
        topology: Topology,
        delay,
        *,
        calls_per_step: int = 1,
        compression: str | None = None,
        telemetry: bool = False,
    ):
        super().__init__(topology, compression=compression, telemetry=telemetry)
        self._D = delay_matrix(topology.n, delay)
        self._depth = int(self._D.max())
        self._ring = self._depth + 1
        self._slots = max(1, int(calls_per_step))
        self._gap_mask = _edge_mask(topology)
        if self._depth == 0:
            return
        uniq = [int(d) for d in np.unique(self._D)]
        # per-phase, per-delay weight matrices: W_t masked to edges with
        # delay d.  The uncompressed path keeps the diagonal inside the d=0
        # group (history slot just written == current payload) to preserve
        # the pre-redesign reduction order bit-exactly; the compressed path
        # needs the raw-diagonal split and uses off-diagonal groups.
        self._Wds: list[list[tuple[int, jnp.ndarray]]] = []
        self._Wds_off: list[list[tuple[int, jnp.ndarray]]] = []
        for t in range(topology.period):
            W = topology.W(t)
            Woff = W - np.diag(np.diag(W))
            per_t, per_t_off = [], []
            for d in uniq:
                Wd = np.where(self._D == d, W, 0.0)
                if (Wd != 0.0).any():
                    per_t.append((d, jnp.asarray(Wd, jnp.float32)))
                Wdo = np.where(self._D == d, Woff, 0.0)
                if (Wdo != 0.0).any():
                    per_t_off.append((d, jnp.asarray(Wdo, jnp.float32)))
            self._Wds.append(per_t)
            self._Wds_off.append(per_t_off)

    def _init_extra(self, template: Tree) -> dict:
        extra = super()._init_extra(template)
        if self._depth > 0:
            extra["delay"] = {
                f"s{i}": _fresh_slot(template, self._ring) for i in range(self._slots)
            }
        return extra

    def _apply_phase(self, t: int, tree: Tree, slot: dict, comp: Tree):
        """One delayed mix: push the (possibly compressed-transmitted)
        payload into the ring, combine per-delay groups."""
        count = slot["count"]
        pos = count % self._ring
        leaves, treedef = jax.tree.flatten(tree)
        hists = treedef.flatten_up_to(slot["hist"])
        compressed = self._compressor.name != "none"
        groups = self._Wds_off[t] if compressed else self._Wds[t]

        if compressed:
            states = (
                treedef.flatten_up_to(comp)
                if self._stateful_comp
                else [()] * len(leaves)
            )
            new_states = []

        mixed, new_hists = [], []
        for k, (x, hist) in enumerate(zip(leaves, hists)):
            x32 = x.astype(jnp.float32)
            if compressed:
                if self._stateful_comp:
                    msg, st = jax.vmap(self._compressor.encode)(x32, states[k])
                    new_states.append(st)
                else:
                    msg = jax.vmap(lambda xi: self._compressor.encode(xi, ())[0])(x32)
                stored = jax.vmap(self._compressor.decode)(msg, x32).astype(
                    jnp.float32
                )
            else:
                stored = x32
            hist = jax.lax.dynamic_update_index_in_dim(hist, stored, pos, axis=0)
            out = (
                self._diag[t].reshape((-1,) + (1,) * (x32.ndim - 1)) * x32
                if compressed
                else jnp.zeros_like(x32)
            )
            for d, Wd in groups:
                # before warmup, fall back to the oldest recorded payload
                d_eff = jnp.minimum(d, count)
                read = (count - d_eff) % self._ring
                stale = jax.lax.dynamic_index_in_dim(hist, read, axis=0, keepdims=False)
                out = out + jnp.einsum("ij,j...->i...", Wd, stale)
            mixed.append(out.astype(x.dtype))
            new_hists.append(hist)

        new_slot = {"hist": treedef.unflatten(new_hists), "count": count + 1}
        comp_out = (
            treedef.unflatten(new_states)
            if compressed and self._stateful_comp
            else comp
        )
        return treedef.unflatten(mixed), new_slot, comp_out

    def apply(self, state: Tree, tree: Tree, step) -> tuple[Tree, Tree]:
        if self._depth == 0:
            return self._plain_apply(state, tree, step)
        period = self.topology.period
        slot = state["delay"]["s0"]
        comp = state.get("comp", ())
        if period == 1:
            mixed, new_slot, comp = self._apply_phase(0, tree, slot, comp)
        else:
            branches = [functools.partial(self._apply_phase, t) for t in range(period)]
            mixed, new_slot, comp = jax.lax.switch(
                step % period, branches, tree, slot, comp
            )
        new_state = dict(state)
        new_state["delay"] = _rotate_slots(state["delay"], self._slots, new_slot)
        return self._finish(new_state, tree, step, comp=comp), mixed

    def version_gaps(self, state: Tree) -> jax.Array:
        if self._depth == 0:
            return super().version_gaps(state)
        return _delayed_version_gaps(state, self._D * self._gap_mask)


# ---------------------------------------------------------------------------
# Distributed channels — run inside shard_map; leaves are per-node slices
# ---------------------------------------------------------------------------


@_register_static
class PpermuteChannel(GossipChannel):
    """Edge-class ppermute gossip (the paper's partial averaging, TPU-native).

    ``serialize=True`` chains each edge class's ppermute behind the previous
    class's accumulation with a data dependency, so only ONE receive buffer
    is live at a time.  Measured on qwen3-8b train (EXPERIMENTS §Perf A-3):
    without it XLA keeps all 7 exponential-graph receives (2 GiB fp32 each)
    in flight and per-device temp memory blows from 12 to 32 GiB.  The cost
    is gossip-internal overlap only — gossip still overlaps with the
    backward pass (it is scheduled off the payload, not the loss).
    """

    name = "ppermute"

    def __init__(
        self,
        topology: Topology,
        node_axes: str | tuple[str, ...],
        *,
        compression: str | None = None,
        serialize: bool = True,
        telemetry: bool = False,
    ):
        self._setup(topology, compression, telemetry)
        self.node_axes = node_axes
        self.serialize = serialize

    def _init_extra(self, template: Tree) -> dict:
        if self._stateful_comp:
            return {"comp": jax.tree.map(self._compressor.init, template)}
        return {}

    def _apply_classes(self, t: int, tree: Tree, comp_state: Tree):
        topology, compressor = self.topology, self._compressor
        classes = topology.edge_classes(t)
        self_w = jnp.asarray(topology.self_weight(t), dtype=jnp.float32)
        idx = jax.lax.axis_index(self.node_axes)

        leaves, treedef = jax.tree.flatten(tree)
        stateless = not jax.tree.leaves(comp_state)
        if stateless:
            states = [()] * len(leaves)
        else:
            states = treedef.flatten_up_to(comp_state)

        msgs, new_states = [], []
        for x, st in zip(leaves, states):
            m, st = compressor.encode(x, st)
            msgs.append(m)
            new_states.append(st)

        out = [self_w[idx] * x.astype(jnp.float32) for x in leaves]
        for ci, c in enumerate(classes):
            w = jnp.asarray(c.recv_weight, dtype=jnp.float32)[idx]
            for k, (x, m) in enumerate(zip(leaves, msgs)):
                if self.serialize and ci > 0:
                    # tie this class's send to the previous accumulation so
                    # receive buffers don't all stay live concurrently —
                    # a real data dependency (a zeroed scalar add), because
                    # optimization_barrier alone does not stop XLA's buffer
                    # assignment from provisioning all receives concurrently
                    z = out[k].ravel()[:1].sum() * 0
                    m = jax.tree.map(lambda a: a + z.astype(a.dtype), m)
                recv = jax.tree.map(
                    lambda a: jax.lax.ppermute(a, self.node_axes, c.pairs), m
                )
                out[k] = out[k] + w * compressor.decode(recv, x).astype(jnp.float32)
        out = [o.astype(x.dtype) for o, x in zip(out, leaves)]
        comp_out = comp_state if stateless else treedef.unflatten(new_states)
        return treedef.unflatten(out), comp_out

    def _plain_apply(self, state: Tree, tree: Tree, step) -> tuple[Tree, Tree]:
        period = self.topology.period
        comp = state.get("comp", ()) if isinstance(state, dict) else state
        if period == 1:
            mixed, comp = self._apply_classes(0, tree, comp)
        else:
            branches = [
                functools.partial(self._apply_classes, t) for t in range(period)
            ]
            mixed, comp = jax.lax.switch(step % period, branches, tree, comp)
        return self._finish(state, tree, step, comp=comp), mixed

    def apply(self, state: Tree, tree: Tree, step) -> tuple[Tree, Tree]:
        return self._plain_apply(state, tree, step)


@_register_static
class DelayedPpermuteChannel(PpermuteChannel):
    """Ppermute gossip that holds payloads back ``delay`` steps on-device.

    Every node keeps a ring buffer of its own past gossip payloads *in
    device memory inside the shard_map region*; each round it pushes the
    fresh payload and ships the one from ``delay`` rounds ago (oldest
    recorded during warmup) along every edge class, while the
    self-contribution stays current.  This is the distributed realization
    of :class:`DelayedStackedChannel` with a uniform delay — the sim's SSP
    ``stale_gossip_k*`` scenarios, runnable on a real mesh.

    Message compression is not supported yet: the ring would have to store
    encoded messages per compressor format and split error feedback per
    round (pass ``compression=None``).  Delay 0 runs the exact
    :class:`PpermuteChannel` code path.
    """

    name = "delayed-ppermute"

    def __init__(
        self,
        topology: Topology,
        node_axes: str | tuple[str, ...],
        delay: int,
        *,
        calls_per_step: int = 1,
        serialize: bool = True,
        telemetry: bool = False,
        compression: str | None = None,
    ):
        if compression not in (None, "none"):
            raise ValueError(
                "DelayedPpermuteChannel does not support message compression "
                "yet (the ring buffer stores raw f32 payloads); pass "
                "compression=None or use the delayed stacked channel"
            )
        super().__init__(
            topology, node_axes, compression=None, serialize=serialize,
            telemetry=telemetry,
        )
        self.delay = int(delay)
        assert self.delay >= 0, "delay must be non-negative"
        self._depth = self.delay
        self._ring = self.delay + 1
        self._slots = max(1, int(calls_per_step))
        self._gap_mask = _edge_mask(topology)

    def _init_extra(self, template: Tree) -> dict:
        if self._depth == 0:
            return {}
        return {
            "delay": {
                f"s{i}": _fresh_slot(template, self._ring) for i in range(self._slots)
            }
        }

    def _mix_phase(self, t: int, tree: Tree, msgs: Tree):
        """Mix current self-contribution with the delayed neighbor payloads."""
        topology = self.topology
        classes = topology.edge_classes(t)
        self_w = jnp.asarray(topology.self_weight(t), dtype=jnp.float32)
        idx = jax.lax.axis_index(self.node_axes)

        leaves, treedef = jax.tree.flatten(tree)
        msg_leaves = treedef.flatten_up_to(msgs)
        out = [self_w[idx] * x.astype(jnp.float32) for x in leaves]
        for ci, c in enumerate(classes):
            w = jnp.asarray(c.recv_weight, dtype=jnp.float32)[idx]
            for k, m in enumerate(msg_leaves):
                if self.serialize and ci > 0:
                    z = out[k].ravel()[:1].sum() * 0
                    m = m + z
                recv = jax.lax.ppermute(m, self.node_axes, c.pairs)
                out[k] = out[k] + w * recv
        out = [o.astype(x.dtype) for o, x in zip(out, leaves)]
        return treedef.unflatten(out)

    def apply(self, state: Tree, tree: Tree, step) -> tuple[Tree, Tree]:
        if self._depth == 0:
            return self._plain_apply(state, tree, step)
        period = self.topology.period
        slot = state["delay"]["s0"]
        count = slot["count"]
        pos = count % self._ring

        leaves, treedef = jax.tree.flatten(tree)
        hists = treedef.flatten_up_to(slot["hist"])
        new_hists = [
            jax.lax.dynamic_update_index_in_dim(h, x.astype(jnp.float32), pos, axis=0)
            for h, x in zip(hists, leaves)
        ]
        # before warmup, ship the oldest recorded payload (round 0 is fresh)
        d_eff = jnp.minimum(jnp.int32(self.delay), count)
        read = (count - d_eff) % self._ring
        msgs = treedef.unflatten(
            [
                jax.lax.dynamic_index_in_dim(h, read, axis=0, keepdims=False)
                for h in new_hists
            ]
        )

        if period == 1:
            mixed = self._mix_phase(0, tree, msgs)
        else:
            branches = [functools.partial(self._mix_phase, t) for t in range(period)]
            mixed = jax.lax.switch(step % period, branches, tree, msgs)

        new_slot = {"hist": treedef.unflatten(new_hists), "count": count + 1}
        new_state = dict(state)
        new_state["delay"] = _rotate_slots(state["delay"], self._slots, new_slot)
        return self._finish(new_state, tree, step), mixed

    def version_gaps(self, state: Tree) -> jax.Array:
        if self._depth == 0:
            return super().version_gaps(state)
        return _delayed_version_gaps(state, self.delay * self._gap_mask)


@_register_static
class AllgatherChannel(GossipChannel):
    """Naive baseline: all-gather payload across nodes, reduce with W row."""

    name = "allgather"
    _impl = "allgather"

    def __init__(
        self,
        topology: Topology,
        node_axes: str | tuple[str, ...],
        *,
        telemetry: bool = False,
    ):
        self._setup(topology, None, telemetry)
        self.node_axes = node_axes
        self._Ws = [
            jnp.asarray(topology.W(t), dtype=jnp.float32)
            for t in range(topology.period)
        ]

    def _apply_W(self, t: int, tree: Tree) -> Tree:
        W = self._Ws[t]
        idx = jax.lax.axis_index(self.node_axes)
        row = W[idx]

        def leaf(x):
            xs = jax.lax.all_gather(x.astype(jnp.float32), self.node_axes, axis=0)
            return jnp.tensordot(row, xs, axes=([0], [0])).astype(x.dtype)

        return jax.tree.map(leaf, tree)

    def apply(self, state: Tree, tree: Tree, step) -> tuple[Tree, Tree]:
        period = self.topology.period
        if period == 1:
            mixed = self._apply_W(0, tree)
        else:
            branches = [functools.partial(self._apply_W, t) for t in range(period)]
            mixed = jax.lax.switch(step % period, branches, tree)
        if isinstance(state, dict) and "t" in state:
            n = self.topology.n
            state = self._tick(state, step, (n - 1) * self._payload_nbytes(tree))
        return state, mixed

    def collectives_per_round(self, payload: Tree, state: Tree | None = None) -> float:
        # one raw-f32 all_gather per payload leaf, whatever the topology
        return float(len(jax.tree.leaves(payload)))


# ---------------------------------------------------------------------------
# Channel factory
# ---------------------------------------------------------------------------


def build_channel(
    impl: str,
    topology: Topology,
    node_axes: str | tuple[str, ...] | None = None,
    *,
    compression: str | None = None,
    delay: int = 0,
    serialize: bool = True,
    calls_per_step: int = 1,
    telemetry: bool = False,
) -> GossipChannel:
    """Construct the right channel for ``impl`` in {stacked, ppermute,
    allgather}; ``delay > 0`` selects the delayed variant."""
    if impl == "stacked":
        if delay:
            return DelayedStackedChannel(
                topology, delay, calls_per_step=calls_per_step,
                compression=compression, telemetry=telemetry,
            )
        return StackedChannel(topology, compression=compression, telemetry=telemetry)
    if node_axes is None:
        raise ValueError(f"impl={impl!r} needs node_axes")
    if impl == "ppermute":
        if delay:
            return DelayedPpermuteChannel(
                topology, node_axes, delay, calls_per_step=calls_per_step,
                serialize=serialize, telemetry=telemetry, compression=compression,
            )
        return PpermuteChannel(
            topology, node_axes, compression=compression, serialize=serialize,
            telemetry=telemetry,
        )
    if impl == "allgather":
        if delay:
            raise ValueError("allgather has no delayed variant (O(n) baseline)")
        if compression not in (None, "none"):
            raise ValueError(
                "impl='allgather' cannot compress (the payload is all-gathered"
                " raw); pass compression=None or use impl='ppermute'"
            )
        return AllgatherChannel(topology, node_axes, telemetry=telemetry)
    raise ValueError(f"unknown gossip impl {impl!r}")


# ---------------------------------------------------------------------------
# Exact-mean closures (PmSGD / SlowMo sync primitive — not part of the
# channel redesign; the exact mean is stateless and staleness-free)
# ---------------------------------------------------------------------------


def make_stacked_mean(n_nodes: int):
    """Exact global average, broadcast back to every node (stacked layout)."""

    def mean(tree):
        def leaf(x):
            m = jnp.mean(x.astype(jnp.float32), axis=0, keepdims=True)
            return jnp.broadcast_to(m, x.shape).astype(x.dtype)

        return jax.tree.map(leaf, tree)

    return mean


def make_psum_mean(node_axes: str | tuple[str, ...], n_nodes: int):
    """Exact global average across nodes (PmSGD / SlowMo sync primitive)."""

    def mean(tree):
        def leaf(x):
            return (jax.lax.psum(x.astype(jnp.float32), node_axes) / n_nodes).astype(
                x.dtype
            )

        return jax.tree.map(leaf, tree)

    return mean


# ---------------------------------------------------------------------------
# Comm-volume accounting (Fig. 6 analytic model)
# ---------------------------------------------------------------------------


def gossip_bytes_per_step(
    topology: Topology,
    payload_bytes: float,
    *,
    impl: str = "ppermute",
    compression: str | None = None,
) -> dict[str, float]:
    """Per-node egress bytes + latency hops for one gossip step (averaged over
    the topology period).  For comparison, ring all-reduce of the same payload
    costs ``2 (n-1)/n * payload`` bytes and ``2 (n-1)`` hops.

    The ``allgather`` baseline ships raw fp32: GSPMD all-gathers the payload
    before the local W-row reduction, so message compression cannot be
    applied on that path — requesting it is a modeling error and raises
    rather than silently pricing bytes that would never be saved.

    (:meth:`GossipChannel.bytes_per_step` delegates here; this function is
    the analytic ground truth the benchmarks cross-check against.)
    """
    n = topology.n
    if impl == "allgather":
        if compression is not None:
            raise ValueError(
                "impl='allgather' cannot compress: the payload is "
                "all-gathered raw before the local W-row reduction; pass "
                "compression=None or use impl='ppermute'"
            )
        return {"egress_bytes": (n - 1) / n * payload_bytes * n, "hops": n - 1}
    per_payload = wire_bytes(payload_bytes, compression)
    sends = np.mean([len(topology.edge_classes(t)) for t in range(topology.period)])
    return {"egress_bytes": float(sends) * per_payload, "hops": float(sends)}


