"""Decentralized momentum optimizers (the paper's subject).

Every algorithm is a pure ``(init, step)`` pair operating on parameter
pytrees.  Communication is injected through two closures so the *same*
optimizer code runs in both harnesses:

* the stacked reference harness (leaves ``(n, ...)``; gossip = dense ``W @``,
  mean = axis-0 mean) — used by tests / bias experiments, and
* the distributed harness (leaves are per-node slices inside a fully-manual
  ``shard_map``; gossip = ppermute edge classes, mean = psum).

Closure signatures::

    gossip(tree, step, comp_state) -> (tree, comp_state)   # partial averaging
    mean(tree) -> tree                                     # exact global mean

Implemented algorithms (paper Sec. 7 baselines + the contribution):

===========  ================================================================
pmsgd        parallel momentum SGD:  m <- b m + mean(g); x <- x - lr m
pmsgd-lars   + layer-wise adaptive rate scaling [You et al. 2017]
dsgd         ATC decentralized SGD (eq. 4-5):  x <- G(x - lr g)
dmsgd        Alg. 1:  m <- b m + g; x <- G(x - lr m)
da-dmsgd     [Yu et al. 2019]: m <- G(b m + g); x <- G(x - lr m)
awc-dmsgd    [Balu et al. 2020]: m <- b m + g; x <- G(x) - lr m
slowmo       [Wang et al. 2019]: inner DmSGD + periodic exact-average slow
             momentum outer update
qg-dmsgd     [Lin et al. 2021] heavy-ball quasi-global momentum
d2-dmsgd     D^2 [Tang et al. 2018] in the [Yuan et al. 2020] form with
             momentum on the local update
decentlam    **Alg. 2 / eq. (17)**:
             g~ = (x - G(x - lr g)) / lr;  m <- b m + g~;  x <- x - lr m
decentlam-sa staleness-aware DecentLaM: under stale mixing the implicit
             gradient g~ carries a drift ~gap x momentum that compounds
             through b (the sim's stale_gossip_k* divergence).  The fix
             damps the drift *entering the momentum* by the observed
             per-node version gap — m <- b m + (sg g~ + (1-sg) g) with
             sg = sa_damping^gap — while the parameter update keeps the
             full g~, so consensus still mixes at channel strength:
             x <- x - lr (b m + g~).  gap 0 (any delay-0 transport)
             reduces to decentlam bit-exactly.
===========  ================================================================

The DecentLaM step sends exactly one gossip payload per iteration —
``x - lr g`` — which every node can emit as soon as its local backward pass
finishes (the paper's wait-free-backprop observation).

Each algorithm's elementwise tail is declared as *data* — an
:class:`~repro.core.update_spec.UpdateSpec` of (payload op, comm, recombine
op) phases — and executed by :func:`~repro.core.update_spec.run_update`.
The reference path here walks the spec with pure-jnp tree maps; the fused
Pallas engine (:mod:`repro.kernels.fused_update`) walks the *same* spec with
one-HBM-pass stage kernels, so the two paths share their math by
construction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from .update_spec import reference_stage, run_update, update_spec

Tree = Any

__all__ = [
    "OptimizerConfig",
    "Optimizer",
    "make_optimizer",
    "state_keys",
    "update_spec",
    "ALGORITHMS",
]

ALGORITHMS = (
    "pmsgd",
    "pmsgd-lars",
    "dsgd",
    "dmsgd",
    "da-dmsgd",
    "awc-dmsgd",
    "slowmo",
    "qg-dmsgd",
    "d2-dmsgd",
    "decentlam",
    "decentlam-sa",
)


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    algorithm: str = "decentlam"
    momentum: float = 0.9
    nesterov: bool = False  # applies to pmsgd / dmsgd / decentlam updates
    weight_decay: float = 0.0
    decoupled_wd: bool = False
    grad_clip: float = 0.0  # 0 = off; global-norm clip of local grads
    # LARS (pmsgd-lars, or lars=True to compose with any algorithm)
    lars: bool = False
    lars_trust: float = 0.001
    lars_eps: float = 1e-9
    # SlowMo
    slowmo_period: int = 12
    slowmo_momentum: float = 0.5
    slowmo_lr: float = 1.0
    # DecentLaM-SA gap-damping schedule: the momentum estimator's implicit-
    # gradient weight is max(sa_damping**gap, sa_floor) per node.  The
    # default 0.5 stabilizes ring/torus meshes up to gap ~8; sa_damping ==
    # momentum (the naive beta^gap of Momentum-Tracking-style corrections)
    # still diverges for beta > ~0.5 — the drift feedback gain scales with
    # gap x (1 - self-weight), not with beta (see BENCH_sim.json).
    sa_damping: float = 0.5
    sa_floor: float = 0.0

    def __post_init__(self):
        if self.algorithm not in ALGORITHMS:
            raise ValueError(
                f"unknown algorithm {self.algorithm!r}; one of {ALGORITHMS}"
            )
        assert 0.0 <= self.momentum < 1.0
        assert 0.0 < self.sa_damping <= 1.0, "sa_damping is a decay base"
        assert 0.0 <= self.sa_floor <= 1.0


def state_keys(cfg: "OptimizerConfig") -> tuple[str, ...]:
    """Names of the optimizer-state buckets (each mirrors the param tree)."""
    keys: list[str] = []
    if cfg.algorithm != "dsgd":
        keys.append("m")
    if cfg.algorithm == "slowmo":
        keys += ["u", "anchor"]
    if cfg.algorithm == "d2-dmsgd":
        keys += ["x_prev", "m_prev"]
    return tuple(keys)


class Optimizer(NamedTuple):
    config: OptimizerConfig
    init: Callable[[Tree], Tree]
    step: Callable[..., tuple[Tree, Tree]]
    # step(params, grads, state, *, lr, step_idx, gossip, mean,
    #      comp_state=(), node_gaps=None) -> (params, state, comp_state)
    # node_gaps: per-node gossip version gaps for staleness-aware
    # algorithms ((n,) stacked / scalar inside shard_map); None derives
    # them from the channel state after each gossip round.
    gossips_per_step: int  # payload sends per iteration (comm accounting)


def _f32(tree: Tree) -> Tree:
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _zeros_like_f32(tree: Tree) -> Tree:
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)


def _axpy(a, x: Tree, y: Tree) -> Tree:  # a*x + y
    return jax.tree.map(lambda u, v: a * u + v, x, y)


def _scale(a, x: Tree) -> Tree:
    return jax.tree.map(lambda u: a * u, x)


def _global_norm(tree: Tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def _clip_by_global_norm(tree: Tree, max_norm: float) -> Tree:
    norm = _global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return _scale(scale, tree)


def _leaf_norms(tree: Tree) -> Tree:
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree
    )


def _lars_scaled(cfg: OptimizerConfig, params: Tree, grads: Tree) -> Tree:
    """Per-leaf trust ratio (layer-wise adaptive rate scaling)."""
    pn = _leaf_norms(params)
    gn = _leaf_norms(grads)

    def ratio(p_norm, g_norm, g):
        denom = g_norm + cfg.weight_decay * p_norm + cfg.lars_eps
        r = jnp.where(
            (p_norm > 0.0) & (g_norm > 0.0),
            cfg.lars_trust * p_norm / denom,
            1.0,
        )
        return r * g

    return jax.tree.map(ratio, pn, gn, grads)


def _preprocess_grads(cfg: OptimizerConfig, params: Tree, grads: Tree) -> Tree:
    """Unfused gradient preprocessing (clip -> coupled wd -> LARS).

    The spec-driven paths fold the resulting *scalars* into the fused stages
    (see ``update_spec.grad_scalars`` / ``_g_eff``) instead of materializing
    the scaled gradient; this tree-level version is the semantic oracle, and
    ``test_optimizers.py::test_preprocess_grads_matches_fused_scalar_folding``
    pins the fused folding to it.
    """
    g = _f32(grads)
    if cfg.grad_clip > 0.0:
        g = _clip_by_global_norm(g, cfg.grad_clip)
    if cfg.weight_decay > 0.0 and not cfg.decoupled_wd:
        g = _axpy(cfg.weight_decay, _f32(params), g)
    if cfg.lars or cfg.algorithm == "pmsgd-lars":
        g = _lars_scaled(cfg, params, g)
    return g


def make_optimizer(cfg: OptimizerConfig) -> Optimizer:
    algo = cfg.algorithm
    spec = update_spec(cfg)
    no_comp = ()

    # ---------------- state ----------------
    def init(params: Tree) -> Tree:
        st: dict[str, Tree] = {}
        if algo not in ("dsgd",):
            st["m"] = _zeros_like_f32(params)
        if algo == "slowmo":
            st["u"] = _zeros_like_f32(params)
            st["anchor"] = _f32(params)
        if algo == "d2-dmsgd":
            st["x_prev"] = _f32(params)
            st["m_prev"] = _zeros_like_f32(params)
        return st

    # ---------------- step ----------------
    def step(
        params, grads, state, *, lr, step_idx, gossip, mean,
        comp_state=no_comp, node_gaps=None,
    ):
        x, new_state, comp_state = run_update(
            spec,
            cfg,
            x=_f32(params),
            g=_f32(grads),
            state=state,
            lr=lr,
            step_idx=step_idx,
            gossip=gossip,
            mean=mean,
            comp_state=comp_state,
            stage=reference_stage,
            node_gaps=node_gaps,
        )
        out = jax.tree.map(lambda p, nx: nx.astype(p.dtype), params, x)
        return out, new_state, comp_state

    return Optimizer(
        config=cfg,
        init=init,
        step=step,
        gossips_per_step=spec.gossips_per_step,
    )
