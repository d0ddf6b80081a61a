"""Algorithm update tails as *data*: the fused-update engine's front end.

Every algorithm in :mod:`repro.core.optimizers` is one to two rounds of

    elementwise PRE  ->  communication  ->  elementwise POST

where PRE builds the gossip payload (and usually the new momentum) and POST
recombines the mixed payload into new parameters.  This module declares that
structure per algorithm as an :class:`UpdateSpec` and provides:

* the per-op elementwise math (:func:`pre_math` / :func:`post_math`) — pure
  ``jnp`` expressions on f32 arrays, executed *both* by the stacked reference
  path and inside the Pallas kernel bodies, so the two are identical by
  construction;
* :func:`run_update` — the phase walker that threads params / momentum /
  comp-state through the phases.  It is parameterized by a *stage executor*:
  :func:`reference_stage` (plain tree-maps; the oracle) or the Pallas
  executor from :mod:`repro.kernels.fused_update` (one HBM pass per stage).

Gradient preprocessing (global-norm clip, coupled weight decay, LARS trust
ratios) needs reductions, so the *norms* are computed outside the kernels
(:func:`grad_scalars`) — but the resulting per-leaf scalars are applied
*inside* the fused stage, so the scaled gradient is never materialized.

Phase table (paper Sec. 7 baselines + Alg. 2):

=============  ============================================================
pmsgd[-lars]   identity_g        -> mean   -> momentum_step
dsgd           grad_step         -> gossip -> assign_x
dmsgd          momentum_payload  -> gossip -> assign_x
da-dmsgd       momentum_accum    -> gossip -> assign_m ;
               x_minus_lr_m      -> gossip -> assign_x
awc-dmsgd      momentum_keep_x   -> gossip -> mix_minus_lr_m
slowmo         momentum_payload  -> gossip -> assign_x  (+ outer sync)
qg-dmsgd       qg_payload        -> gossip -> qg_post
d2-dmsgd       d2_payload        -> gossip -> assign_x  (+ prev-state shift)
decentlam      grad_step         -> gossip -> decentlam_post
decentlam-sa   grad_step         -> gossip -> decentlam_sa_post
=============  ============================================================

Staleness-aware phases (``UpdateSpec.staleness_aware``) additionally consume
the per-node gossip version gap observed by the round that produced their
``mix``: after the gossip comm, :func:`run_update` derives the gap from the
channel state (:meth:`repro.core.gossip.GossipChannel.node_gaps`) — or takes
an explicit ``node_gaps`` override from engines that know staleness out of
band (the discrete-event simulator's snapshot versions) — and folds the
damping factor :func:`staleness_damping` into the stage scalars as ``sg``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .gossip import GossipChannel

Tree = Any

__all__ = [
    "Phase",
    "UpdateSpec",
    "MathCtx",
    "update_spec",
    "math_ctx",
    "phase_ctx",
    "pre_is_free",
    "post_is_free",
    "stage_plan",
    "grad_scalars",
    "pre_io",
    "post_io",
    "pre_math",
    "post_math",
    "staleness_damping",
    "reference_stage",
    "run_update",
]


# ---------------------------------------------------------------------------
# Spec declarations
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Phase:
    pre: str  # elementwise payload op (PRE_IO key)
    comm: str  # "gossip" | "mean" | "none"
    post: str  # elementwise recombination op (POST_IO key)


@dataclasses.dataclass(frozen=True)
class UpdateSpec:
    algorithm: str
    phases: tuple[Phase, ...]
    nesterov_ok: bool = False  # whether cfg.nesterov applies to this tail
    slowmo_outer: bool = False  # periodic exact-average outer step
    d2_state: bool = False  # carries (x_prev, m_prev)
    staleness_aware: bool = False  # post stages consume the "sg" gap damping

    @property
    def gossips_per_step(self) -> int:
        return sum(p.comm == "gossip" for p in self.phases)


_SPEC_TABLE: dict[str, UpdateSpec] = {
    "pmsgd": UpdateSpec(
        "pmsgd",
        (Phase("identity_g", "mean", "momentum_step"),),
        nesterov_ok=True,
    ),
    "pmsgd-lars": UpdateSpec(
        "pmsgd-lars",
        (Phase("identity_g", "mean", "momentum_step"),),
        nesterov_ok=True,
    ),
    "dsgd": UpdateSpec("dsgd", (Phase("grad_step", "gossip", "assign_x"),)),
    "dmsgd": UpdateSpec(
        "dmsgd",
        (Phase("momentum_payload", "gossip", "assign_x"),),
        nesterov_ok=True,
    ),
    "da-dmsgd": UpdateSpec(
        "da-dmsgd",
        (
            Phase("momentum_accum", "gossip", "assign_m"),
            Phase("x_minus_lr_m", "gossip", "assign_x"),
        ),
    ),
    "awc-dmsgd": UpdateSpec(
        "awc-dmsgd", (Phase("momentum_keep_x", "gossip", "mix_minus_lr_m"),)
    ),
    "slowmo": UpdateSpec(
        "slowmo",
        (Phase("momentum_payload", "gossip", "assign_x"),),
        slowmo_outer=True,
    ),
    "qg-dmsgd": UpdateSpec("qg-dmsgd", (Phase("qg_payload", "gossip", "qg_post"),)),
    "d2-dmsgd": UpdateSpec(
        "d2-dmsgd", (Phase("d2_payload", "gossip", "assign_x"),), d2_state=True
    ),
    "decentlam": UpdateSpec(
        "decentlam",
        (Phase("grad_step", "gossip", "decentlam_post"),),
        nesterov_ok=True,
    ),
    "decentlam-sa": UpdateSpec(
        "decentlam-sa",
        (Phase("grad_step", "gossip", "decentlam_sa_post"),),
        nesterov_ok=True,
        staleness_aware=True,
    ),
}


def update_spec(cfg) -> UpdateSpec:
    """The update-spec for an :class:`~repro.core.optimizers.OptimizerConfig`."""
    return _SPEC_TABLE[cfg.algorithm]


@dataclasses.dataclass(frozen=True)
class MathCtx:
    """Compile-time constants of one fused stage (hashable: the Pallas kernel
    specializes on it; python-level branches below become static)."""

    beta: float = 0.9
    nesterov: bool = False
    wd: float = 0.0
    coupled_wd: bool = False  # fold  g <- wd*x + g  into the payload stage
    decoupled_wd: bool = False  # fold  x <- x - lr*wd*x  into this post stage
    clip: bool = False  # multiply g by the global clip scale s["gs"]
    lars: bool = False  # multiply g by the per-leaf trust ratio s["r"]
    # finite guard folded into the stage: g reads as where(ok, g, 0) and a
    # state output whose old value is an input keeps that old value when
    # s["ok"] is 0.0 (the grads went non-finite); see run_update's ``ok``
    guard: bool = False


def math_ctx(cfg, *, nesterov_ok: bool, apply_decoupled_wd: bool) -> MathCtx:
    return MathCtx(
        beta=cfg.momentum,
        nesterov=bool(cfg.nesterov and nesterov_ok),
        wd=cfg.weight_decay,
        coupled_wd=cfg.weight_decay > 0.0 and not cfg.decoupled_wd,
        decoupled_wd=(
            cfg.weight_decay > 0.0 and cfg.decoupled_wd and apply_decoupled_wd
        ),
        clip=cfg.grad_clip > 0.0,
        lars=bool(cfg.lars or cfg.algorithm == "pmsgd-lars"),
    )


def phase_ctx(cfg, spec: UpdateSpec, i: int) -> MathCtx:
    """The MathCtx of phase ``i``: decoupled wd folds into the final phase's
    post stage, except for SlowMo where it applies after the outer sync."""
    last = i == len(spec.phases) - 1
    return math_ctx(
        cfg,
        nesterov_ok=spec.nesterov_ok,
        apply_decoupled_wd=last and not spec.slowmo_outer,
    )


def pre_is_free(ph: Phase, ctx: MathCtx) -> bool:
    """Payload stages that cost nothing (pure handoff, no kernel launch)."""
    return ph.pre == "identity_g" and not (ctx.clip or ctx.coupled_wd or ctx.lars)


def post_is_free(ph: Phase, ctx: MathCtx) -> bool:
    """Recombine stages that are pure assigns (no kernel launch)."""
    return ph.post == "assign_m" or (ph.post == "assign_x" and not ctx.decoupled_wd)


def stage_plan(cfg) -> list[tuple[str, str, MathCtx]]:
    """The (kind, op, ctx) stages :func:`run_update` actually executes —
    the single source of truth for anything enumerating engine stages
    (the kernel microbenchmark derives its cost model from this)."""
    spec = update_spec(cfg)
    plan: list[tuple[str, str, MathCtx]] = []
    for i, ph in enumerate(spec.phases):
        ctx = phase_ctx(cfg, spec, i)
        if not pre_is_free(ph, ctx):
            plan.append(("pre", ph.pre, ctx))
        if not post_is_free(ph, ctx):
            plan.append(("post", ph.post, ctx))
    return plan


# ---------------------------------------------------------------------------
# Elementwise op math (f32 in, f32 out) — shared by reference and kernels
# ---------------------------------------------------------------------------

# op -> (input names, output names).  "x" is appended to g-consuming ops when
# coupled weight decay needs it (see pre_io).
_PRE_IO: dict[str, tuple[tuple[str, ...], tuple[str, ...]]] = {
    "grad_step": (("x", "g"), ("payload",)),
    "identity_g": (("g",), ("payload",)),
    "momentum_payload": (("x", "g", "m"), ("payload", "m")),
    "momentum_accum": (("g", "m"), ("payload", "m")),
    "x_minus_lr_m": (("x", "m"), ("payload",)),
    "momentum_keep_x": (("x", "g", "m"), ("payload", "m")),
    "qg_payload": (("x", "g", "m"), ("payload",)),
    "d2_payload": (("x", "g", "m", "x_prev", "m_prev"), ("payload", "m")),
}

_POST_IO: dict[str, tuple[tuple[str, ...], tuple[str, ...]]] = {
    "assign_x": (("mix",), ("x",)),
    "assign_m": (("mix",), ("m",)),
    "mix_minus_lr_m": (("mix", "m"), ("x",)),
    "momentum_step": (("x", "mix", "m"), ("x", "m")),
    "qg_post": (("x", "mix", "m"), ("x", "m")),
    "decentlam_post": (("x", "mix", "m"), ("x", "m")),
    # needs the raw gradient: the damped momentum estimator blends the
    # implicit gradient with g_eff (recomputed from the same scalars the
    # payload stage folded in)
    "decentlam_sa_post": (("x", "mix", "m", "g"), ("x", "m")),
}


def pre_io(op: str, ctx: MathCtx) -> tuple[tuple[str, ...], tuple[str, ...]]:
    ins, outs = _PRE_IO[op]
    if ctx.coupled_wd and "g" in ins and "x" not in ins:
        ins = ("x",) + ins
    return ins, outs


def post_io(op: str) -> tuple[tuple[str, ...], tuple[str, ...]]:
    return _POST_IO[op]


def _ok(s, like):
    """The guard's predicate as a mask of ``like``'s shape (the f32 scalar
    is broadcast before the compare, so the kernels select on a vector
    mask)."""
    return jnp.broadcast_to(jnp.asarray(s["ok"], jnp.float32), like.shape) != 0.0


def _keep(ctx: MathCtx, s, new, old):
    """A state output under the guard: ``old`` when the grads went
    non-finite (costs no HBM bytes: ``old`` is already an operand)."""
    return jnp.where(_ok(s, new), new, old) if ctx.guard else new


def _g_eff(ctx: MathCtx, s, x, g):
    """Guard zeroing + clip-scale + coupled weight decay + LARS, folded into
    the stage.

    Mirrors ``optimizers._preprocess_grads`` order exactly: clip first, then
    ``wd*x + g``, then the trust ratio on the decayed gradient.  The guard
    zeroes ``g`` before all three, as the unfused path zeroes the grads
    before the update.
    """
    if ctx.guard:
        g = jnp.where(_ok(s, g), g, 0.0)
    if ctx.clip:
        g = s["gs"] * g
    if ctx.coupled_wd:
        g = ctx.wd * x + g
    if ctx.lars:
        g = s["r"] * g
    return g


def _with_nesterov(ctx: MathCtx, m_new, d):
    """The applied direction: m (heavy ball) or beta*m + d (Nesterov)."""
    return ctx.beta * m_new + d if ctx.nesterov else m_new


def _decay(ctx: MathCtx, lr, x_new):
    if ctx.decoupled_wd:
        return x_new - lr * ctx.wd * x_new
    return x_new


def staleness_damping(cfg, gap):
    """Per-gap damping factor of the staleness-aware estimator.

    ``gamma = max(sa_damping ** gap, sa_floor)`` — monotone non-increasing in
    the observed gap, exactly 1 at gap 0 (so a fresh round reduces
    ``decentlam-sa`` to ``decentlam`` bit-for-bit).  ``gap`` is the per-node
    version gap: ``(n,)`` in the stacked layout, a scalar per node inside
    shard_map, or ``None`` when the transport cannot observe staleness
    (legacy closures) — treated as fresh.
    """
    if gap is None:
        return jnp.float32(1.0)
    gap = jnp.asarray(gap).astype(jnp.float32)
    base = jnp.float32(getattr(cfg, "sa_damping", 0.5))
    floor = jnp.float32(getattr(cfg, "sa_floor", 0.0))
    return jnp.maximum(jnp.power(base, gap), floor)


def _sg_of(s, like):
    """The stage's damping factor, broadcast against a leaf value: scalar in
    the per-node (shard_map / Pallas) layout, ``(n,)`` reshaped to
    ``(n, 1, ...)`` in the stacked layout."""
    sg = s.get("sg")
    if sg is None:
        return jnp.float32(1.0)
    sg = jnp.asarray(sg)
    if sg.ndim:
        sg = sg.reshape(sg.shape + (1,) * (like.ndim - sg.ndim))
    return sg


def pre_math(op: str, ctx: MathCtx, s, **v):
    """Payload stage: f32 leaf values in ``v`` -> dict of f32 outputs."""
    lr = s["lr"]
    if op == "grad_step":
        return {"payload": v["x"] - lr * _g_eff(ctx, s, v.get("x"), v["g"])}
    if op == "identity_g":
        return {"payload": _g_eff(ctx, s, v.get("x"), v["g"])}
    if op == "momentum_payload":
        g = _g_eff(ctx, s, v["x"], v["g"])
        m = ctx.beta * v["m"] + g
        return {
            "payload": v["x"] - lr * _with_nesterov(ctx, m, g),
            "m": _keep(ctx, s, m, v["m"]),
        }
    if op == "momentum_accum":
        g = _g_eff(ctx, s, v.get("x"), v["g"])
        m = ctx.beta * v["m"] + g
        return {"payload": m, "m": _keep(ctx, s, m, v["m"])}
    if op == "x_minus_lr_m":
        return {"payload": v["x"] - lr * v["m"]}
    if op == "momentum_keep_x":
        g = _g_eff(ctx, s, v["x"], v["g"])
        m = ctx.beta * v["m"] + g
        return {"payload": v["x"], "m": _keep(ctx, s, m, v["m"])}
    if op == "qg_payload":
        g = _g_eff(ctx, s, v["x"], v["g"])
        return {"payload": v["x"] - lr * (ctx.beta * v["m"] + g)}
    if op == "d2_payload":
        g = _g_eff(ctx, s, v["x"], v["g"])
        m = ctx.beta * v["m"] + g
        z = 2.0 * v["x"] - v["x_prev"] - lr * (m - v["m_prev"])
        return {"payload": z, "m": _keep(ctx, s, m, v["m"])}
    raise ValueError(f"unknown pre op {op!r}")


def post_math(op: str, ctx: MathCtx, s, **v):
    """Recombination stage: f32 leaf values in ``v`` -> dict of f32 outputs."""
    lr = s["lr"]
    safe_lr = jnp.maximum(lr, 1e-12)
    if op == "assign_x":
        return {"x": _decay(ctx, lr, v["mix"])}
    if op == "assign_m":
        return {"m": v["mix"]}
    if op == "mix_minus_lr_m":
        return {"x": _decay(ctx, lr, v["mix"] - lr * v["m"])}
    if op == "momentum_step":
        m = ctx.beta * v["m"] + v["mix"]
        x = v["x"] - lr * _with_nesterov(ctx, m, v["mix"])
        return {"x": _decay(ctx, lr, x), "m": _keep(ctx, s, m, v["m"])}
    if op == "qg_post":
        m = ctx.beta * v["m"] + (1.0 - ctx.beta) * (v["x"] - v["mix"]) / safe_lr
        return {"x": _decay(ctx, lr, v["mix"]), "m": _keep(ctx, s, m, v["m"])}
    if op == "decentlam_post":
        g_tilde = (v["x"] - v["mix"]) / safe_lr
        m = ctx.beta * v["m"] + g_tilde
        x = v["x"] - lr * _with_nesterov(ctx, m, g_tilde)
        return {"x": _decay(ctx, lr, x), "m": _keep(ctx, s, m, v["m"])}
    if op == "decentlam_sa_post":
        # Staleness-aware DecentLaM, a gap-scheduled decentlam -> dsgd
        # interpolation.  Under stale mixing the implicit gradient carries a
        # drift ~ gap x update-magnitude that compounds through beta (the
        # sim's stale_gossip_k* divergence), so both momentum couplings are
        # damped by sg = sa_damping**gap while the mixing itself stays at
        # full channel strength:
        #     m <- beta m + (sg drift + (1 - sg) g_eff)   [damped estimator]
        #     x <- x - lr (sg beta m + drift)             [= mix - sg lr beta m]
        # sg == 1 (gap 0) is decentlam_post exactly (1*a == a, +0 absorbed);
        # sg -> 0 is ATC DSGD with a local-gradient momentum bank, the
        # configuration that is provably stable under arbitrary staleness.
        sg = _sg_of(s, v["x"])
        drift = (v["x"] - v["mix"]) / safe_lr
        g_eff = _g_eff(ctx, s, v["x"], v["g"])
        m = ctx.beta * v["m"] + (sg * drift + (1.0 - sg) * g_eff)
        if ctx.nesterov:
            applied = sg * (ctx.beta * m) + drift
        else:
            applied = sg * (ctx.beta * v["m"]) + drift
        x = v["x"] - lr * applied
        return {"x": _decay(ctx, lr, x), "m": _keep(ctx, s, m, v["m"])}
    raise ValueError(f"unknown post op {op!r}")


# ---------------------------------------------------------------------------
# Preprocessing scalars (the only reductions in the tail)
# ---------------------------------------------------------------------------


def _leaf_norm(x) -> jax.Array:
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def grad_scalars(cfg, x: Tree, g: Tree) -> dict[str, Any]:
    """Traced scalars applied inside the fused stages.

    ``gs`` — global-norm clip scale (scalar); ``r`` — LARS trust ratio (tree
    of per-leaf scalars, structure of ``x``).  Entries are 1.0 when the
    feature is off; the MathCtx flags gate their use so the kernels never
    read them in that case.
    """
    one = jnp.float32(1.0)
    s: dict[str, Any] = {"gs": one, "r": one}
    if cfg.grad_clip > 0.0:
        sq = [jnp.sum(jnp.square(l.astype(jnp.float32))) for l in jax.tree.leaves(g)]
        norm = jnp.sqrt(jnp.sum(jnp.stack(sq)))
        s["gs"] = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(norm, 1e-12))
    if cfg.lars or cfg.algorithm == "pmsgd-lars":
        gs = s["gs"]
        coupled = cfg.weight_decay > 0.0 and not cfg.decoupled_wd

        def ratio(p, gl):
            p32 = p.astype(jnp.float32)
            g32 = gs * gl.astype(jnp.float32) if cfg.grad_clip > 0.0 else gl.astype(jnp.float32)
            if coupled:
                g32 = cfg.weight_decay * p32 + g32
            pn, gn = _leaf_norm(p32), _leaf_norm(g32)
            denom = gn + cfg.weight_decay * pn + cfg.lars_eps
            return jnp.where(
                (pn > 0.0) & (gn > 0.0), cfg.lars_trust * pn / denom, 1.0
            )

        s["r"] = jax.tree.map(ratio, x, g)
    return s


# ---------------------------------------------------------------------------
# Stage executors + the phase walker
# ---------------------------------------------------------------------------

# stage(kind, op, ctx, operands, scalars, like_x) -> dict[name, Tree]
StageFn = Callable[..., dict[str, Tree]]


def _f32_tree(tree: Tree) -> Tree:
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _leaf_scalars(scalars, treedef, ctx: MathCtx):
    """Per-leaf (lr, gs, r, sg[, ok]) dicts; r may be a tree of scalars
    (LARS), sg is the staleness damping (scalar, or (n,) in the stacked
    layout), ok the finite guard's flag when a guarded stage needs it."""
    n = treedef.num_leaves
    r = scalars.get("r")
    if ctx.lars and r is not None and jax.tree.structure(r) == treedef:
        rs = treedef.flatten_up_to(r)
    else:
        rs = [r if r is not None else jnp.float32(1.0)] * n
    gs = scalars.get("gs")
    if gs is None:
        gs = jnp.float32(1.0)
    sg = scalars.get("sg")
    if sg is None:
        sg = jnp.float32(1.0)
    out = [{"lr": scalars["lr"], "gs": gs, "r": rs[i], "sg": sg} for i in range(n)]
    if ctx.guard:
        for d in out:
            d["ok"] = scalars["ok"]
    return out


def reference_stage(kind, op, ctx, operands, scalars, like_x):
    """Pure-jnp oracle executor: tree-mapped :func:`pre_math`/:func:`post_math`.

    Output dtype policy (matched by the Pallas executor): ``x`` keeps the
    dtype of ``like_x``; ``payload`` and ``m`` are f32.
    """
    names = tuple(operands)
    treedef = jax.tree.structure(operands[names[0]])
    leaf_cols = [treedef.flatten_up_to(operands[n]) for n in names]
    x_like = treedef.flatten_up_to(like_x)
    per_leaf_s = _leaf_scalars(scalars, treedef, ctx)
    math = pre_math if kind == "pre" else post_math

    out_cols: dict[str, list] = {}
    for i in range(treedef.num_leaves):
        vals = {n: col[i].astype(jnp.float32) for n, col in zip(names, leaf_cols)}
        res = math(op, ctx, per_leaf_s[i], **vals)
        for name, val in res.items():
            if name == "x":
                val = val.astype(x_like[i].dtype)
            out_cols.setdefault(name, []).append(val)
    return {n: jax.tree.unflatten(treedef, col) for n, col in out_cols.items()}


def _guard_plan(spec: UpdateSpec, cfg):
    """How :func:`run_update` applies the finite guard to ``spec``, decided
    statically: ``(folds, zero_g, unrestored)``.

    ``folds[j]`` says whether the guard folds into stage ``j`` (pre and
    post of each phase, in order, free ones included).  A stage folds when
    each state key it writes is restored against the step's original value
    (the old value is its own input, and no earlier stage wrote the key)
    and is the step's final value (no later stage reads or writes the key).
    So no stage ever reads a restored value, and payloads and params use
    the unrestored state, as the guard done around the update does.  It
    fails for awc-dmsgd's payload stage, whose momentum the recombine stage
    reads, for da-dmsgd's, whose momentum ``assign_m`` overwrites, and for
    the free ``assign_m``, which does not read the old momentum.

    ``zero_g``: ``g`` needs a ``where`` over the whole tree, because a free
    payload hands it to the communication as it is, or a stage that cannot
    fold reads it.

    ``unrestored``: the state keys the step writes that no folded stage
    restores (those of the stages that cannot fold, d2's ``x_prev`` and
    ``m_prev``, SlowMo's ``u`` and ``anchor``); each takes a ``where`` over
    its tree.
    """
    ios, free = [], []
    for i, ph in enumerate(spec.phases):
        ctx = phase_ctx(cfg, spec, i)
        ios += [pre_io(ph.pre, ctx), post_io(ph.post)]
        free += [pre_is_free(ph, ctx), post_is_free(ph, ctx)]
    folds, written, restored = [], set(), set()
    for j, (ins, outs) in enumerate(ios):
        state_outs = {k for k in outs if k not in ("x", "payload")}
        fold = all(
            k in ins
            and not any(k in o for _, o in ios[:j])
            and not any(k in i_ or k in o for i_, o in ios[j + 1:])
            for k in state_outs
        )
        folds.append(fold)
        written |= state_outs
        if fold:
            restored |= state_outs
    zero_g = any(
        "g" in ins and (is_free or not fold)
        for (ins, _), is_free, fold in zip(ios, free, folds)
    )
    if spec.d2_state:
        written |= {"x_prev", "m_prev"}
    if spec.slowmo_outer:
        written |= {"u", "anchor"}
    return folds, zero_g, written - restored


def zero_unless(ok, tree: Tree) -> Tree:
    """``tree`` with every leaf zeroed where ``ok`` is False; ``tree`` itself
    when there is no guard (``ok`` is None)."""
    if ok is None:
        return tree
    return jax.tree.map(lambda a: jnp.where(ok, a, jnp.zeros_like(a)), tree)


def run_update(
    spec: UpdateSpec,
    cfg,
    *,
    x: Tree,
    g: Tree,
    state: dict[str, Tree],
    lr,
    step_idx,
    gossip,
    mean,
    comp_state: Tree,
    stage: StageFn = reference_stage,
    node_gaps=None,
    scalars: dict | None = None,
    ok=None,
):
    """Walk the spec's phases; returns ``(x, new_state, comp_state)``.

    ``x`` may be any float dtype (the stages compute in f32 and cast the
    parameter output back); ``g`` and the state buckets are f32.  ``stage``
    selects the executor: :func:`reference_stage` or the Pallas engine's
    (see ``repro.kernels.fused_update.make_plane_stage``).

    ``node_gaps`` overrides the per-node gossip version gaps a
    staleness-aware spec folds into its stages (``(n,)`` stacked / scalar
    per node inside shard_map).  Default: derived from the channel's own
    state after each gossip round (:meth:`GossipChannel.node_gaps`); engines
    that know staleness out of band — the discrete-event simulator reading
    snapshot versions — pass it explicitly.  Ignored by the other specs.

    ``scalars`` overrides the gradient-preprocessing scalars (``gs``, ``r``)
    normally derived here by :func:`grad_scalars`.  The flat-plane path uses
    it: per-leaf norms cannot be read off the packed buffers, so
    :func:`repro.core.planes.plane_scalars` computes them on the original
    trees (bit-identical to this default) and hands them in with the LARS
    tree already converted to row-indexed columns.

    ``ok`` (a traced bool scalar, or ``None`` for no guard) is the finite
    guard: when it is False the update runs with ``g = 0`` and the state
    comes back as it went in, while ``x`` keeps its ``g = 0`` update.  The
    stages fold it in (``MathCtx.guard``, the flag in the stage scalars'
    ``ok``), so it costs no pass over the planes where a stage already
    reads ``g`` and the old state.  Where ``g`` or a state key goes around
    a stage (a free payload, a free ``assign_m``, awc-dmsgd's momentum, the
    d2 and SlowMo state), a ``jnp.where`` over that tree keeps the same
    result.  Caller-supplied ``scalars`` must come from zeroed grads.
    """
    lr = jnp.asarray(lr, jnp.float32)
    safe_lr = jnp.maximum(lr, 1e-12)
    guard = ok is not None
    if scalars is None:
        scalars = grad_scalars(cfg, x, zero_unless(ok, g))
    scalars = dict(scalars)
    scalars["lr"] = lr
    if guard:
        scalars["ok"] = jnp.asarray(ok).astype(jnp.float32)
        folds, zero_g, unrestored = _guard_plan(spec, cfg)
        if zero_g:
            g = zero_unless(ok, g)

    env: dict[str, Tree] = {"x": x, "g": g}
    for k in ("m", "x_prev", "m_prev"):
        if k in state:
            env[k] = state[k]
    x0 = x

    def run_stage(kind, op, ctx, ins, j, mixed=None):
        """One stage of the walk (stage ``j`` of the guard's ``folds``)."""
        if guard and folds[j]:
            ctx = dataclasses.replace(ctx, guard=True)
        operands = {n: (mixed if n == "mix" else env[n]) for n in ins}
        return stage(kind, op, ctx, operands, scalars, env["x"])

    for i, ph in enumerate(spec.phases):
        ctx = phase_ctx(cfg, spec, i)

        # --- PRE: build the payload (and usually the new momentum) ---------
        if pre_is_free(ph, ctx):
            payload = _f32_tree(env["g"])  # nothing to fuse (zeroed: zero_g)
        else:
            ins, _ = pre_io(ph.pre, ctx)
            out = run_stage("pre", ph.pre, ctx, ins, 2 * i)
            payload = out.pop("payload")
            env.update(out)

        # --- COMM ----------------------------------------------------------
        if ph.comm == "gossip":
            # ``gossip`` is either a GossipChannel (the transport API) or a
            # legacy closure ``(tree, step, comp_state) -> (tree, comp_state)``
            if isinstance(gossip, GossipChannel):
                comp_state, mixed = gossip.apply(comp_state, payload, step_idx)
            else:
                mixed, comp_state = gossip(payload, step_idx, comp_state)
            if spec.staleness_aware:
                # the gap the round just executed actually used (post-apply
                # state carries the warmup-aware count), unless the engine
                # observed staleness out of band and told us
                gaps = node_gaps
                if gaps is None and isinstance(gossip, GossipChannel):
                    gaps = gossip.node_gaps(comp_state)
                scalars["sg"] = staleness_damping(cfg, gaps)
        elif ph.comm == "mean":
            mixed = mean(payload)
        else:
            mixed = payload
        last_mixed = mixed

        # --- POST: recombine -----------------------------------------------
        if post_is_free(ph, ctx):
            if ph.post == "assign_m":
                env["m"] = _f32_tree(mixed)
            else:  # assign_x
                env["x"] = jax.tree.map(
                    lambda p, v: v.astype(p.dtype), env["x"], mixed
                )
        else:
            ins, _ = post_io(ph.post)
            env.update(run_stage("post", ph.post, ctx, ins, 2 * i + 1, mixed))

    x = env["x"]
    new_state = dict(state)
    if "m" in state:
        new_state["m"] = env["m"]
    if spec.d2_state:
        new_state["x_prev"] = _f32_tree(x0)
        new_state["m_prev"] = env["m"]

    if spec.slowmo_outer:

        # the sync must see the f32 inner-step result: for low-precision
        # params, quantize-then-average loses bits that (anchor - xbar)/lr
        # amplifies by 1/lr.  The final phase's gossip output *is* the new x
        # in f32 (slowmo's inner post is assign_x), so average that.
        x32 = _f32_tree(last_mixed)

        def sync(args):
            xc, u, anchor = args
            xbar = mean(x32)
            u = jax.tree.map(
                lambda uu, a, xb: cfg.slowmo_momentum * uu + (a - xb) / safe_lr,
                u,
                anchor,
                xbar,
            )
            xs = jax.tree.map(lambda a, uu: a - cfg.slowmo_lr * lr * uu, anchor, u)
            xo = jax.tree.map(lambda p, v: v.astype(p.dtype), xc, xs)
            return xo, u, xs

        def no_sync(args):
            return args

        do_sync = (step_idx + 1) % cfg.slowmo_period == 0
        x, u, anchor = jax.lax.cond(
            do_sync, sync, no_sync, (x, state["u"], state["anchor"])
        )
        new_state["u"] = u
        new_state["anchor"] = anchor
        if cfg.weight_decay > 0.0 and cfg.decoupled_wd:
            x = jax.tree.map(
                lambda p: (
                    p.astype(jnp.float32)
                    - lr * cfg.weight_decay * p.astype(jnp.float32)
                ).astype(p.dtype),
                x,
            )

    if guard:
        for k in [k for k in new_state if k in unrestored]:
            new_state[k] = jax.tree.map(
                lambda a, b: jnp.where(ok, a, b), new_state[k], state[k]
            )
    return x, new_state, comp_state
