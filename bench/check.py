"""The comparison that decides ``correct``.

Three numbers, each against a limit of its own (``bench/limits/<cell>.json``):

* ``loss``: the widest relative gap between the program's loss and the
  reference's over the checked steps;
* ``grad``: by the worst leaf, the gap between the norms of the gradient as
  the optimizer got it (the momentum after step 1), over the larger of the
  reference's norm of that leaf and of the median leaf;
* ``change``: the same for the parameters' change over the checked steps,
  leaving out leaves whose reference gradient is under a thousandth of the
  median leaf's (they move by rounding alone).

A leaf is one tensor of one layer on one node.  A number that is not
finite fails.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("grad", "change", "grad_median", "change_median")
STILL = 1e-3  # a leaf whose reference gradient is under this x the median


def _gaps(got: dict, ref: dict, keep: dict | None = None):
    """(worst, where, median) of the per-leaf gaps."""
    med = float(np.median(np.concatenate([np.ravel(v) for v in ref.values()])))
    worst, where, every = 0.0, "", []
    for path, r in ref.items():
        gap = np.ravel(np.abs(got[path] - r) / np.maximum(r, med))
        if keep is not None:
            gap = gap[np.ravel(keep[path])]
        if not np.all(np.isfinite(gap)):
            return math.inf, path, math.inf
        every.append(gap)
        if gap.size and float(gap.max()) > worst:
            worst, where = float(gap.max()), f"{path}[{int(gap.argmax())}]"
    return worst, where, float(np.median(np.concatenate(every)))


def numbers(got: dict, ref: dict) -> dict[str, tuple[float, str]]:
    """{name: (value, where)} of the three numbers."""
    losses = [abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"])]
    if len(got["loss"]) != len(ref["loss"]) or not all(map(math.isfinite, losses)):
        loss = (math.inf, "non-finite or missing loss")
    else:
        i = int(np.argmax(losses))
        loss = (float(losses[i]), f"step {i + 1}")
    raw = ref["raw_grad"]
    med = float(np.median(np.concatenate([np.ravel(v) for v in raw.values()])))
    keep = {p: v >= STILL * med for p, v in raw.items()}
    gw, gwhere, gmed = _gaps(got["grad"], ref["grad"])
    cw, cwhere, cmed = _gaps(got["change"], ref["change"], keep)
    return {"loss": loss, "grad": (gw, gwhere), "change": (cw, cwhere),
            "grad_median": (gmed, "median leaf"), "change_median": (cmed, "median leaf")}


def judge(nums: dict, limits: dict) -> tuple[bool, list[dict]]:
    """(correct, [{name, value, limit, where}]) with every number beside its
    limit."""
    rows = []
    ok = True
    for name in NAMES:
        value, where = nums[name]
        limit = float(limits[name])
        ok &= math.isfinite(value) and value <= limit
        rows.append({"name": name, "value": value, "limit": limit, "where": where})
    return ok, rows


def leaf_norms(tree):
    """Per (node, layer) L2 norms of a node-stacked tree: leaves under
    ``groups`` keep their layer axis, the others reduce to (node,)."""

    def norm(path, x):
        keep = 2 if path[0].key == "groups" else 1
        axes = tuple(range(keep, x.ndim))
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes))

    return jax.tree_util.tree_map_with_path(norm, tree)


def flat_norms(tree) -> dict[str, np.ndarray]:
    """{"path": host array} of a norm tree."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[jax.tree_util.keystr(path)] = np.asarray(v, np.float64)
    return out
