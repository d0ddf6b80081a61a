"""Checkpoint / restart / elastic rescale.

Fault-tolerance contract (DESIGN.md §6):

* **atomic**: state is written to ``<dir>/tmp.<step>`` then renamed to
  ``<dir>/step_<k>`` — a crash mid-write never corrupts the latest
  checkpoint;
* **exact restart**: restoring with the same node count is bit-identical
  (stacked per-node replicas + optimizer state + step counter);
* **elastic rescale**: restoring with a different node count
  consensus-collapses the replicas (the decentralized average *is* the
  model — paper Sec. 3) and re-broadcasts to the new node set; momentum is
  mean-collapsed the same way.  Topology/weights are re-derived by the
  caller for the new n.

Storage is .npz per pytree bucket + a JSON manifest; keys are the pytree
paths, so restore needs no pickled treedefs.  For multi-host pods each
process would write its address-space shard under ``shard_<proc>/`` — the
single-process container writes one shard.

Manifest format v3 records every bucket's dtype by name (``"dtypes"``):
dtypes numpy cannot natively round-trip through npz (bfloat16 saves as an
opaque 2-byte void; the fp8 plane-bucket dtypes ``float8_e4m3fn`` /
``float8_e5m2`` as 1-byte voids) are restored by *declared* dtype, not by
sniffing the void width; unknown declared names fail with a clean
``ValueError``.  V2 checkpoints (no ``"dtypes"`` entry) still restore
through the legacy sniff — bf16 was the only 2-byte void V2 ever stored —
pinned by a migration test in ``tests/test_checkpoint.py``.  Every
manifest is stamped with the plane layout's shard metadata
(``"plane_tp"``, per-bucket local ``"plane_rows"``), the key that lets a
resume at a different tensor-parallel degree rebuild the written layout
and reconcile the plane-form optimizer state.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Tree = Any

__all__ = [
    "save_checkpoint",
    "restore_checkpoint",
    "check_plane_manifest",
    "latest_step",
    "elastic_reshape",
]


def _flatten(tree: Tree) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in path
        )
        flat[key] = np.asarray(leaf)
    return flat


def _resolve_dtype(name: str) -> np.dtype:
    """Manifest dtype name -> numpy dtype.

    Non-native names (bfloat16 and the fp8 plane-bucket dtypes
    ``float8_e4m3fn`` / ``float8_e5m2``) resolve through ``ml_dtypes`` —
    they round-trip npz as opaque voids and are reinterpreted by declared
    dtype on restore.  Anything neither numpy nor ml_dtypes knows is a
    corrupt or future-format manifest: fail with a clean error instead of
    silently misreading bytes.
    """
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        try:
            return np.dtype(getattr(ml_dtypes, name))
        except (AttributeError, TypeError):
            raise ValueError(
                f"checkpoint manifest declares unknown dtype {name!r} "
                f"(not a numpy dtype and not in ml_dtypes) — the "
                f"checkpoint was written by an incompatible version"
            ) from None


def _npz_native(d: np.dtype) -> bool:
    """True when numpy's npz format round-trips ``d`` by itself.

    ml_dtypes extension dtypes are not: bf16/e4m3fn serialize as opaque
    voids, and ``float8_e5m2`` (registered with kind ``'f'``) writes a
    ``'<f1'`` descr numpy cannot even parse back.  Non-native buckets are
    stored as same-width void *views* and restored by the manifest's
    declared dtype.
    """
    if d.kind == "V":  # extension voids (bf16, e4m3fn): store as plain voids
        return False
    try:
        from numpy.lib.format import descr_to_dtype, dtype_to_descr

        return descr_to_dtype(dtype_to_descr(d)) == d
    except (ValueError, TypeError):
        return False


def _unflatten(flat: dict[str, np.ndarray], dtypes: dict | None = None) -> Tree:
    """Rebuild the pytree; ``dtypes`` is the v3 manifest's per-bucket dtype
    map (restore-by-declaration).  ``None`` = v2: fall back to sniffing the
    2-byte void that numpy round-trips bfloat16 into."""
    tree: Tree = {}
    for key, val in flat.items():
        if dtypes is not None:
            want = _resolve_dtype(dtypes[key])
            if val.dtype != want:
                # npz stored an opaque void for a non-native dtype:
                # reinterpret as the declared bucket dtype
                assert val.dtype.kind == "V" and val.dtype.itemsize == want.itemsize, (
                    key, val.dtype, want,
                )
                val = val.view(want)
        elif val.dtype == np.dtype("V2"):
            # legacy v2 manifest (no "dtypes"): bf16 is the only 2-byte
            # void v2 ever stored — flat-plane buffers keep bucket dtype
            import ml_dtypes

            val = val.view(ml_dtypes.bfloat16)
        parts = key.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jnp.asarray(val)
    return tree


def save_checkpoint(directory: str, state: Tree, *, plane_layout,
                    metadata: dict | None = None):
    """Write one atomic checkpoint under ``directory``.

    ``plane_layout`` (the training run's :class:`PlaneLayout`, the one its
    plane-form optimizer and channel state are packed in) stamps the V3
    manifest with shard metadata — ``plane_tp`` and the per-bucket local
    row counts — so a resume at a different tensor-parallel degree can
    rebuild the *written* layout and convert the plane-form optimizer state
    through ``reconcile_plane_state(..., stored_layout=...)``.
    """
    step = int(state["step"])
    os.makedirs(directory, exist_ok=True)
    tmp = tempfile.mkdtemp(prefix=f"tmp.{step}.", dir=directory)
    try:
        flat = _flatten(state)
        np.savez(
            os.path.join(tmp, "state.npz"),
            **{
                k: v if _npz_native(v.dtype)
                else v.view(np.dtype(f"V{v.dtype.itemsize}"))
                for k, v in flat.items()
            },
        )
        manifest = {
            "format": 3,
            "step": step,
            "keys": sorted(flat),
            "dtypes": {k: v.dtype.name for k, v in flat.items()},
            "n_nodes": int(state["params"][next(iter(state["params"]))]["table"].shape[0])
            if "embed" in state.get("params", {})
            else None,
            "plane_tp": int(plane_layout.tp),
            "plane_model_axis": plane_layout.model_axis,
            "plane_rows": {k: int(v) for k, v in plane_layout.rows.items()},
            **(metadata or {}),
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=2)
        final = os.path.join(directory, f"step_{step:08d}")
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)  # atomic publish
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return os.path.join(directory, f"step_{step:08d}")


def check_plane_manifest(manifest: dict, stored_layout) -> None:
    """Cross-check a resume's rebuilt stored :class:`PlaneLayout` against
    the V3 manifest's shard metadata (``plane_rows`` / ``plane_model_axis``).

    The resume path reconstructs the written layout purely from the
    current model config plus the manifest's ``plane_tp``; if the model
    config drifted between write and resume, the rebuilt layout silently
    disagrees with the one the planes were packed with and the mismatch
    only surfaces as a shape assert deep inside ``unpack``.  This check
    fails fast with an actionable error instead.  Manifests without plane
    metadata (written before every checkpoint carried it) pass through
    untouched.
    """
    rows = manifest.get("plane_rows")
    if rows is not None:
        actual = {k: int(v) for k, v in stored_layout.rows.items()}
        declared = {k: int(v) for k, v in rows.items()}
        if declared != actual:
            raise ValueError(
                f"checkpoint manifest plane_rows {declared} do not match "
                f"the layout rebuilt from the current model config at "
                f"tp={stored_layout.tp} ({actual}) — the model config "
                f"changed between checkpoint write and resume, so the "
                f"stored planes cannot be reinterpreted"
            )
    axis = manifest.get("plane_model_axis")
    if axis is not None and axis != stored_layout.model_axis:
        raise ValueError(
            f"checkpoint manifest plane_model_axis {axis!r} does not match "
            f"the current layout's model axis {stored_layout.model_axis!r}"
        )


def latest_step(directory: str) -> int | None:
    if not os.path.isdir(directory):
        return None
    steps = [
        int(d.split("_")[1])
        for d in os.listdir(directory)
        if d.startswith("step_") and d.split("_")[1].isdigit()
    ]
    return max(steps) if steps else None


def restore_checkpoint(directory: str, step: int | None = None) -> tuple[Tree, dict]:
    step = latest_step(directory) if step is None else step
    assert step is not None, f"no checkpoints under {directory}"
    d = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    with np.load(os.path.join(d, "state.npz")) as z:
        flat = {k: z[k] for k in z.files}
    state = _unflatten(flat, manifest.get("dtypes"))
    # pre-channel checkpoints stored compression error-feedback under "comp";
    # the GossipChannel state bucket nests it as channel["comp"]
    if "comp" in state:
        state["channel"] = {"comp": state.pop("comp")}
    state.setdefault("channel", {})  # empty-subtree keys are dropped by savez
    return state, manifest


def elastic_reshape(state: Tree, new_n_nodes: int) -> Tree:
    """Consensus-collapse the stacked replicas and re-broadcast to a new n.

    Works for both shrink (node failure) and grow (scale-out).  Channel
    state — compression error feedback, delay ring buffers, telemetry — is
    reset to zeros (it is node-local by definition, and buffered payloads
    from the old cluster shape are meaningless on the new one; the delayed
    channels re-warm from fresh gossip, which round 0 treats as delay 0).
    """

    def collapse(x):
        mean = jnp.mean(jnp.asarray(x, jnp.float32), axis=0, keepdims=True)
        out = jnp.broadcast_to(mean, (new_n_nodes,) + x.shape[1:])
        return out.astype(x.dtype)

    new = dict(state)
    new["params"] = jax.tree.map(collapse, state["params"])
    new["opt"] = jax.tree.map(collapse, state.get("opt", {}))
    new["channel"] = jax.tree.map(
        lambda x: jnp.zeros_like(collapse(x)), state.get("channel", {})
    )
    return new
