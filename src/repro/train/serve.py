"""Serve step builders (prefill / decode) over the production mesh.

Serving uses the *consensus* model: parameters are replicated across the
node axes (the decentralized average is the model you ship — see README
§"Serving while training" for how snapshots are published off the training
fleet) and sharded only over the model axis.  Request batches shard across
the node axes when divisible; otherwise they stay replicated — the
``_batch_axes`` fallback, hit e.g. by a single-request batch on a multi-node
mesh (``tests/test_serve_specs.py`` + ``tests/scripts/distributed_serve.py``
pin both paths).  KV caches are sequence-sharded over the model axis:
each model shard owns a contiguous slice of cache slots and decode merges
partial attention with a split-K softmax reduction (``models/attention.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..models import transformer as T
from ..models.layers import TPContext

Tree = Any

__all__ = ["ServeConfig", "build_prefill_step", "build_decode_step", "serve_specs"]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    runtime: T.RuntimeConfig = T.RuntimeConfig()
    target_len: int = 0  # cache capacity target (0 -> prefill length)


def _batch_axes(global_batch: int, node_axes: tuple[str, ...], mesh):
    n = 1
    for a in node_axes:
        n *= mesh.shape[a]
    return node_axes if global_batch % n == 0 and global_batch >= n else None


def serve_specs(
    cfg: ModelConfig, mesh, *, global_batch: int,
    node_axes: tuple[str, ...] = ("data",), model_axis: str = "model",
):
    ba = _batch_axes(global_batch, node_axes, mesh)
    pspecs = T.param_specs(cfg, mesh.shape[model_axis], model_axis, serve=True)
    cspecs = T.cache_specs(cfg, ba, model_axis)
    tok = P(ba, None)
    return pspecs, cspecs, tok, ba


def build_prefill_step(
    cfg: ModelConfig, mesh, scfg: ServeConfig, *, global_batch: int,
    node_axes: tuple[str, ...] = ("data",), model_axis: str = "model",
):
    tp = mesh.shape[model_axis]
    tp_ctx = TPContext(axis=model_axis, size=tp, in_shard_map=True)
    pspecs, cspecs, tok_spec, ba = serve_specs(
        cfg, mesh, global_batch=global_batch,
        node_axes=node_axes, model_axis=model_axis,
    )

    bspec: Tree = {"tokens": tok_spec}
    if cfg.family == "vlm":
        bspec["patch_embeds"] = P(ba, None, None)
    if cfg.arch_kind == "encdec":
        bspec["enc_frames"] = P(ba, None, None)

    def fn(params, batch):
        return T.prefill(
            params, batch, cfg, tp_ctx, scfg.runtime,
            target_len=scfg.target_len or batch["tokens"].shape[1],
        )

    sm = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(pspecs, bspec),
        out_specs=(P(ba, model_axis), cspecs),  # logits vocab-sharded
        axis_names=set(node_axes) | {model_axis},
    )
    return jax.jit(sm), (pspecs, bspec, cspecs)


def build_decode_step(
    cfg: ModelConfig, mesh, scfg: ServeConfig, *, global_batch: int,
    target_len: int, per_slot_t: bool = False,
    node_axes: tuple[str, ...] = ("data",), model_axis: str = "model",
):
    """One-token decode step.  With ``per_slot_t`` the position argument is
    a ``(global_batch,)`` int32 vector (sharded with the batch) instead of
    a shared scalar — the continuous-batching scheduler runs slots whose
    request timelines are independent."""
    tp = mesh.shape[model_axis]
    tp_ctx = TPContext(axis=model_axis, size=tp, in_shard_map=True)
    pspecs, cspecs, tok_spec, ba = serve_specs(
        cfg, mesh, global_batch=global_batch,
        node_axes=node_axes, model_axis=model_axis,
    )

    def fn(params, tokens, cache, t):
        return T.decode_step(
            params, tokens, cache, t, cfg, tp_ctx, scfg.runtime,
            target_len=target_len,
        )

    t_spec = P(ba) if per_slot_t else P()
    sm = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(pspecs, tok_spec, cspecs, t_spec),
        out_specs=(P(ba, model_axis), cspecs),  # logits vocab-sharded
        axis_names=set(node_axes) | {model_axis},
    )
    return jax.jit(sm, donate_argnums=(2,)), (pspecs, tok_spec, cspecs)
