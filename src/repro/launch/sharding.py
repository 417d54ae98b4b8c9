"""The sharding planner: DP/TP/EP/SP specs for every tensor of every arch.

Rules (Megatron-style TP pairs, EP for divisible expert counts, SP fallback
for the batch=1 long-context cells), all divisibility-checked against the
mesh — a dimension that does not divide falls back to replication rather
than failing to lower.  This is the "parallelize" recipe of the daisy
scheduler operating at the framework level: the canonical contraction of
each layer determines which axis its parallel loop maps to.

  column-parallel (wq/wg/wu/in_proj/...):  (..., D, F) -> (..., None, model)
  row-parallel    (wo/wd/out_proj/...):    (..., F, D) -> (..., model, None)
  expert weights  (E, D, F): EP (model, None, None) when E%model==0,
                             else TP on the trailing dims
  embed (V, D): vocab-parallel when V%model==0 else feature-parallel
  batch dims: (pod, data); KV caches (L, B, KV, S, dh): batch -> DP,
              heads -> model when divisible; batch=1 decode shards the
              cache *sequence* (SP)
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig
from .mesh import dp_axes

Pytree = Any


def _msize(mesh) -> int:
    # a mesh without a model axis (e.g. the pure-DP column mesh of the
    # sharded canonical-program path) has TP size 0: every divisibility
    # check fails and all rules fall back to replication instead of
    # emitting specs that name a nonexistent axis
    return mesh.shape.get("model", 0)


def _dpsize(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------
_COLUMN = ("wq", "wk", "wv", "wg", "wu", "in_proj", "dt_proj", "wz", "wi",
           "wf", "wo_gate", "conv_w")
_ROW = ("wo", "wd", "out_proj", "x_proj")


def _param_rule(path: str, shape: tuple[int, ...], mesh, cfg=None) -> P:
    m = _msize(mesh)
    nd = len(shape)
    leaf = path.split("/")[-1].strip("'[]")

    def pad(spec: list) -> P:
        return P(*([None] * (nd - len(spec)) + spec))

    # GQA: a head-count that does not divide the model axis cannot keep its
    # (B, S, heads, dh) reshape sharded (XLA "involuntary full remat" —
    # replicates the tensor).  Shard the *contracting* dim instead
    # (row-parallel: psum'd, output replicated over model).
    if cfg is not None and leaf in ("wq", "wk", "wv") and nd >= 2 and m > 0:
        heads = cfg.n_heads if leaf == "wq" else cfg.n_kv_heads
        if heads % m != 0:
            return pad(["model" if _div(shape[-2], m) else None, None])

    if leaf == "embed":
        if _div(shape[0], m):
            return P("model", None)
        return P(None, "model" if _div(shape[1], m) else None)
    if leaf == "lm_head":
        return P(None, "model" if _div(shape[1], m) else None)
    # MoE expert tensors: (..., E, D, F) with E the -3rd dim
    if "ffn" in path and leaf in ("wg", "wu", "wd") and nd >= 3:
        e = shape[-3]
        if _div(e, m):
            return pad(["model", None, None])  # EP
        if leaf in ("wg", "wu"):
            return pad([None, None, "model" if _div(shape[-1], m) else None])
        return pad([None, "model" if _div(shape[-2], m) else None, None])
    if leaf == "router":
        return P(*([None] * nd))
    if leaf in _COLUMN and nd >= 2:
        return pad([None, "model" if _div(shape[-1], m) else None])
    if leaf in _ROW and nd >= 2:
        return pad(["model" if _div(shape[-2], m) else None, None])
    if leaf in ("bq", "bk", "bv") and nd >= 1:
        return pad(["model" if _div(shape[-1], m) else None])
    if leaf in ("A_log", "Dskip", "conv_b", "dt_bias"):
        # mamba per-channel tensors: shard d_inner (first trailing dim)
        if nd >= 2:
            return pad(["model" if _div(shape[-2], m) else None, None])
        return pad(["model" if _div(shape[-1], m) else None])
    return P(*([None] * nd))  # norms, biases, scalars


def _add_fsdp(spec: P, shape: tuple[int, ...], mesh, exclude_last: bool = False) -> P:
    """Shard one more dim over the DP axes (ZeRO-3/FSDP): parameters and
    optimizer state then scale 1/(dp*model) per device; XLA all-gathers each
    scanned layer's weights on use and reduce-scatters its gradients."""
    dp = dp_axes(mesh)
    dpn = _dpsize(mesh)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    # candidate dims: largest first; skip already-sharded; skip the leading
    # stack dim of scanned layers (slicing a sharded stack dim regathers)
    cands = sorted(range(len(shape)), key=lambda d: -shape[d])
    for d in cands:
        if entries[d] is not None:
            continue
        if d == 0 and len(shape) >= 3:
            continue
        if exclude_last and d == len(shape) - 1:
            continue
        if _div(shape[d], dpn) and shape[d] >= dpn:
            entries[d] = dp if len(dp) > 1 else dp[0]
            break
    return P(*entries)


def param_specs(params_shape: Pytree, mesh, fsdp: bool = False, cfg=None) -> Pytree:
    def spec_of(path, leaf):
        p = "/".join(str(x) for x in path)
        leafname = p.split("/")[-1].strip("'[]")
        spec = _param_rule(p, tuple(leaf.shape), mesh, cfg)
        if fsdp and leaf.ndim >= 2:
            # qkv head-flat output dims excluded: FSDP there would reshard
            # across the (heads, dh) reshape (the involuntary-remat trap)
            spec = _add_fsdp(spec, tuple(leaf.shape), mesh,
                             exclude_last=leafname in ("wq", "wk", "wv"))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(spec_of, params_shape)


# ---------------------------------------------------------------------------
# batch / state / metric specs
# ---------------------------------------------------------------------------
def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh, batch_shape: Pytree,
                axes: tuple[str, ...] | None = None) -> Pytree:
    dp = axes if axes is not None else dp_axes(mesh)
    dpn = int(np.prod([mesh.shape[a] for a in dp]))

    def spec_of(path, leaf):
        b = leaf.shape[0] if leaf.ndim else 0
        first = dp if _div(b, dpn) else None
        return NamedSharding(mesh, P(first, *([None] * (leaf.ndim - 1))))

    return jax.tree_util.tree_map_with_path(spec_of, batch_shape)


def state_specs(cfg: ModelConfig, mesh, state_shape: Pytree) -> Pytree:
    """Decode-state sharding: batch -> DP; KV heads -> model if divisible;
    batch=1 (long-context): shard cache sequence over DP instead (SP)."""
    dp = dp_axes(mesh)
    dpn = _dpsize(mesh)
    m = _msize(mesh)

    def spec_of(path, leaf):
        p = "/".join(str(x) for x in path)
        nd = leaf.ndim
        if nd == 0:
            return NamedSharding(mesh, P())
        if "memory" in p and nd == 3:  # (B, S_mem, D)
            b = leaf.shape[0]
            return NamedSharding(
                mesh, P(dp if _div(b, dpn) else None, None,
                        "model" if _div(leaf.shape[2], m) else None))
        # KV caches: (L, B, KV, S, dh), head-major, the sequence at dim 3;
        # mamba/mlstm states (L, B, ...) keep their dim 2 whole
        if nd >= 3:
            b = leaf.shape[1]
            seq = 3 if nd == 5 else 2
            spec = [None] * nd
            if _div(b, dpn):
                spec[1] = dp
            elif nd >= 4 and _div(leaf.shape[seq], dpn):
                # SP: batch too small -> shard the sequence dim of the cache
                spec[seq] = dp
            if spec[1] is not None or nd >= 4:
                # shard a feature dim over model when possible
                for d in range(2, nd):
                    if d != seq and _div(leaf.shape[d], m):
                        spec[d] = "model"
                        break
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P(*([None] * nd)))

    return jax.tree_util.tree_map_with_path(spec_of, state_shape)


def replicated(mesh, tree_shape: Pytree) -> Pytree:
    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(mesh, P(*([None] * leaf.ndim))), tree_shape
    )
