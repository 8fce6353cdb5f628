"""Tensor-parallel serving: heads and the paged pool across a device mesh.

``Engine.serve(mesh=...)`` (or ``shards=N``) runs the continuous-batching
loop under a 1-D ``("model",)`` mesh: attention heads (dense/GQA) or the MLA
latent rank shard across the axis, and the paged block POOL partitions with
them — each device holds its heads' slice of every block, so per-device pool
memory drops to ~1/N while block tables, rope keys, and all allocator
metadata stay replicated/host-side and shard-agnostic. The allocator never
learns about the mesh: block ids mean the same thing on every device, so
refcounting, copy-on-write, and eviction apply symmetrically to every shard
by construction.

This module is the host-side half: shard validation (loud errors instead of
GSPMD padding surprises), parameter/cache placement, the per-device pool
accounting the benchmarks gate on, and the single-device-vs-sharded parity
check. The device-side half is the ``ctx.shard`` carry constraints in
``models/attention.py`` / ``models/mla.py`` under
:func:`repro.distributed.sharding.serving_rules`.

On CPU hosts, simulate a mesh with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (before the first jax
import) — the whole path is exercised this way in CI.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.distributed.sharding import ShardingRules, serving_rules
from repro.models import kv_cache

MODEL_AXIS = "model"

_SHARD_RECIPE = ("on CPU hosts simulate devices with XLA_FLAGS="
                 "--xla_force_host_platform_device_count=N set before the "
                 "first jax import (see README, 'Multi-device serving')")


def validate_serving_shards(cfg, n_shards: int) -> None:
    """Reject shard counts the model cannot split evenly across — BEFORE any
    device placement, with the failing dimension named. GSPMD would silently
    pad a non-dividing head count; serving demands exact partitions so every
    device owns whole heads (whole latent lanes for MLA) of every pool block.
    """
    n = int(n_shards)
    if n <= 1:
        return
    if cfg.family in ("ssm", "hybrid", "encdec"):
        raise ValueError(
            f"tensor-parallel serving shards attention heads; family "
            f"{cfg.family!r} decodes through state/ring caches that have no "
            f"head axis to split — serve it single-device (mesh=None)")
    if cfg.n_heads % n:
        raise ValueError(
            f"n_heads={cfg.n_heads} is not divisible by shards={n}; pick a "
            f"shard count dividing the head count (divisors of "
            f"{cfg.n_heads})")
    if cfg.attention == "mla":
        if cfg.kv_lora_rank % n:
            raise ValueError(
                f"kv_lora_rank={cfg.kv_lora_rank} is not divisible by "
                f"shards={n}; the MLA latent pool partitions on the rank "
                f"dim, so shards must divide it")
    elif cfg.n_kv_heads % n:
        raise ValueError(
            f"n_kv_heads={cfg.n_kv_heads} is not divisible by shards={n}; "
            f"the KV pool partitions on the kv-head dim, so shards must "
            f"divide it (GQA with fewer KV heads than shards would need "
            f"KV replication, which serve() does not do)")


def validate_serving_mesh(cfg, mesh) -> None:
    """A serving mesh must carry the ``"model"`` axis and split the model
    evenly across it (``validate_serving_shards``)."""
    if MODEL_AXIS not in mesh.axis_names:
        raise ValueError(
            f"serving mesh needs a {MODEL_AXIS!r} axis to shard heads "
            f"across; got axes {tuple(mesh.axis_names)} — build one with "
            f"repro.launch.mesh.make_serving_mesh(shards); {_SHARD_RECIPE}")
    validate_serving_shards(cfg, mesh.shape[MODEL_AXIS])


def _place(tree, axes_tree, rules: ShardingRules, mesh):
    # lazy: launch.specs imports serving.engine — a top-level import here
    # would cycle through serving/__init__
    from repro.launch.specs import sharding_for

    return jax.tree.map(
        lambda v, ax: jax.device_put(v, sharding_for(v.shape, ax, mesh,
                                                     rules)),
        tree, axes_tree)


def _row_parallel(ax, rules: ShardingRules) -> bool:
    """A weight whose contraction feeds the replicated residual stream (wo:
    ("heads","embed"), mlp down: ("mlp","embed"), the embedding table's logit
    use: ("vocab","embed")) — sharding these turns their matmul into
    partial-sum + psum, whose reduction order differs from single-device and
    breaks bitwise greedy parity. Serving keeps them replicated; the paired
    ``tp_collect`` activation constraints gather their inputs."""
    return (isinstance(ax, tuple) and len(ax) >= 2 and ax[-1] == "embed"
            and any(_maps_to_model(rules, a) for a in ax[:-1]))


def shard_params(params, axes_tree, rules: ShardingRules, mesh):
    """device_put every parameter to its serving NamedSharding: column-
    parallel weights (qkv / gate / up / MLA up-projections) shard on the
    model axis, row-parallel weights (see :func:`_row_parallel`) and norms
    replicate. ``axes_tree`` is ``Model.param_axes()`` — same treedef as the
    values tree."""
    from repro.launch.specs import sharding_for

    def put(v, ax):
        if _row_parallel(ax, rules):
            ax = (None,) * len(ax)
        return jax.device_put(v, sharding_for(v.shape, ax, mesh, rules))

    return jax.tree.map(put, params, axes_tree)


def place_cache(cache, axes_tree, rules: ShardingRules, mesh):
    """device_put a zeroed serving cache to the serving layout: pools
    partition on kv-heads (or the MLA latent rank), tables/rings/rope-keys
    replicate. Matching the in-graph carry constraints exactly means the
    donated cache never relayouts between steps."""
    return _place(cache, axes_tree, rules, mesh)


def _maps_to_model(rules: ShardingRules, logical: Optional[str]) -> bool:
    ax = rules.mesh_axes(logical)
    return ax == MODEL_AXIS or (isinstance(ax, tuple) and MODEL_AXIS in ax)


def pool_report(cfg, slots: int, cache_len: int, block_size: int,
                num_blocks: int, n_shards: int,
                rules: Optional[ShardingRules] = None) -> Dict[str, float]:
    """Analytic per-device memory accounting for one paged-serving geometry.

    Walks the real pool builders (``paged_cache_struct`` + the serving axes
    from ``paged_cache_axes``), so it can never drift from what serve()
    allocates. Partitioned bytes (pools with a model-axis dim) divide by
    ``n_shards``; replicated bytes (block tables, MLA rope keys, ring
    metadata) are paid in full on every device. The benchmark gates on
    ``per_device_bytes`` — the ~1/N capacity win this PR exists for."""
    validate_serving_shards(cfg, n_shards)
    n = max(1, int(n_shards))
    if rules is None:
        rules = serving_rules(ShardingRules(cfg.sharding_overrides))
    struct = kv_cache.paged_cache_struct(cfg, slots, cache_len, block_size,
                                         num_blocks)
    axes = kv_cache.paged_cache_axes(cfg, slots, cache_len, block_size,
                                     num_blocks)
    part, repl = [0], [0]

    def _count(s, ax):
        nbytes = int(np.prod(s.shape, dtype=np.int64)) * \
            np.dtype(s.dtype).itemsize
        if any(_maps_to_model(rules, a) for a in ax):
            part[0] += nbytes
        else:
            repl[0] += nbytes

    jax.tree.map(_count, struct, axes)
    total = part[0] + repl[0]
    per_device = part[0] // n + repl[0]
    return {"total_bytes": float(total),
            "partitioned_bytes": float(part[0]),
            "replicated_bytes": float(repl[0]),
            "per_device_bytes": float(per_device),
            "capacity_ratio": total / max(per_device, 1),
            "shards": float(n)}


@dataclasses.dataclass
class ConsistencyReport:
    """Outcome of a single-device vs sharded serve of the same trace."""
    matched: bool
    n_requests: int
    shards: int
    mismatched_rids: List[int]

    def __bool__(self) -> bool:
        return self.matched


def check_sharded_consistency(engine, requests, shards: Optional[int] = None,
                              mesh=None, **serve_kw) -> ConsistencyReport:
    """Serve ``requests`` twice — single-device and sharded — and compare
    every request's full token stream. Greedy sampling makes the sharded run
    token-identical (head-parallel attention is bitwise; the row-parallel
    output projections reduce in a different order, which greedy argmax
    absorbs). Returns a report; ``bool(report)`` is the pass/fail."""
    if mesh is None and shards is None:
        raise ValueError("check_sharded_consistency needs shards=N or a "
                         "mesh to compare against one device")
    reqs = list(requests)
    base = engine.serve(reqs, **serve_kw)
    shrd = engine.serve(reqs, mesh=mesh, shards=shards, **serve_kw)
    base_by, shrd_by = base.by_rid(), shrd.by_rid()
    bad = [rid for rid in sorted(base_by)
           if not np.array_equal(base_by[rid].tokens, shrd_by[rid].tokens)]
    n = mesh.shape[MODEL_AXIS] if mesh is not None else shards
    return ConsistencyReport(matched=not bad, n_requests=len(reqs),
                             shards=int(n), mismatched_rids=bad)
