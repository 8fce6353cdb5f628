"""Logical-axis sharding: the single place where tensor dimensions meet mesh axes.

Tensors carry *logical* axis names ("batch", "seq", "heads", "mlp", ...); a
rules table maps each name to zero or more *mesh* axes. Models only ever talk
logical names, so re-sharding an architecture (or hillclimbing a cell) is a
rules edit, not a model edit.

Default mapping (production mesh ("pod", "data", "model")):

  batch    -> (pod, data)   pure DP for activations
  embed    -> (pod, data)   FSDP: d_model dim of weights sharded over DP axes
  heads    -> model         TP over attention heads
  kv_heads -> model         TP over KV heads (GSPMD pads non-divisible counts)
  mlp      -> model         TP over FFN hidden
  vocab    -> model         TP over embedding/logits vocab dim
  experts  -> model         expert parallelism
  seq_sp   -> model         Megatron-style sequence sharding of the residual
                            stream between blocks (train path)
  kv_seq   -> model         split-KV (flash-decoding style) decode sharding
  stacked  -> None          scan-stacked layer dim, never sharded
"""

from __future__ import annotations

from typing import Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]

DEFAULT_RULES: dict = {
    "batch": ("pod", "data"),
    "embed": ("pod", "data"),
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "expert_mlp": None,
    "seq": None,
    "seq_sp": "model",
    "kv_seq": "model",
    "kv_lora": None,
    "latent": None,      # MLA latent CACHE dim (serve-path TP shards it)
    # pre-row-parallel-contraction collect point (attn out before wo, MLP
    # hidden before down): "model" here = the layout the producing einsum
    # already emits, so the constraint is a no-op; the serving rules remap it
    # to None, all-gathering the operand so the contraction runs in full on
    # every device (deterministic; bitwise vs single-device on the CPU, within
    # bf16 rounding on a TPU) instead of as partial-sum + psum
    # (order-dependent rounding)
    "tp_collect": "model",
    "head_dim": None,
    "state": None,
    "conv": None,
    "stacked": None,
    "cross_seq": None,
}


class ShardingRules:
    """Immutable logical->mesh rules with per-arch overrides."""

    def __init__(self, overrides: Sequence[Tuple[str, AxisVal]] = (),
                 base: Optional[Mapping[str, AxisVal]] = None):
        rules = dict(base if base is not None else DEFAULT_RULES)
        for k, v in overrides:
            rules[k] = tuple(v) if isinstance(v, list) else v
        self._rules = rules

    def mesh_axes(self, logical: Optional[str]) -> AxisVal:
        if logical is None:
            return None
        if logical not in self._rules:
            raise KeyError(f"unknown logical axis {logical!r}")
        return self._rules[logical]

    def spec(self, logical_axes: Sequence[Optional[str]],
             mesh: Optional[Mesh] = None) -> P:
        """PartitionSpec for a tensor labeled with logical axes. Mesh axes not
        present in ``mesh`` (e.g. "pod" on a single-pod mesh) are dropped."""
        avail = set(mesh.axis_names) if mesh is not None else None
        used: set = set()
        parts = []
        for name in logical_axes:
            ax = self.mesh_axes(name)
            was_str = isinstance(ax, str)
            if was_str:
                ax = (ax,)
            if ax is not None:
                ax = tuple(a for a in ax
                           if (avail is None or a in avail) and a not in used)
                used.update(ax)
            if not ax:
                parts.append(None)
            elif was_str and len(ax) == 1:
                parts.append(ax[0])
            else:
                # tuple-valued rules stay tuples even when filtering leaves
                # one axis: PartitionSpec equality is form-sensitive
                parts.append(tuple(ax))
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def sharding(self, logical_axes: Sequence[Optional[str]], mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.spec(logical_axes, mesh))


# Tensor-parallel serving (Engine.serve(mesh=...)): decode parallelism comes
# from sharding attention heads / MLA latents, NOT from splitting the KV
# sequence — the cache carry must keep ONE stable head-sharded layout across
# every compiled step, so "kv_seq" is unmapped and the MLA latent cache dim
# picks up the model axis instead. "seq_sp" is unmapped (decode activations
# are [S, 1, d]; nothing to split) and "tp_collect" -> None turns every
# row-parallel contraction into gather-then-full-matmul: greedy sharded
# decode emits the exact single-device token stream instead of drifting on
# psum rounding order.
SERVING_OVERRIDES = (("kv_seq", None), ("seq_sp", None),
                     ("latent", "model"), ("tp_collect", None))


def serving_rules(base: Optional[ShardingRules] = None) -> ShardingRules:
    """Rules for the tensor-parallel serve path, layered over an arch's own
    rules: heads/kv_heads/mlp/vocab stay on the model axis, kv_seq is never
    sharded (head TP replaces split-KV for decode), and the MLA latent cache
    dim maps to the model axis so the paged latent pool partitions per
    device."""
    return ShardingRules(SERVING_OVERRIDES,
                         base=base._rules if base is not None else None)


def logical_constraint(x, logical_axes: Sequence[Optional[str]],
                       rules: Optional[ShardingRules],
                       mesh: Optional[Mesh] = None):
    """with_sharding_constraint by logical names.

    No-op when ``rules`` is None (single-device tests) or no mesh is
    resolvable. Accepts an explicit concrete mesh (preferred: works under any
    context) or falls back to the ambient mesh set by ``jax.set_mesh``.
    """
    if rules is None:
        return x
    if mesh is None:
        mesh = get_abstract_mesh()
        if mesh is None:
            return x
    if isinstance(mesh, Mesh):  # concrete mesh: NamedSharding works anywhere
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, rules.spec(logical_axes, mesh)))
    # abstract mesh (jax.set_mesh): bare PartitionSpec form
    return jax.lax.with_sharding_constraint(x, rules.spec(logical_axes, mesh))


def get_abstract_mesh():
    """The ambient mesh set by ``jax.set_mesh``, or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or m.empty else m


def make_mesh(shape, axis_names, devices=None):
    """``jax.make_mesh`` with Auto axis types on every axis (over
    ``devices`` when given, else every visible device)."""
    return jax.make_mesh(
        shape, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def use_mesh(mesh: Mesh):
    """Ambient-mesh context manager (``jax.set_mesh``)."""
    return jax.set_mesh(mesh)
