"""Model-facing entry points for the fused paged-decode kernels.

These wrappers own everything the kernels keep out of their grids: the
model-layout <-> kernel-layout reshapes (rows are ``t * group + g`` dense,
``t * heads + h`` MLA), the tile choice (``choose_tiles``: kv heads per
program and pages per step,
validated against the roofline VMEM model, whose budget is also the
kernel's compiler VMEM limit), the sentinel padding of the block table to a
whole number of grid steps, and the interpret default
(``repro.kernels.resolve_interpret``).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.core.precision import PrecisionConfig
from repro.kernels import resolve_interpret
from repro.kernels.paged_attention.kernel import (
    LANES, aligned_pages, paged_attention_dense, paged_attention_mla,
)
from repro.launch.roofline import VMEM_LIMIT_BYTES, paged_tile_vmem_bytes

_STEP_MULTIPLES = (4, 2, 1)


@functools.lru_cache(maxsize=None)
def choose_tiles(rows: int, n_logical: int, block_size: int, d_head: int,
                 dv_head: int, compute_bytes: int = 2, quant: bool = False,
                 vmem_budget: int = VMEM_LIMIT_BYTES,
                 kv_heads: int = 1) -> tuple[int, int]:
    """Pick ``(pps, hpp)`` for the paged kernel: pages per grid step and kv
    heads per program. ``hpp`` is the largest divisor of ``kv_heads`` (the
    heads the kernel is handed; 1 for MLA) for which some step fits; a
    block of several heads needs 128-lane-aligned head slices, so ``d_head``
    and ``dv_head`` multiples of 128. ``pps`` is a multiple of
    ``aligned_pages(block_size)`` (each step's score slab fills whole
    128-lane tiles), the largest that divides the table once it is padded
    to the next aligned length. A tile fits when the roofline VMEM model
    (``launch/roofline.paged_tile_vmem_bytes``) is within ``vmem_budget`` —
    the limit the kernel is compiled with. Cached per static config — the
    choice is a trace-time constant, so it can never cause a retrace
    mid-serve. Fails loudly (instead of letting the compiler refuse the
    kernel) when even one head at the smallest step exceeds the budget."""
    base = aligned_pages(block_size)
    n_pad = -(-n_logical // base) * base
    l_full = n_pad * block_size
    lane_aligned = d_head % LANES == 0 and dv_head % LANES == 0
    for hpp in range(kv_heads if lane_aligned else 1, 0, -1):
        if kv_heads % hpp != 0:
            continue
        for m in _STEP_MULTIPLES:
            pps = base * m
            if n_pad % pps != 0:
                continue
            need = paged_tile_vmem_bytes(rows, l_full, block_size, d_head,
                                         dv_head, pps, compute_bytes, quant,
                                         hpp)
            if need <= vmem_budget:
                return pps, hpp
    need = paged_tile_vmem_bytes(rows, l_full, block_size, d_head, dv_head,
                                 base, compute_bytes, quant)
    raise ValueError(
        f"paged-decode tile rejected by roofline VMEM model: rows={rows} "
        f"l_full={l_full} needs {need} B at pps={base} > budget "
        f"{vmem_budget} B; shrink the cache length or the verify width")


def _pad_table(table, pps: int, nb: int):
    """Sentinel-pad the block table to a whole number of ``pps``-page
    steps (sentinel pages load as zeros and lie past the real length)."""
    extra = (-table.shape[1]) % pps
    return jnp.pad(table, ((0, 0), (0, extra)), constant_values=nb)


def paged_attend_dense(q, k_pool, v_pool, table, positions,
                       pcfg: PrecisionConfig, *, scale: float,
                       window: int = 0, k_scale=None, v_scale=None,
                       scores_dtype=jnp.float32, interpret=None):
    """q [B, T, H, D] (model layout) -> [B, T, H, Dv].

    ``k_pool``/``v_pool`` are the ``[NB, BS, KV * D]`` pools;
    ``positions`` [B, T] are the absolute query positions (decode: the
    written ``cache_pos`` broadcast to T=1; verify: the draft positions).
    """
    b, t, h, d = q.shape
    nb, bs = k_pool.shape[:2]
    kvh = k_pool.shape[-1] // d
    dv = v_pool.shape[-1] // kvh
    group = h // kvh
    rows = t * group
    quant = k_scale is not None
    qk = q.reshape(b, t, kvh, group, d).transpose(0, 2, 1, 3, 4)
    qk = qk.reshape(b, kvh, rows, d)
    pps, hpp = choose_tiles(rows, table.shape[1], bs, d, dv,
                            jnp.dtype(q.dtype).itemsize, quant,
                            kv_heads=kvh)
    out = paged_attention_dense(
        qk, k_pool, v_pool, _pad_table(table, pps, nb),
        positions.astype(jnp.int32), pcfg,
        scale=scale, window=window, k_scale=k_scale, v_scale=v_scale,
        scores_dtype=jnp.dtype(scores_dtype), pps=pps, hpp=hpp,
        length=table.shape[1] * bs, vmem_limit=VMEM_LIMIT_BYTES,
        interpret=resolve_interpret(interpret))
    out = out.reshape(b, kvh, t, group, dv).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, t, h, dv)


def paged_attend_mla(q_lat, q_rope, c_pool, kr_pool, table, positions,
                     pcfg: PrecisionConfig, *, scale: float, interpret=None):
    """q_lat [B, T, H, R], q_rope [B, T, H, DR] -> o_lat [B, T, H, R].

    Absorbed-MLA attention over the latent pool; the ``W_uv`` up-projection
    and output projection stay with the caller (shared with the reference)."""
    b, t, h, r = q_lat.shape
    dr = q_rope.shape[-1]
    nb, bs = c_pool.shape[:2]
    rows = t * h
    # dv slot = R (the [L, R] latent scratch dominates, mirroring dense's V)
    pps, _ = choose_tiles(rows, table.shape[1], bs, dr, r,
                          jnp.dtype(q_lat.dtype).itemsize, False)
    out = paged_attention_mla(
        q_lat.reshape(b, rows, r), q_rope.reshape(b, rows, dr),
        c_pool, kr_pool, _pad_table(table, pps, nb),
        positions.astype(jnp.int32), pcfg, scale=scale, pps=pps,
        length=table.shape[1] * bs, vmem_limit=VMEM_LIMIT_BYTES,
        interpret=resolve_interpret(interpret))
    return out.reshape(b, t, h, r)
