"""Pallas decode-attention kernels that walk the block table directly.

The gather-then-attend reference path (``models/attention.py``,
``_attn_decode_paged``) materializes the WHOLE logical cache
``[S, n_logical * BS, KV, D]`` every step before attending — on the decode
roofline that is a memory term proportional to the pool's logical capacity,
not to the tokens a slot has actually written. These kernels instead stream
K/V **pages** straight from the global pool, one VMEM residency per
(slot, block of HPP kv-heads) program:

  grid = (S, KV / HPP, n_pad / PPS)       dense / GQA
  grid = (S,           n_pad / PPS)       MLA (latent cache is head-shared)

with the per-slot block table and the per-row query positions passed as
**scalar-prefetch** operands (``pltpu.PrefetchScalarGridSpec``): the k/v
``BlockSpec`` index maps read ``table[s, page]`` to pick the physical pool
block each grid step fetches, so the data path never touches a dense
gathered intermediate. The dense pools are stored ``[NB, BS, KV * D]``
(the heads of one position side by side), so a block of HPP heads of a page
is the ``[BS, HPP * D]`` block at lane offset ``hb * HPP * D``: at
HPP = KV one contiguous fetch of the page's whole row, in place of KV
strided ``[BS, D]`` head slices. Each grid step stages its PPS pages into
a ``[PPS * BS, HPP * D]`` K slab and computes each head's ``[ROWS,
PPS * BS]`` score slab from its own 128-lane-aligned ``D``-wide slice into
rows ``i * ROWS ...`` of one ``[HPP * ROWS, L]`` VMEM scratch (and stages
the V pages into an ``[L, HPP * Dv]`` scratch); at the last step of a slot
it applies the shared Alg.-1 integer softmax (``core/alg1.py``) over the
FULL rows of every head at once (rows are independent, so each row's codes
are what a one-head program would give) and each head's weighted PV sum
from its own lane slice of V, in the same residency. ``ops.choose_tiles``
picks HPP and PPS from the shapes.

TPU tiling: a score slab is stored at a dynamic lane offset, which Mosaic
accepts only at a multiple of 128 lanes, so PPS * BS is a multiple of 128
(``aligned_pages``) and the wrapper pads the block table with sentinel
pages up to a whole number of steps. Padded columns lie past the real
cache length and are masked out, so they add nothing to a row. A head's
slice of a multi-head page starts at a multiple of 128 lanes only when D
and Dv are multiples of 128, so HPP > 1 needs both.

DESIGN NOTE — why full rows, not online rescaling: flash-style softmax
accumulates ``exp(x - m_running)`` and rescales the partial sums when the
running max moves. That identity (``exp(a - b) = exp(a) / exp(b)``) does NOT
hold for the paper's integer exponential: Alg. 1 quantizes ``x - max(x)``
onto an M-bit grid and evaluates a fixed-point LUT polynomial, so
re-quantizing against a shifted max lands on DIFFERENT grid points and the
"rescaled" integer probabilities diverge from the one-shot ones (the prefill
kernel, ``kernels/int_attention/kernel.py``, keeps whole rows for the same
reason). The kernel therefore keeps whole score rows resident — cheap at
decode, where ROWS = T * G is tiny — and stays bit-identical to the gather
reference instead of approximately close.

Bit-exactness contract (vs gather + the ``int_jax`` backend, interpret
mode; compiled on a TPU v5e, decode logits agree with the gather path
only within a few bf16 ulps — ``chip_smoke.py`` measures the drift):

  * each step's score slab is ``dot_general(q, k_slab)`` with f32
    accumulation, rounded through the compute dtype and cast to the scores
    dtype EXPLICITLY — ``jnp.einsum`` on bf16 operands rounds its f32
    accumulator to bf16 before the reference's ``.astype(float32)``, and
    matching that rounding is what makes the kernel's scores equal the
    reference's bit for bit (QK^T columns depend only on their own K rows,
    so per-step slabs assemble the full-row dot exactly);
  * the MLA score is the SUM of two dots (latent + rope); XLA rounds each
    einsum to bf16 and performs the add in f32 ("semi" semantics) — the
    kernel reproduces that explicitly instead of letting one fused dot
    accumulate across both contractions;
  * sentinel table entries (outside ``[0, num_blocks)``) contribute
    all-zero K/V tiles, matching ``paged_gather``'s zeros-for-sentinels
    contract;
  * the int8 KV dequant (``kv_quant``) is fused into the page load:
    ``(codes.astype(f32) * scale).astype(compute)`` is elementwise, so
    dequantizing per page equals dequantizing the gathered whole. The
    ``[BS, KV]`` scale page is fetched whole, and each head's lane slice of
    a page is dequantized with its own column, selected by a masked lane
    sum, which is exact (one term, the rest 0).

VMEM per program: ``launch/roofline.paged_tile_vmem_bytes``; ``ops``
picks HPP and PPS against it and passes the same budget to the compiler
as the kernel's VMEM limit, so a tile the model accepts is one the
compiler accepts.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.alg1 import int_softmax_block
from repro.core.precision import PrecisionConfig

LANES = 128


def aligned_pages(block_size: int) -> int:
    """Fewest pages per grid step whose score slab (``pps * block_size``
    columns) is a whole number of 128-lane tiles."""
    return LANES // math.gcd(block_size, LANES)


def _page_tile(tile, ent, nb, scale=None, compute_dtype=None):
    """One [BS, D] page tile: dequantized when a [BS, 1] scale column rides
    along, zeroed when the table entry ``ent`` is a sentinel (outside
    [0, nb))."""
    if scale is not None:
        tile = (tile.astype(jnp.float32) * scale).astype(compute_dtype)
    live = (ent >= 0) & (ent < nb)
    return jnp.where(live, tile, jnp.zeros_like(tile))


def _head_column(scales, h):
    """Column ``h`` of a [BS, KV] scale page as [BS, 1] — a masked lane sum,
    exact because every other term is zero."""
    lane = jax.lax.broadcasted_iota(jnp.int32, scales.shape, 1)
    return jnp.sum(jnp.where(lane == h, scales, 0.0), axis=1, keepdims=True)


def _rounded_dot(a, b, compute_dtype):
    """f32-accumulated dot rounded to the compute dtype — the einsum-on-bf16
    rounding the reference path lowers to."""
    out = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return out.astype(compute_dtype)


def _row_mask(pos_ref, s, group, window, shape, length, rows):
    """[HPP * ROWS, L] validity: row i*ROWS + t*group + g attends kv
    positions <= pos[s, t] (within the trailing window when set) and below
    the real cache ``length`` (the padded tail never counts) —
    ``valid_upto``/``verify_mask`` semantics, shared by decode (T=1) and
    speculative verify (T=K+1). ``rows`` is one head's row count (MLA's
    head-shared block: all of ``shape[0]``). Positions are read one scalar
    at a time: a TPU kernel loads only scalars from SMEM, where the
    prefetched positions live."""
    row = jax.lax.broadcasted_iota(jnp.int32, (shape[0], 1), 0)
    if rows != shape[0]:
        row = row % rows
    row_t = row // group
    qpos = jnp.zeros((shape[0], 1), jnp.int32)
    for t in range(pos_ref.shape[1]):
        qpos = jnp.where(row_t == t, pos_ref[s, t], qpos)
    kpos = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = (kpos <= qpos) & (kpos < length)
    if window:
        mask &= kpos > qpos - window
    return mask


def _pv(probs, v_scr, compute_dtype):
    """Weighted value sum over the full staged rows, reference rounding."""
    out = jax.lax.dot_general(probs.astype(compute_dtype), v_scr,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return out.astype(compute_dtype)


def _slab_cols(pp, width):
    """The 128-aligned lane window of grid step ``pp``'s score slab."""
    return pl.ds(pl.multiple_of(pp * width, LANES), width)


def _compiler_params(vmem_limit):
    return (None if vmem_limit is None
            else pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit)))


# --------------------------------------------------------------- dense / GQA


def _stage_page(dst, at, tile_ref, scale_ref, ent, nb, h0, hpp,
                compute_dtype):
    """Stage one fetched page of ``hpp`` heads into rows ``at`` of ``dst``.
    An int8 page is dequantized head by head, each lane slice with its own
    scale column (head ``h0 + i``)."""
    if scale_ref is None:
        dst[at, :] = _page_tile(tile_ref[0], ent, nb)
        return
    w = tile_ref.shape[-1] // hpp
    for i in range(hpp):
        cols = slice(i * w, (i + 1) * w)
        dst[at, cols] = _page_tile(tile_ref[0, :, cols], ent, nb,
                                   _head_column(scale_ref[0], h0 + i),
                                   compute_dtype)


def _dense_kernel(table_ref, pos_ref, q_ref, *refs, cfg: PrecisionConfig,
                  scale: float, window: int, group: int, hpp: int, pps: int,
                  bs: int, nb: int, length: int, quant: bool, compute_dtype,
                  scores_dtype):
    k_refs = refs[:pps]
    v_refs = refs[pps:2 * pps]
    ks_refs = refs[2 * pps:3 * pps] if quant else (None,) * pps
    vs_refs = refs[3 * pps:4 * pps] if quant else (None,) * pps
    nin = pps * (4 if quant else 2)
    o_ref, scores, v_scr, k_slab = refs[nin:nin + 4]
    rows, d = q_ref.shape[2:]
    dv = o_ref.shape[-1]

    s, hb, pp = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    for j in range(pps):
        page = pp * pps + j
        ent = table_ref[s, page]
        _stage_page(k_slab, pl.ds(j * bs, bs), k_refs[j], ks_refs[j], ent,
                    nb, hb * hpp, hpp, compute_dtype)
        _stage_page(v_scr, pl.ds(page * bs, bs), v_refs[j], vs_refs[j], ent,
                    nb, hb * hpp, hpp, compute_dtype)
    width = pps * bs
    for i in range(hpp):
        st = _rounded_dot(q_ref[0, i], k_slab[:, i * d:(i + 1) * d],
                          compute_dtype).astype(scores_dtype) * scale
        scores[i * rows:(i + 1) * rows, _slab_cols(pp, width)] = (
            st.astype(jnp.float32))

    @pl.when(pp == pl.num_programs(2) - 1)
    def _():
        mask = _row_mask(pos_ref, s, group, window, scores.shape, length,
                         rows)
        probs = int_softmax_block(scores[...].astype(scores_dtype), mask, cfg)
        for i in range(hpp):
            o_ref[0, i] = _pv(probs[i * rows:(i + 1) * rows],
                              v_scr[:, i * dv:(i + 1) * dv], compute_dtype)


def paged_attention_dense(q, k_pool, v_pool, table, positions,
                          cfg: PrecisionConfig, *, scale: float,
                          window: int = 0, k_scale=None, v_scale=None,
                          scores_dtype=jnp.float32, pps: int = 8,
                          hpp: int = 1, length=None, vmem_limit=None,
                          interpret: bool = True):
    """Fused paged decode attention, dense/GQA layout.

    q          [S, KV, ROWS, D]   ROWS = T * group, row order t*group+g
    k/v_pool   [NB, BS, KV * D]   global block pools, heads side by side
                                  (int8 codes when the matching
                                  ``*_scale`` [NB, BS, KV] rides)
    table      [S, NLOG] int32    per-slot block table; sentinel = any
                                  entry outside [0, NB); NLOG % pps == 0
    positions  [S, T]  int32      per-query absolute positions
    hpp        kv heads per program (divides KV); each page is fetched as
               one [BS, hpp * D] block
    length     real cache length (default NLOG * BS); columns past it are
               padding and masked out
    -> [S, KV, ROWS, Dv] in the compute dtype (q's dtype).
    """
    s_, kv, rows, d = q.shape
    nb, bs = k_pool.shape[:2]
    nlog = table.shape[1]
    t = positions.shape[1]
    dv = v_pool.shape[-1] // kv
    assert k_pool.shape[-1] == kv * d, (k_pool.shape, kv, d)
    assert rows % t == 0, (rows, t)
    assert nlog % pps == 0, (nlog, pps)
    assert kv % hpp == 0, (kv, hpp)
    group = rows // t
    quant = k_scale is not None
    compute_dtype = q.dtype
    length = nlog * bs if length is None else length
    # the scratch is f32 regardless of scores_dtype: up-casting a rounded
    # scores slice to f32 is exact, and the final-step softmax re-rounds the
    # whole block through scores_dtype, which is idempotent
    l_full = nlog * bs

    def kv_index(j):
        def idx(s, hb, pp, table_ref, pos_ref):
            return (jnp.clip(table_ref[s, pp * pps + j], 0, nb - 1), 0, hb)
        return idx

    def sc_index(j):
        def idx(s, hb, pp, table_ref, pos_ref):
            return (jnp.clip(table_ref[s, pp * pps + j], 0, nb - 1), 0, 0)
        return idx

    in_specs = [pl.BlockSpec((1, hpp, rows, d),
                             lambda s, hb, pp, *_: (s, hb, 0, 0))]
    in_specs += [pl.BlockSpec((1, bs, hpp * d), kv_index(j))
                 for j in range(pps)]
    in_specs += [pl.BlockSpec((1, bs, hpp * dv), kv_index(j))
                 for j in range(pps)]
    operands = [q] + [k_pool] * pps + [v_pool] * pps
    if quant:
        in_specs += [pl.BlockSpec((1, bs, kv), sc_index(j))
                     for j in range(pps)] * 2
        operands += [k_scale] * pps + [v_scale] * pps

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_, kv // hpp, nlog // pps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hpp, rows, dv),
                               lambda s, hb, pp, *_: (s, hb, 0, 0)),
        scratch_shapes=[pltpu.VMEM((hpp * rows, l_full), jnp.float32),
                        pltpu.VMEM((l_full, hpp * dv), compute_dtype),
                        pltpu.VMEM((pps * bs, hpp * d), compute_dtype)])
    kernel = functools.partial(
        _dense_kernel, cfg=cfg, scale=scale, window=window, group=group,
        hpp=hpp, pps=pps, bs=bs, nb=nb, length=length, quant=quant,
        compute_dtype=compute_dtype, scores_dtype=scores_dtype)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((s_, kv, rows, dv), compute_dtype),
        grid_spec=grid_spec,
        compiler_params=_compiler_params(vmem_limit),
        interpret=interpret,
        name="paged_decode_dense",
    )(table, positions, *operands)


# ------------------------------------------------------------------ MLA


def _mla_kernel(table_ref, pos_ref, ql_ref, qr_ref, *refs,
                cfg: PrecisionConfig, scale: float, heads: int, pps: int,
                bs: int, nb: int, length: int, compute_dtype):
    c_refs = refs[:pps]
    kr_refs = refs[pps:2 * pps]
    o_ref, scores, c_scr, c_slab, kr_slab = refs[2 * pps:2 * pps + 5]

    s, pp = pl.program_id(0), pl.program_id(1)
    for j in range(pps):
        page = pp * pps + j
        ent = table_ref[s, page]
        ct = _page_tile(c_refs[j][0], ent, nb)         # [BS, R]
        c_slab[pl.ds(j * bs, bs), :] = ct
        kr_slab[pl.ds(j * bs, bs), :] = _page_tile(kr_refs[j][0], ent, nb)
        c_scr[pl.ds(page * bs, bs), :] = ct
    # "semi" sum semantics: each dot f32-accumulated then rounded to the
    # compute dtype, the ADD performed in f32 — exactly how XLA lowers
    # einsum(latent) + einsum(rope) on bf16 operands
    s1 = _rounded_dot(ql_ref[0], c_slab[...], compute_dtype)
    s2 = _rounded_dot(qr_ref[0], kr_slab[...], compute_dtype)
    st = (s1.astype(jnp.float32) + s2.astype(jnp.float32)) * scale
    scores[:, _slab_cols(pp, pps * bs)] = st

    @pl.when(pp == pl.num_programs(1) - 1)
    def _():
        mask = _row_mask(pos_ref, s, heads, 0, scores.shape, length,
                         scores.shape[0])
        probs = int_softmax_block(scores[...], mask, cfg)
        o_ref[0] = _pv(probs, c_scr[...], compute_dtype)


def paged_attention_mla(q_lat, q_rope, c_pool, kr_pool, table, positions,
                        cfg: PrecisionConfig, *, scale: float, pps: int = 8,
                        length=None, vmem_limit=None,
                        interpret: bool = True):
    """Fused paged absorbed-MLA decode attention.

    q_lat      [S, ROWS, R]    absorbed queries, row order t*H + h
    q_rope     [S, ROWS, DR]   rope queries, same row order
    c_pool     [NB, BS, R]     latent pool; kr_pool [NB, BS, DR] rope keys
    table      [S, NLOG] int32 (NLOG % pps == 0); positions [S, T] int32
    length     real cache length (default NLOG * BS); columns past it are
               padding and masked out
    -> o_lat [S, ROWS, R] in the compute dtype (the ``W_uv`` up-projection
    and output projection stay outside, shared with the reference path).
    """
    s_, rows, r = q_lat.shape
    dr = q_rope.shape[-1]
    nb, bs = c_pool.shape[:2]
    nlog = table.shape[1]
    t = positions.shape[1]
    assert rows % t == 0, (rows, t)
    assert nlog % pps == 0, (nlog, pps)
    heads = rows // t
    compute_dtype = q_lat.dtype
    l_full = nlog * bs

    def pool_index(j):
        def idx(s, pp, table_ref, pos_ref):
            return (jnp.clip(table_ref[s, pp * pps + j], 0, nb - 1), 0, 0)
        return idx

    in_specs = [pl.BlockSpec((1, rows, r), lambda s, pp, *_: (s, 0, 0)),
                pl.BlockSpec((1, rows, dr), lambda s, pp, *_: (s, 0, 0))]
    in_specs += [pl.BlockSpec((1, bs, r), pool_index(j)) for j in range(pps)]
    in_specs += [pl.BlockSpec((1, bs, dr), pool_index(j)) for j in range(pps)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_, nlog // pps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, r), lambda s, pp, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rows, l_full), jnp.float32),
                        pltpu.VMEM((l_full, r), compute_dtype),
                        pltpu.VMEM((pps * bs, r), compute_dtype),
                        pltpu.VMEM((pps * bs, dr), compute_dtype)])
    kernel = functools.partial(
        _mla_kernel, cfg=cfg, scale=scale, heads=heads, pps=pps, bs=bs,
        nb=nb, length=l_full if length is None else length,
        compute_dtype=compute_dtype)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((s_, rows, r), compute_dtype),
        grid_spec=grid_spec,
        compiler_params=_compiler_params(vmem_limit),
        interpret=interpret,
        name="paged_decode_mla",
    )(table, positions, q_lat, q_rope,
      *([c_pool] * pps), *([kr_pool] * pps))
