"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds (TPU v5e constants):

  compute    = HLO_FLOPs_per_device / peak_FLOPs_chip
  memory     = HLO_bytes_per_device / HBM_bw
  collective = collective_operand_bytes_per_device / link_bw

cost_analysis() reports the per-device (post-SPMD) program, so per-device
terms equal the spec's global/(chips*bw) formulation. Collective bytes are
NOT in cost_analysis: we parse the optimized HLO, build an instruction->shape
table, and sum operand sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute.
"""

from __future__ import annotations

import re
from typing import Dict

# TPU v5e hardware constants (per the brief)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 50e9                # B/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\][^\s]*)\s+([\w\-]+)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum operand bytes per collective kind from optimized HLO text."""
    shapes: Dict[str, int] = {}
    per_kind = {k: 0.0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    operand_re = re.compile(r"%([\w.\-]+)")
    lines = hlo_text.splitlines()
    for ln in lines:
        m = _DEF_RE.match(ln)
        if not m:
            continue
        name, type_str, _op = m.groups()
        shapes[name] = _shape_bytes(type_str)
    for ln in lines:
        m = _DEF_RE.match(ln)
        if not m:
            continue
        name, type_str, op = m.groups()
        kind = next((k for k in _COLLECTIVES if op.startswith(k)), None)
        if kind is None:
            continue
        count[kind] += 1
        paren = ln[ln.index(op) + len(op):]
        paren = paren[:paren.find(")") + 1] if ")" in paren else paren
        ops = [o for o in operand_re.findall(paren) if o in shapes]
        if ops:
            per_kind[kind] += sum(shapes[o] for o in ops)
        else:
            # start-done pairs print operands elsewhere; fall back to result size
            per_kind[kind] += _shape_bytes(type_str)
    per_kind["_counts"] = count
    return per_kind


def roofline_terms(flops_pd: float, bytes_pd: float,
                   coll_bytes_pd: float) -> Dict[str, float]:
    compute = flops_pd / PEAK_FLOPS
    memory = bytes_pd / HBM_BW
    collective = coll_bytes_pd / ICI_BW
    dominant = max(("compute", compute), ("memory", memory),
                   ("collective", collective), key=lambda kv: kv[1])[0]
    total = max(compute, memory, collective)
    return {"compute_s": compute, "memory_s": memory,
            "collective_s": collective, "dominant": dominant,
            "bound_s": total}


def model_flops_train(active_params: float, tokens: float,
                      attn_flops: float = 0.0) -> float:
    """6*N_active*D (+ attention quadratic term), global."""
    return 6.0 * active_params * tokens + attn_flops


def mfu_like(model_flops_global: float, flops_pd: float, n_chips: int) -> float:
    """MODEL_FLOPS / HLO_FLOPS: how much compiled compute is useful."""
    total_hlo = flops_pd * n_chips
    return model_flops_global / total_hlo if total_hlo else float("nan")


# --------------------------------------------------------------------------
# Paged-decode attention operator (the fused block-table kernel)

# The scoped VMEM limit the paged-decode kernels are compiled with
# (``pltpu.CompilerParams(vmem_limit_bytes=...)``) and the budget
# ``kernels/paged_attention/ops.choose_tiles`` fits tiles into: half of a
# v5e core's 128 MiB physical VMEM, the rest left to XLA's own fusions.
# Without an explicit limit Mosaic enforces its default scope (16 MiB).
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a [rows, cols] array in native (sublane, 128) tiles:
    8 sublanes of 32-bit words, packed 2x for 16-bit and 4x for 8-bit."""
    return _rup(rows, 8 * (4 // itemsize)) * _rup(cols, 128) * itemsize


def paged_tile_vmem_bytes(rows: int, l_full: int, block_size: int,
                          d_head: int, dv_head: int, pps: int,
                          compute_bytes: int = 2, quant: bool = False,
                          hpp: int = 1) -> int:
    """VMEM per program of the paged-decode kernel (one slot and a block of
    ``hpp`` kv heads; MLA is head-shared, ``hpp`` 1), counted the way Mosaic
    lays it out (every array padded to native tiles).

    scores scratch  [hpp * rows, l_full] f32     (full rows — no online
                                                  rescaling, see kernel docs)
    V scratch       [l_full, hpp * dv_head] compute dtype
    K slab          [pps * block_size, hpp * (d_head + dv_head)] compute
                    dtype (dense stages [.., hpp * d_head]; MLA stages both
                    latent and rope slabs, so count both widths)
    page tiles      2 buffers x pps x ([BS, hpp * d_head] +
                    [BS, hpp * dv_head]) at the pool dtype, one fetch each
                    (+ two [BS, KV] f32 scale pages when quant)
    q / out blocks  2 buffers x hpp x [rows, d_head] / [rows, dv_head]
    softmax         the Alg.-1 body's live full-row temporaries at the last
                    step: five 32-bit [hpp * rows, l_full] arrays and the
                    compute-dtype probabilities

    Checked against the v5e compiler (``tests/test_tpu_compile.py``): an
    upper bound on the scoped VMEM Mosaic allocates, within 4-25% of it at
    the shapes serving uses (1 to 64 rows, 4k to 32k columns).
    """
    elt = 1 if quant else compute_bytes
    w, wv = hpp * d_head, hpp * dv_head
    scratch = (_tile_bytes(hpp * rows, l_full, 4)
               + _tile_bytes(l_full, wv, compute_bytes)
               + _tile_bytes(pps * block_size, w, compute_bytes)
               + _tile_bytes(pps * block_size, wv, compute_bytes))
    tiles = pps * (_tile_bytes(block_size, w, elt)
                   + _tile_bytes(block_size, wv, elt))
    if quant:
        tiles += 2 * pps * _tile_bytes(block_size, 1, 4)
    blocks = hpp * (_tile_bytes(rows, d_head, compute_bytes)
                    + _tile_bytes(rows, dv_head, compute_bytes))
    softmax = (5 * _tile_bytes(hpp * rows, l_full, 4)
               + _tile_bytes(hpp * rows, l_full, compute_bytes))
    return scratch + 2 * (tiles + blocks) + softmax


def paged_decode_operator(slots: int, kv_heads: int, rows: int, d_head: int,
                          dv_head: int, pages_touched: int, block_size: int,
                          n_logical: int, compute_bytes: int = 2,
                          quant: bool = False) -> Dict[str, float]:
    """Roofline terms for one fused paged-decode step, plus the
    gather-then-attend bytes it replaces.

    The fused kernel's memory term counts only the PAGES TOUCHED — table
    entries actually walked — not the logical capacity: per (slot, kv-head)
    it streams ``pages_touched * block_size`` K and V rows once. The gather
    reference instead materializes (write + re-read) the full
    ``n_logical * block_size`` logical cache, so its bytes scale with pool
    capacity even for mostly-empty slots.
    """
    elt = 1 if quant else compute_bytes
    l_live = pages_touched * block_size
    l_full = n_logical * block_size
    kv_bytes = slots * kv_heads * l_live * (d_head + dv_head) * elt
    if quant:
        kv_bytes += 2 * slots * kv_heads * l_live * 4
    q_o_bytes = slots * kv_heads * rows * (d_head + dv_head) * compute_bytes
    flops = 2.0 * slots * kv_heads * rows * l_live * (d_head + dv_head)
    terms = roofline_terms(flops, kv_bytes + q_o_bytes, 0.0)
    # gather path: pool -> dense [S, l_full, KV, D] intermediate (write),
    # then the attention reads it back; x3 ~= write + read K and V
    gather = slots * kv_heads * l_full * (d_head + dv_head) * elt * 3
    terms["fused_bytes"] = kv_bytes + q_o_bytes
    terms["gather_bytes"] = float(gather)
    terms["bytes_ratio"] = gather / max(kv_bytes + q_o_bytes, 1.0)
    return terms
