"""Kernel ``paged_decode_dense`` (kernels/paged_attention, dense / GQA):
the least time the chip needs for the work the window's decode lanes asked
of it, over the kernel's device time in the trace, in percent.

The work of one lane in one layer, attending over ``ctx`` positions:
QK^T and PV, ``4 * H * ctx * D`` operations; the K and V of ``ctx``
positions read once (``2 * ctx * KV * D`` elements of the pool's type, plus
an f32 scale per position and head of each when the pool is int8), and the
queries read and outputs written once (``2 * H * D`` bf16 values). A step's
bound is the larger of its operations over the bf16 peak and its bytes over
the HBM bandwidth, once per layer."""

from chipbench.work import decode_contexts

KERNEL = "/paged_decode_dense"


def lane_work(cfg: dict, ctx: int):
    """(operations, bytes) of one lane attending over ``ctx`` positions in
    one layer."""
    h, kv, d = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
    quant = bool(cfg.get("kv_quant", False))
    kv_bytes = 2 * ctx * kv * d * (1 if quant else 2)
    if quant:
        kv_bytes += 2 * ctx * kv * 4
    return 4 * h * ctx * d, kv_bytes + 2 * h * d * 2


def bound_s(cfg: dict, steps, peaks) -> float:
    total = 0.0
    for ctxs in steps.values():
        ops = sum(lane_work(cfg, c)[0] for c in ctxs)
        byt = sum(lane_work(cfg, c)[1] for c in ctxs)
        total += max(ops / peaks["bf16_flop_s"], byt / peaks["hbm_byte_s"])
    return cfg["n_layers"] * total


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    kernel_s = sum(s for k, s in run.trace["ops"].items() if KERNEL in k)
    if not kernel_s:
        return None
    return 100.0 * bound_s(run.config, decode_contexts(run.report),
                           run.peaks) / kernel_s
