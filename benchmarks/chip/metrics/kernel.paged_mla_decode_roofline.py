"""Kernel ``paged_decode_mla`` (kernels/paged_attention, absorbed MLA):
the least time the chip needs for the work the window's decode lanes asked
of it, over the kernel's device time in the trace, in percent.

The work of one lane in one layer, attending over ``ctx`` positions with
``H`` heads against the latent cache (rank ``R``, rotary key ``DR``):
scores ``2 * H * ctx * (R + DR)`` and the latent-weighted sum
``2 * H * ctx * R`` operations; the latent and rotary keys of ``ctx``
positions read once (``ctx * (R + DR)`` bf16 values), the queries
(``H * (R + DR)``) read and the latent outputs (``H * R``) written once. A
step's bound is the larger of its operations over the bf16 peak and its
bytes over the HBM bandwidth, once per layer."""

from chipbench.work import decode_contexts

KERNEL = "/paged_decode_mla"


def lane_work(cfg: dict, ctx: int):
    """(operations, bytes) of one lane attending over ``ctx`` positions in
    one layer."""
    h, r, dr = cfg["n_heads"], cfg["kv_lora_rank"], cfg["qk_rope_dim"]
    ops = 2 * h * ctx * (r + dr) + 2 * h * ctx * r
    return ops, 2 * (ctx * (r + dr) + h * (r + dr) + h * r)


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
