"""Whole step: model operations of every prompt token the window prefilled
and every token it decoded, over the traced window and the chip's bf16
peak, in percent.

Per token, ``2`` operations per weight of every matrix product (the
attention projections, the SwiGLU MLP and the output head; embedding
lookups are free) and, per layer, ``2 * H * ctx * (Dqk + Dv)`` for QK^T and
PV over the ``ctx`` positions it attends to. Positions served from shared
prefix blocks are not computed, so they are not counted."""

from chipbench.work import decode_contexts, prefilled


def matmul_weights(cfg: dict) -> int:
    d, h, ff = cfg["d_model"], cfg["n_heads"], cfg["d_ff"]
    if cfg["attention"] == "mla":
        dn, dr, dv = cfg["qk_nope_dim"], cfg["qk_rope_dim"], cfg["v_head_dim"]
        r, ql = cfg["kv_lora_rank"], cfg["q_lora_rank"]
        q = d * ql + ql * h * (dn + dr) if ql else d * h * (dn + dr)
        attn = q + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    else:
        kv, dh = cfg["n_kv_heads"], cfg["d_head"]
        attn = 2 * d * h * dh + 2 * d * kv * dh
    return cfg["n_layers"] * (attn + 3 * d * ff) + d * cfg["vocab"]


def attn_ops_per_ctx(cfg: dict) -> int:
    """Attention operations per attended position, all layers."""
    if cfg["attention"] == "mla":
        dqk, dv = cfg["qk_nope_dim"] + cfg["qk_rope_dim"], cfg["v_head_dim"]
    else:
        dqk = dv = cfg["d_head"]
    return cfg["n_layers"] * 2 * cfg["n_heads"] * (dqk + dv)


def model_ops(cfg: dict, report) -> float:
    per_tok, per_ctx = 2 * matmul_weights(cfg), attn_ops_per_ctx(cfg)
    ops = 0.0
    for lo, hi in prefilled(report):
        n = hi - lo
        ops += n * per_tok + per_ctx * (n * (lo + hi + 1) / 2)
    for ctxs in decode_contexts(report).values():
        ops += len(ctxs) * per_tok + per_ctx * sum(ctxs)
    return ops


def read(run):
    if run.trace is None or run.peaks is None or not run.trace["window_s"]:
        return None
    return 100.0 * model_ops(run.config, run.report) / (
        run.trace["window_s"] * run.peaks["bf16_flop_s"])
