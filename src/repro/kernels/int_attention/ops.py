"""jit'd public wrapper for the fused integer-softmax attention kernel."""

from __future__ import annotations

from functools import partial

import jax

from repro.core.precision import PrecisionConfig
from repro.kernels import resolve_interpret
from repro.kernels.int_attention.kernel import int_attention_kernel


def _auto_blk_q(skv: int) -> int:
    """Scale the query tile so scores + k/v tiles stay within ~8 MB VMEM."""
    budget = 8 * 1024 * 1024
    kv_bytes = 2 * skv * 128 * 2
    blk = max(16, (budget - kv_bytes) // (skv * 4))
    return int(min(128, 1 << (blk.bit_length() - 1)))


@partial(jax.jit, static_argnames=("cfg", "causal", "window", "blk_q",
                                   "interpret"))
def int_attention_pallas(q, k, v, cfg: PrecisionConfig = PrecisionConfig(),
                         causal: bool = True, window: int = 0,
                         blk_q: int = None, interpret: bool = None):
    """q: [B, H, Sq, D]; k, v: [B, KV, Skv, D] -> [B, H, Sq, D] float32."""
    b, h, sq, d = q.shape
    kv, skv = k.shape[1], k.shape[2]
    interpret = resolve_interpret(interpret)
    blk_q = _auto_blk_q(skv) if blk_q is None else blk_q
    out = int_attention_kernel(
        q.reshape(b * h, sq, d), k.reshape(b * kv, skv, d),
        v.reshape(b * kv, skv, d), cfg, causal=causal, window=window,
        blk_q=blk_q, interpret=interpret)
    return out.reshape(b, h, sq, d)
