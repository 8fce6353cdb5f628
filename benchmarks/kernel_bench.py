"""Kernel microbenchmarks: Pallas int-softmax / fused int-attention / fused
paged-decode attention vs the pure-jnp oracles and FP softmax. Wall times on
this CPU host are interpret-mode (correctness-path) numbers — the TPU perf
story lives in the roofline tables — but the derived column reports exactness
vs the oracle, which is the contract. ``--out`` additionally writes the
machine-readable BENCH_kernels.json that ``check_regression.py`` gates
(exactness rows deterministically; wall-clock rows only with
``--gate-absolute``, since interpret-mode latency is runner-dependent).
"""

from __future__ import annotations

import argparse
import json

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import time_fn
from repro.core import BEST, fp_softmax
from repro.core.int_softmax import int_softmax
from repro.kernels.int_attention.ops import int_attention_pallas
from repro.kernels.int_attention.ref import int_attention_ref
from repro.kernels.int_softmax.ops import int_softmax_pallas
from repro.kernels.int_softmax.ref import int_softmax_ref
from repro.kernels.paged_attention import ops as paged_ops


def run() -> list:
    rng = np.random.default_rng(0)
    rows = []
    for r, c in ((64, 512), (16, 4096)):
        x = jnp.asarray(rng.normal(0, 2, (r, c)), jnp.float32)
        jit_ref = jax.jit(lambda x: int_softmax_ref(x, BEST))
        jit_fp = jax.jit(lambda x: fp_softmax(x))
        us_k = time_fn(lambda: int_softmax_pallas(x, BEST), iters=3)
        us_r = time_fn(lambda: jit_ref(x), iters=3)
        us_f = time_fn(lambda: jit_fp(x), iters=3)
        exact = bool(jnp.array_equal(int_softmax_pallas(x, BEST), jit_ref(x)))
        rows.append((f"kernel.int_softmax.{r}x{c}", us_k,
                     f"exact_vs_oracle={exact};ref_us={us_r:.0f};fp_us={us_f:.0f}"))
    b, h, kv, s, d = 1, 8, 2, 256, 64
    q = jnp.asarray(rng.normal(0, 1, (b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (b, kv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (b, kv, s, d)), jnp.float32)
    jref = jax.jit(lambda q, k, v: int_attention_ref(q, k, v, BEST))
    us_a = time_fn(lambda: int_attention_pallas(q, k, v, BEST, blk_q=64), iters=3)
    us_ar = time_fn(lambda: jref(q, k, v), iters=3)
    err = float(jnp.abs(int_attention_pallas(q, k, v, BEST, blk_q=64)
                        - jref(q, k, v)).max())
    rows.append((f"kernel.int_attention.{b}x{h}x{s}x{d}", us_a,
                 f"max_err_vs_oracle={err:.1e};ref_us={us_ar:.0f}"))
    return rows


def _paged_case(rng, ctx: int, bs: int = 64):
    """One (fused, gather) paged-decode pair at a logical context length."""
    S, KVH, H, D = 2, 2, 4, 64
    nlog = ctx // bs
    nb = nlog + 4
    q = jnp.asarray(rng.normal(0, 1, (S, 1, H, D)), jnp.bfloat16)
    k_pool = jnp.asarray(rng.normal(0, 1, (nb, bs, KVH, D)), jnp.bfloat16)
    v_pool = jnp.asarray(rng.normal(0, 1, (nb, bs, KVH, D)), jnp.bfloat16)
    table = jnp.asarray(
        np.stack([rng.permutation(nb)[:nlog] for _ in range(S)]), jnp.int32)
    positions = jnp.asarray([[ctx - 1]] * S, jnp.int32)
    scale = D ** -0.5

    fused = jax.jit(lambda *a: paged_ops.paged_attend_dense(
        *a, BEST, scale=scale))

    @jax.jit
    def gather(q, k_pool, v_pool, table, positions):
        pages = jnp.take(k_pool, jnp.clip(table, 0, nb - 1), axis=0)
        k = pages.reshape(S, ctx, KVH, D)
        v = jnp.take(v_pool, jnp.clip(table, 0, nb - 1),
                     axis=0).reshape(S, ctx, KVH, D)
        qg = q.reshape(S, 1, KVH, H // KVH, D)
        sc = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
        sc = sc * scale
        kv_pos = jnp.arange(ctx, dtype=jnp.int32)[None, None, :]
        m = (kv_pos <= positions[:, :, None])[:, None, None]
        w = int_softmax(sc, cfg=BEST, mask=m, axis=-1).astype(q.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", w, v).reshape(S, 1, H, D)

    args = (q, k_pool, v_pool, table, positions)
    # the kernel reads the pools as stored: one position's heads side by side
    fargs = (q, k_pool.reshape(nb, bs, KVH * D), v_pool.reshape(nb, bs, KVH * D),
             table, positions)
    us_fused = time_fn(lambda: fused(*fargs), iters=3, warmup=1)
    us_gather = time_fn(lambda: gather(*args), iters=3, warmup=1)
    exact = bool(jnp.array_equal(
        fused(*fargs).astype(jnp.float32), gather(*args).astype(jnp.float32)))
    return us_fused, us_gather, exact


def run_paged(contexts=(1024, 4096, 32768)) -> dict:
    """Fused block-table walk vs gather-then-attend at decode contexts.

    Interpret-mode walls: the fused column pays the Pallas interpreter's
    per-page dispatch on CPU, so the gather column (compiled XLA) usually
    wins here — the fused win is a bytes story (pages touched vs logical
    capacity, see ``launch/roofline.paged_decode_operator``) that
    materializes on the TPU target. Exactness is the gated contract."""
    rng = np.random.default_rng(0)
    out = {}
    for ctx in contexts:
        us_f, us_g, exact = _paged_case(rng, ctx)
        out[f"ctx{ctx}"] = {"fused_us": us_f, "gather_us": us_g,
                            "exact": exact}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="also write BENCH_kernels.json-style report here")
    ap.add_argument("--max-ctx", type=int, default=32768,
                    help="cap the paged-decode context sweep (CI smoke uses "
                         "4096 to bound interpret-mode wall time)")
    args = ap.parse_args()
    from benchmarks.common import emit
    rows = run()
    paged = run_paged([c for c in (1024, 4096, 32768) if c <= args.max_ctx])
    for ctx, r in paged.items():
        rows.append((f"kernel.paged_decode.{ctx}", r["fused_us"],
                     f"exact_vs_gather={r['exact']};"
                     f"gather_us={r['gather_us']:.0f}"))
    emit(rows)
    if args.out:
        report = {
            "rows": [{"name": n, "us": us, "derived": d}
                     for n, us, d in rows],
            "paged_decode": paged,
        }
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
