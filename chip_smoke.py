#!/usr/bin/env python3
"""Chip smoke test: serve olmo-1b at its published widths through the
integer softmax on a TPU, through the entry points a user calls.

    python3 chip_smoke.py             # one chip: gather vs fused Pallas decode
    python3 chip_smoke.py --chips 4   # four chips: 4-way tensor-parallel
                                      # serving vs one device, nothing else

The launcher's own set-up (``repro.launch.serve.parse_args`` +
``build_engine`` with ``--warm-steps 0``: seeded random params, no optimizer
state) builds olmo-1b at 16 layers, d_model 2048, vocab 50304 with the
Alg.-1 integer softmax (M=6, N=16). ``Engine.serve`` then runs a seeded
8-request trace (prompts of 128, 256 or 512 tokens, 32 new tokens each) over
the paged KV pool with continuous admission and 4 slots.

One chip serves the trace with the gather decode path (``kernel="jnp"``) and
with the fused paged-decode Pallas kernel (``kernel="pallas"``), each cold
(compiling) and then warm, and checks that

  * the pallas run's decode step calls a compiled TPU kernel
    (``tpu_custom_call`` in its lowered program);
  * the two kernels emit the same greedy tokens, or else that their decode
    logits agree within ``LOGIT_TOL`` when one request of each prompt length
    is replayed through both on the same cache (``check_agreement``; the
    line says which held);
  * every request finishes, the warm run repeats the cold run's tokens, and
    no pool block leaks (``leaked_blocks == 0``).

``--chips 4`` serves the same trace on one device and on a 4-way
tensor-parallel mesh (``make_serving_mesh``), with the same token-or-logit
agreement check between the two.

Lines before the last are smoke output, not metrics. The last line is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every check
passed; without a TPU, or when a check fails, the script exits non-zero and
prints no such line. Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "olmo-1b"
# the published olmo-1b widths (configs/olmo_1b.py) the run must use
WIDTHS = {"n_layers": 16, "d_model": 2048, "n_heads": 16, "n_kv_heads": 16,
          "d_head": 128, "d_ff": 8192, "vocab": 50304}
LAUNCH_ARGV = ["--arch", ARCH, "--softmax", "int", "--M", "6", "--N", "16",
               "--warm-steps", "0", "--max-new", "32", "--continuous",
               "--paged", "--slots", "4", "--block-size", "16"]
N_REQUESTS, PROMPT_LENS, MAX_NEW, TRACE_SEED = 8, (128, 256, 512), 32, 777
# Logit tolerance, relative to the largest reference logit, for when the
# chip's rounding breaks the bit-exactness both comparisons keep on the CPU.
# Both executors round the same bf16 dot products; a different tiling or f32
# accumulation order inside one dot moves its bf16 result by at most one ulp
# (2^-8 relative), and 16 residual layers compound that to a few ulps of the
# largest logit — 8 ulps here.
LOGIT_TOL = 2.0 ** -5


class SmokeFailure(Exception):
    """A check of this script failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"check ok: {what}")


def build_engine():
    """The launcher's parse + set-up, exactly as ``repro.launch.serve``
    runs it."""
    from repro.launch.serve import build_engine, parse_args

    args, options = parse_args(LAUNCH_ARGV)
    engine = build_engine(args)
    cfg = engine.model.cfg
    widths = {k: getattr(cfg, k) for k in WIDTHS}
    print(f"smoke: model {cfg.name} widths {widths} softmax "
          f"{cfg.softmax.kind} M={cfg.softmax.precision.M} "
          f"N={cfg.softmax.precision.N}")
    check(widths == WIDTHS, f"{ARCH} at its published widths")
    return engine, options


def smoke_trace(vocab: int):
    from repro.serving.scheduler import random_trace

    return random_trace(N_REQUESTS, vocab, seed=TRACE_SEED,
                        prompt_lens=PROMPT_LENS,
                        max_new_range=(MAX_NEW, MAX_NEW))


def tokens_of(rep):
    return [r.tokens.tolist() for r in rep.results]


def serve_cold_warm(engine, reqs, options, label: str):
    """Serve ``reqs`` twice (cold: compiles included, then warm) and check
    both runs finished every request without leaking a pool block."""
    t0 = time.perf_counter()
    cold = engine.serve(reqs, options=options)
    t1 = time.perf_counter()
    warm = engine.serve(reqs, options=options)
    t2 = time.perf_counter()
    print(f"smoke: {label}: cold serve {t1 - t0:.2f} s (compiles included), "
          f"warm serve {t2 - t1:.2f} s, so ~{(t1 - t0) - (t2 - t1):.2f} s "
          f"compiling; {warm.steps} decode steps, cache_len "
          f"{warm.cache_len}")
    for name, rep in (("cold", cold), ("warm", warm)):
        check(len(rep.results) == len(reqs)
              and all(r.tokens.shape[0] == r.prompt_len + MAX_NEW
                      for r in rep.results),
              f"{label} {name}: all {len(reqs)} requests finished")
        check(rep.leaked_blocks == 0, f"{label} {name}: leaked_blocks == 0")
    check(tokens_of(cold) == tokens_of(warm),
          f"{label}: warm run repeats the cold run's tokens")
    return warm


def replay_logits(engine, req, cache_len: int, block_size: int,
                  slots: int, test_executor, place=None):
    """Replay ``req`` through two decode executors on the SAME cache.

    The prompt is prefilled into slot 0 of a fresh paged cache (the other
    slots parked, as in serving); each of the ``max_new - 1`` decode steps
    then runs the one-device gather path and ``test_executor`` (a
    ``(model, params)`` from ``Engine.decode_executor``; ``place`` puts the
    cache where its params live) on that one cache, and follows the gather
    path's greedy token. Returns (max |logit diff|, max |logit|, steps with
    bitwise-equal logits, steps)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import kv_cache

    cfg = engine.model.cfg
    place = place or (lambda tree: tree)
    n_log = cache_len // block_size
    cache = kv_cache.paged_cache_zeros(cfg, slots, cache_len, block_size,
                                       slots * n_log)
    logits, prompt_cache = jax.jit(
        engine.model.prefill, static_argnames=("cache_len",))(
        engine.params, {"tokens": jnp.asarray(req.prompt[None])},
        cache_len=cache_len)
    row = np.arange(n_log, dtype=np.int32)
    wpos = np.arange(req.prompt_len)
    cache = jax.jit(kv_cache.paged_scatter, static_argnames=("t0", "t1"))(
        cache, prompt_cache, jnp.int32(0), jnp.asarray(row),
        jnp.asarray(row[wpos // block_size]),
        jnp.asarray((wpos % block_size).astype(np.int32)),
        t0=0, t1=req.prompt_len)
    test_model, test_params = test_executor
    ref_step = jax.jit(engine.model.decode_step)
    test_step = jax.jit(test_model.decode_step)
    tok = np.zeros((slots, 1), np.int32)
    tok[0, 0] = int(jnp.argmax(logits[0, -1]))
    pos = np.full((slots,), cache_len, np.int32)
    pos[0] = req.prompt_len
    diff = scale = 0.0
    equal = 0
    for _ in range(req.max_new - 1):
        ref, nxt = ref_step(engine.params, cache, {"token": tok}, pos)
        got, _ = test_step(test_params, place(cache), {"token": tok}, pos)
        ref = np.asarray(ref[0, -1], np.float32)
        got = np.asarray(got[0, -1], np.float32)
        diff = max(diff, float(np.abs(ref - got).max()))
        scale = max(scale, float(np.abs(ref).max()))
        equal += int(np.array_equal(ref, got))
        cache, tok[0, 0], pos[0] = nxt, int(ref.argmax()), pos[0] + 1
    return diff, scale, equal, req.max_new - 1


def check_agreement(engine, reqs, ref_rep, test_rep, options, what: str,
                    test_executor, place=None) -> None:
    """Greedy tokens identical between two serves of ``reqs`` — or, where
    the chip's rounding breaks that, decode logits within ``LOGIT_TOL`` when
    one request of each prompt length is replayed through both executors on
    one cache. Prints which of the two held."""
    import numpy as np

    differ = [a.rid for a, b in zip(ref_rep.results, test_rep.results)
              if not np.array_equal(a.tokens, b.tokens)]
    firsts = {r.prompt_len: r for r in reversed(reqs)}
    drift = [replay_logits(engine, r, test_rep.cache_len, options.block_size,
                           options.slots, test_executor, place)
             for r in firsts.values()]
    diff = max(d[0] for d in drift)
    scale = max(d[1] for d in drift)
    equal, steps = sum(d[2] for d in drift), sum(d[3] for d in drift)
    print(f"smoke: decode logits, {what}, replayed on one cache: "
          f"{equal}/{steps} steps bitwise equal, max |diff| {diff:.6g} = "
          f"{diff / scale:.6g} x max |logit| {scale:.6g}")
    if not differ:
        print(f"smoke: held: greedy tokens identical, {what}, for all "
              f"{len(reqs)} requests")
        return
    print(f"smoke: greedy tokens differ, {what}, for rids {differ}; "
          f"checking logits within {LOGIT_TOL:g} x max |logit|")
    check(diff <= LOGIT_TOL * scale,
          f"held: decode logits agree within tolerance, {what}")


def one_chip_phase() -> None:
    engine, options = build_engine()
    reqs = smoke_trace(engine.model.cfg.vocab)
    print(f"smoke: trace {len(reqs)} requests, prompt lengths "
          f"{sorted({r.prompt_len for r in reqs})}, max_new {MAX_NEW}, "
          f"{options.slots} slots, paged block {options.block_size}")
    gather = serve_cold_warm(engine, reqs,
                             dataclasses.replace(options, kernel="jnp"),
                             "kernel=jnp (gather)")
    fused_opts = dataclasses.replace(options, kernel="pallas")
    fused = serve_cold_warm(engine, reqs, fused_opts,
                            "kernel=pallas (fused paged decode)")
    text = engine.lower_serve_step(fused_opts, fused.cache_len).as_text()
    check("tpu_custom_call" in text,
          "the pallas decode step calls a compiled TPU kernel "
          "(tpu_custom_call)")

    check_agreement(engine, reqs, gather, fused, options, "gather vs fused",
                    engine.decode_executor("pallas"))


def four_chip_phase() -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.mesh import make_serving_mesh
    from repro.serving.sharded import pool_report

    engine, options = build_engine()
    cfg = engine.model.cfg
    reqs = smoke_trace(cfg.vocab)
    one = serve_cold_warm(engine, reqs, options, "one device (jnp)")
    mesh = make_serving_mesh(4)
    tp = serve_cold_warm(engine, reqs, dataclasses.replace(options, mesh=mesh),
                         "shards=4 (jnp)")
    bs = options.block_size
    pool = pool_report(cfg, options.slots, tp.cache_len, bs,
                       options.slots * (tp.cache_len // bs), 4)
    print(f"smoke: paged pool bytes: total {pool['total_bytes']:.0f}, "
          f"per device at 4 shards {pool['per_device_bytes']:.0f}")
    replicated = NamedSharding(mesh, PartitionSpec())
    check_agreement(engine, reqs, one, tp, options,
                    "one device vs 4-way tensor-parallel",
                    engine.decode_executor("jnp", mesh),
                    place=lambda tree: jax.device_put(tree, replicated))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: gather vs fused kernel on one chip; 4: only "
                         "4-way tensor-parallel serving vs one device")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    print(f"smoke: jax {jax.__version__}, platform {dev.platform}, "
          f"device_kind {dev.device_kind!r}, device count {len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"smoke: compile cache {use_compile_cache()}")
    try:
        (four_chip_phase if args.chips == 4 else one_chip_phase)()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
