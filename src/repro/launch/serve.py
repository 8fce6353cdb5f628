"""Serving launcher: restore (or briefly train, or seed) a model, then run
batched generation through the engine with any registered softmax backend (FP
baselines, SoftmAP integer paths, the Pallas kernel, or the functional AP
simulator), reporting the per-request AP softmax cost for metered backends.

Generation runs as ONE fused device dispatch after prefill (the lax.scan
decode loop with in-scan sampling and a donated cache — see
serving/engine.py); ``--eager`` falls back to the per-token dispatch loop for
comparison. ``--continuous`` switches to the continuous-batching scheduler:
a trace of staggered mixed-length requests served through slot-based KV
caching (``Engine.serve``), with per-request latency and attributed AP cost,
and where the serve loop's host time went: each phase's self seconds, count
and longest span (``ServeReport.host_s``; ``loop`` is the rest), and the
gaps between tokens by the admission prefill they held
(``ServeReport.gap_kinds``).

    PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --smoke \
        --softmax int --max-new 32 --sampler top_p --top-p 0.9
    PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --smoke \
        --softmax int --continuous --requests 16 --slots 4
    # published widths (no --smoke), seeded random params, on one chip
    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b \
        --softmax int --continuous --paged --kernel pallas --prompt-len 128
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.backends import get_backend
from repro.backends.registry import settled_backend_names
from repro.checkpoint import checkpointer as ckpt
from repro.configs.registry import get_config, smoke_config
from repro.core.precision import PrecisionConfig
from repro.core.softmax_variants import SoftmaxSpec
from repro.data.synthetic import SyntheticCorpus
from repro.distributed.sharding import ShardingRules
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.model import Model
from repro.serving.engine import Engine


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced smoke config of --arch "
                         "(d_model 128, vocab 512) instead of its published "
                         "widths")
    # registered-names validation at argparse time: a typo'd --softmax or
    # --serve-softmax fails with the full registry listed, before any model
    # or training work (settled_backend_names() is None only mid-import,
    # which cannot happen at __main__ time — but degrade to unvalidated
    # rather than crash if it ever does)
    _names = settled_backend_names()
    backend_names = sorted(_names) if _names is not None else None
    ap.add_argument("--softmax", default="int", choices=backend_names,
                    help="softmax backend the MODEL is built (and warm-"
                         "trained, if differentiable) with")
    ap.add_argument("--serve-softmax", default=None, choices=backend_names,
                    help="--continuous: serve-time softmax-variant override "
                         "(ServeOptions.softmax_kind) — the variant zoo "
                         "shares the engine's params; e.g. consmax, sole, "
                         "mive")
    ap.add_argument("--M", type=int, default=6)
    ap.add_argument("--N", type=int, default=16)
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from a train.py checkpoint")
    ap.add_argument("--warm-steps", type=int, default=None,
                    help="if no checkpoint: quick-train so outputs are "
                         "meaningful (default 120 with --smoke; 0 at "
                         "published widths, whose optimizer state does not "
                         "fit one chip). 0 serves seeded random params")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=24)
    from repro.serving.sampler import available_samplers
    ap.add_argument("--sampler", default="greedy",
                    choices=available_samplers())
    ap.add_argument("--temp", type=float, default=1.0,
                    help="temperature for temperature/top_p samplers")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k cutoff for the temperature sampler")
    ap.add_argument("--top-p", type=float, default=0.9,
                    help="nucleus mass for the top_p sampler")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop-token id: finished sequences emit it for the "
                         "remaining steps (EOS early-masking)")
    ap.add_argument("--eager", action="store_true",
                    help="pre-fusion per-token dispatch loop (baseline)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching trace serving (Engine.serve)")
    ap.add_argument("--requests", type=int, default=16,
                    help="--continuous: trace length")
    ap.add_argument("--slots", type=int, default=4,
                    help="--continuous: decode slots")
    ap.add_argument("--policy", default="continuous",
                    choices=["continuous", "gang"],
                    help="--continuous: admission policy (gang = static "
                         "batching on the same executor)")
    ap.add_argument("--paged", action="store_true",
                    help="--continuous: paged KV cache (block pool + "
                         "per-slot block tables)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="--paged: tokens per KV block")
    ap.add_argument("--prefix-share", action="store_true",
                    help="--paged: reuse resident prompt blocks across "
                         "requests with a common prefix (tail-only prefill)")
    ap.add_argument("--speculative", action="store_true",
                    help="--continuous: draft-and-verify decoding (n-gram "
                         "prompt-lookup drafts, one compiled multi-token "
                         "verify step, exact rejection sampling)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="--speculative: draft tokens per verify round")
    ap.add_argument("--kernel", default="jnp", choices=("jnp", "pallas"),
                    help="--paged: decode-attention path; 'pallas' runs the "
                         "fused block-table-walk kernel (compiled on a TPU; "
                         "interpret mode, bit-identical to the gather "
                         "baseline, on the CPU)")
    ap.add_argument("--shards", type=int, default=0,
                    help="tensor-parallel serving across N mesh devices "
                         "(heads + paged pool shard; greedy output is "
                         "bit-identical to single-device on the CPU, "
                         "within bf16 rounding on a TPU). On CPU hosts "
                         "set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N before launch")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="--continuous: cap prompt tokens committed per "
                         "engine step — long prefills interleave with "
                         "decode instead of stalling it (bit-identical "
                         "output)")
    ap.add_argument("--preemption", action="store_true",
                    help="--paged: premium arrivals may swap a lower-class "
                         "request's blocks to host memory and resume it "
                         "later, bit-identically")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache with per-position scales (fake-quant "
                         "prefill; composes with every serve mode — sharing, "
                         "chunking, preemption, speculation, pallas)")
    ap.add_argument("--kv-quant-scheme", default="absmax",
                    choices=("absmax", "exaq", "exaq_clamped"),
                    help="--kv-quant: scale rule (exaq = EXAQ-style "
                         "power-of-two scales, arxiv 2410.03185; "
                         "exaq_clamped = 5-bit-exponent hardware point)")
    return ap


def parse_args(argv=None):
    """Parse and cross-check launcher flags; returns ``(args,
    serve_options)``. Flag conflicts fail here, before any model work."""
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.warm_steps is None:
        args.warm_steps = 120 if args.smoke else 0
    if (args.paged or args.prefix_share or args.speculative or args.shards) \
            and not args.continuous:
        ap.error("--paged/--prefix-share/--speculative/--shards require "
                 "--continuous (they configure Engine.serve)")
    if args.shards:
        if len(jax.devices()) < args.shards:
            ap.error(f"--shards {args.shards} needs {args.shards} devices "
                     f"but jax sees {len(jax.devices())}; on CPU hosts set "
                     f"XLA_FLAGS=--xla_force_host_platform_device_count="
                     f"{args.shards} before launch")
    if args.prefill_chunk is not None and not args.continuous:
        ap.error("--prefill-chunk requires --continuous (it paces "
                 "Engine.serve admissions)")
    if args.serve_softmax is not None and not args.continuous:
        ap.error("--serve-softmax requires --continuous (it overrides the "
                 "softmax variant for Engine.serve)")
    # cross-field serve constraints (--prefix-share/--kernel/--preemption
    # require --paged, ...) live in ONE place: ServeOptions.__post_init__.
    # Build the options object up front so flag conflicts fail before any
    # training/restore work happens.
    from repro.serving import ServeOptions
    try:
        serve_options = ServeOptions(
            slots=args.slots, policy=args.policy,
            paged=args.paged, block_size=args.block_size,
            prefix_share=args.prefix_share,
            speculative=args.speculative, draft_k=args.draft_k,
            kernel=args.kernel,
            shards=args.shards if args.shards else None,
            softmax_kind=args.serve_softmax,
            prefill_chunk=args.prefill_chunk,
            preemption=args.preemption)
    except ValueError as e:
        ap.error(str(e))
    return args, serve_options


def build_engine(args) -> Engine:
    """Model, parameters and engine for parsed launcher flags — the one
    set-up the launcher and ``chip_smoke.py`` share.

    Parameters come from ``--ckpt-dir``, else from ``--warm-steps`` of quick
    training, else (``--warm-steps 0``) straight from a seeded
    ``Model.init_split``, with no optimizer state built at all. The model
    lives on ONE device; tensor-parallel serving (``--shards``) places its
    own copy on the serving mesh inside ``Engine.serve``."""
    metered = get_backend(args.softmax).metered
    spec = SoftmaxSpec(args.softmax, PrecisionConfig(M=args.M, N=args.N)) \
        if metered else SoftmaxSpec(args.softmax)
    cfg = (smoke_config(args.arch, softmax=spec) if args.smoke
           else get_config(args.arch, softmax=spec))
    if args.kv_quant:
        import dataclasses
        cfg = dataclasses.replace(cfg, kv_quant=True,
                                  kv_quant_scheme=args.kv_quant_scheme)
    mesh = make_host_mesh(jax.devices()[:1])
    rules = ShardingRules(cfg.sharding_overrides)
    model = Model(cfg, rules=rules, mesh=mesh)
    # warm training keeps the requested spec when its backend differentiates
    # (fp family, int, int_ste QAT); the non-differentiable substrates
    # (int_pallas, ap_sim) are serving-only choices, so their warm-up trains
    # against fp and the engine serves with the requested spec
    train_model = model if spec.backend().differentiable else Model(
        cfg.with_softmax(SoftmaxSpec("fp")), rules=rules, mesh=mesh)

    if args.ckpt_dir:
        from repro.training.optimizer import AdamW, constant_schedule
        from repro.training.step import init_state
        opt = AdamW(lr=constant_schedule(1e-3))
        state, step, _ = ckpt.restore(
            args.ckpt_dir, init_state(train_model, opt, jax.random.PRNGKey(0)))
        params = state.params
        print(f"restored step {step} from {args.ckpt_dir}")
    elif args.warm_steps > 0:
        from repro.training.optimizer import AdamW, cosine_schedule
        from repro.training.step import init_state, make_train_step
        corpus = SyntheticCorpus(cfg.vocab, seed=1234)
        opt = AdamW(lr=cosine_schedule(1e-2, 20, args.warm_steps))
        state = init_state(train_model, opt, jax.random.PRNGKey(0))
        step_fn = jax.jit(make_train_step(train_model, opt))
        for i in range(args.warm_steps):
            state, met = step_fn(state, {
                k: jnp.asarray(v)
                for k, v in corpus.batch(16, 64, seed=i).items()})
        params = state.params
        print(f"warm-trained {args.warm_steps} steps, "
              f"loss={float(met['loss']):.3f}")
    else:
        params, _ = model.init_split(jax.random.PRNGKey(0))
        print("serving seeded random params (PRNGKey(0), no training)")

    sampler_kw = {}
    if args.sampler == "temperature":
        sampler_kw = {"temp": args.temp, "top_k": args.top_k}
    elif args.sampler in ("top_p", "nucleus"):
        sampler_kw = {"p": args.top_p, "temp": args.temp}
    return Engine(model, params, max_new=args.max_new, sampler=args.sampler,
                  eos_id=args.eos_id, **sampler_kw)


def host_loop_lines(rep) -> list:
    """The serve loop's host phases and gap kinds of one ``ServeReport``, as
    ``--continuous`` prints them."""
    lines = [f"host loop ({rep.wall_s * 1e3:.1f} ms wall): phase, "
             "self ms, count, longest ms"]
    for name, (secs, n, longest) in rep.host_s.items():
        lines.append(f"  {name:<12} {secs * 1e3:10.3f} {n:7d} "
                     f"{longest * 1e3:10.3f}")
    lines.append("gaps between tokens: " + ", ".join(
        f"{kind} {n}" for kind, n in rep.gap_kinds.items()))
    return lines


def main(argv=None):
    use_compile_cache()
    args, serve_options = parse_args(argv)
    eng = build_engine(args)
    cfg = eng.model.cfg
    if args.continuous:
        from repro.serving.scheduler import random_trace
        reqs = random_trace(args.requests, cfg.vocab, seed=777,
                            prompt_lens=(4, args.prompt_len,
                                         2 * args.prompt_len),
                            max_new_range=(max(args.max_new // 4, 1),
                                           args.max_new))
        import dataclasses as _dc
        eng.serve(reqs, options=serve_options)  # compile
        rep = eng.serve(reqs, options=_dc.replace(serve_options,
                                                  report_cost=True))
        import numpy as np
        gen = sum(r.max_new for r in reqs)
        lat = [r.latency_s for r in rep.results]
        paged_note = (f", paged bs={rep.block_size} "
                      f"(prefill {rep.prefill_tokens} tok, "
                      f"shared {rep.shared_prefill_tokens})"
                      if rep.paged else "")
        spec_note = (f", speculative k={rep.draft_k} "
                     f"(acceptance {rep.acceptance_rate:.2f})"
                     if rep.speculative else "")
        print(f"{args.policy} serving: {len(reqs)} requests / {args.slots} "
              f"slots, {gen} tokens in {rep.steps} decode steps, "
              f"{rep.wall_s * 1e3:.1f} ms ({gen / rep.wall_s:.0f} tok/s)"
              f"{paged_note}{spec_note}")
        print(f"request latency p50={np.percentile(lat, 50) * 1e3:.1f} ms "
              f"p99={np.percentile(lat, 99) * 1e3:.1f} ms")
        print("\n".join(host_loop_lines(rep)))
        if args.prefill_chunk is not None or args.preemption:
            print(f"sla: prefill_chunk={rep.prefill_chunk or 'off'} "
                  f"(max prefill/step {rep.max_prefill_per_step}), "
                  f"preemptions={rep.preemptions} resumes={rep.resumes} "
                  f"leaked_blocks={rep.leaked_blocks}")
            for cls in sorted(rep.class_latency):
                c = rep.class_latency[cls]
                sla = ("" if c["sla_attainment"] is None
                       else f"  sla={c['sla_attainment'] * 100:.0f}%")
                print(f"  class {cls}: n={c['n']} "
                      f"ttft p50={c['ttft_p50'] * 1e3:.1f}/"
                      f"p99={c['ttft_p99'] * 1e3:.1f} ms  "
                      f"tbt p50={c['tbt_p50'] * 1e3:.1f}/"
                      f"p99={c['tbt_p99'] * 1e3:.1f} ms"
                      f"{sla}  preempted={c['preemptions']}")
        for r in rep.results[:3]:
            cost = (f"  cost: {r.cost.describe()}"
                    if r.cost is not None and r.cost.cycles else "")
            print(f"  rid={r.rid} P={r.prompt_len} "
                  f"new={len(r.tokens) - r.prompt_len} "
                  f"lat={r.latency_s * 1e3:.1f} ms{cost}")
        if rep.cost is not None and rep.cost.cycles:
            print(f"batch softmax AP cost: {rep.cost.describe()}")
            if rep.speculative and rep.cost_verify is not None:
                print(f"  verify phase: {rep.cost_verify.describe()}")
            if rep.speculative and rep.cost_draft is not None \
                    and rep.cost_draft.cycles:
                print(f"  draft phase: {rep.cost_draft.describe()}")
        return
    corpus = SyntheticCorpus(cfg.vocab, seed=1234)
    prompts = corpus.sample(args.batch, args.prompt_len, seed=777)[:, :args.prompt_len]
    mode = "eager" if args.eager else "fused"
    res = eng.generate(prompts, report_cost=True, mode=mode)  # compile + run
    t0 = time.perf_counter()
    res = eng.generate(prompts, report_cost=True, mode=mode)
    dt = time.perf_counter() - t0
    tps = args.batch * args.max_new / dt
    print(f"{mode} generation: {args.batch}x{args.max_new} tokens "
          f"in {dt * 1e3:.1f} ms ({tps:.0f} tok/s)")
    ok = sum(int(row[t + 1] in corpus.table[row[t]])
             for row in res.tokens
             for t in range(res.prompt_len - 1, res.tokens.shape[1] - 1))
    print(f"softmax={cfg.softmax.kind}: {ok}/{args.batch * args.max_new} "
          "generated transitions follow the corpus chain")
    for row in res.tokens[:2]:
        p, g = row[:args.prompt_len].tolist(), row[args.prompt_len:].tolist()
        print(f"  prompt {p} -> {g}")
    if res.cost is not None and res.cost.cycles:
        print(f"softmax AP cost (batch of {args.batch}): {res.cost.describe()}")
    elif res.cost is not None:
        print("softmax AP cost: n/a (unmetered fp backend)")


if __name__ == "__main__":
    main()
