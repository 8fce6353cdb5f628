"""One run of one cell: set-up, the timed window, the metrics, the check.

The window is one ``Engine.serve`` call over the mix's fixed step-indexed
replay, sized by ``--seconds``, on an engine built as the serving launcher
builds one (``repro.launch.serve.build_engine``: the model pinned to one
device, the paged pool, Alg.-1 integer softmax), but with the configuration
read from the cell's file and the weights made from the run seed. A cell on
more than one chip serves tensor-parallel on a serving mesh of its chips
(``ServeOptions.mesh``), with the weights made in the serving placement.
Set-up compiles, or loads from the persistent cache, every shape the window
runs: the decode step and each prompt shape of the mix.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import check, gaps, observe, peaks, spec, traffic, weights

TARGET_CHECK_TOKENS = 400


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""

    config: dict                  # the configuration file
    traffic: dict                 # the mix file
    report: object                # repro.serving.engine.ServeReport
    due: Dict[int, float]         # rid -> host time it was queued
    emits: Dict[int, List[float]]  # rid -> host time of each token
    gaps: gaps.Gaps
    window_s: float               # host clock around the serve call
    setup_s: float
    trace: Optional[dict]         # tracing.reduce(), traced runs only
    peaks: Optional[dict]


class _CompileCounter:
    """Counts compilations, traces and persistent-cache hits, per phase."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self):
        import jax

        self.phase = "setup"
        self.counts = {p: dict.fromkeys(self.EVENTS.values(), 0)
                       for p in ("setup", "window", "check")}
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, duration, **kw: self._seen(event))
        jax.monitoring.register_event_listener(
            lambda event, **kw: self._seen(event))

    def _seen(self, event):
        if event in self.EVENTS:
            self.counts[self.phase][self.EVENTS[event]] += 1


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at the program's default place,
    ``.jax_cache`` at the root of the checkout
    (``repro.launch.compile_cache``), holding every program, however small
    or quick to compile, so that only a cell's first run in a checkout
    compiles. A ``JAX_COMPILATION_CACHE_DIR`` from outside is not taken: the
    cache stays inside the checkout, so that two checkouts run side by side
    share nothing. Call before the first compilation."""
    import jax

    from repro.launch import compile_cache

    os.environ.pop(compile_cache.ENV_VAR, None)
    path = compile_cache.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if len(devs) < chips or (require_tpu and devs[0].platform != "tpu"):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def serving_layout(model, devs):
    """(serving mesh, weight layout) for a cell on ``devs``: (None, None) on
    one chip; on more, a serving mesh of them and ``shard_params`` onto it,
    the placement ``Engine.serve`` gives the weights there."""
    from repro.distributed.sharding import serving_rules
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.sharded import shard_params, validate_serving_mesh

    if len(devs) == 1:
        return None, None
    mesh = make_serving_mesh(len(devs), devices=devs)
    validate_serving_mesh(model.cfg, mesh)
    return mesh, functools.partial(
        shard_params, axes_tree=model.param_axes(),
        rules=serving_rules(model.ctx.rules), mesh=mesh)


def build(config: dict, mix: dict, seed: int, devs):
    """(engine, model config, serve options) for one configuration and
    mix on ``devs`` (the cell's chips), with weights made from ``seed``."""
    import jax

    from repro.configs.base import ModelConfig
    from repro.core.precision import PrecisionConfig
    from repro.core.softmax_variants import SoftmaxSpec
    from repro.distributed.sharding import ShardingRules
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import Model
    from repro.serving import ServeOptions
    from repro.serving.engine import Engine

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    smx = config["softmax"]
    cfg = ModelConfig(
        **{k: v for k, v in config.items() if k in fields and k != "softmax"},
        softmax=SoftmaxSpec(smx["kind"], PrecisionConfig(
            M=smx["M"], N=smx["N"], T_C=smx["T_C"])))
    model = Model(cfg, rules=ShardingRules(cfg.sharding_overrides),
                  mesh=make_host_mesh(devs[:1]))
    mesh, layout = serving_layout(model, devs)
    params = weights.make(model, seed, layout)
    jax.block_until_ready(params)
    geo = traffic.geometry(mix)
    options = ServeOptions(**traffic.serve_settings(mix), paged=True,
                           cache_len=geo["cache_len"],
                           num_blocks=geo["num_blocks"], mesh=mesh)
    return Engine(model, params, sampler="greedy"), cfg, options


def serving_devices(eng, options) -> set:
    """The devices the window serves on: those that hold the weights, and
    the serving mesh's."""
    import jax

    used = {d for leaf in jax.tree.leaves(eng.params) for d in leaf.devices()}
    if options.mesh is not None:
        used |= set(options.mesh.devices.flat)
    return used


def judge(limits: dict, got: dict, failed: int, leaked_blocks: int):
    """(the numbers compared, each with its limit; whether all are within
    them). ``limits`` are the configuration's, ``got`` what ``check.compare``
    read for the program (or, with ``control_`` names, for the control)."""
    checks = {name: {"value": got[name], "limit": limit}
              for name, limit in limits.items()}
    checks["failed"] = {"value": int(failed), "limit": 0}
    checks["leaked_blocks"] = {"value": int(leaked_blocks), "limit": 0}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def requests(asks, vocab: int, seed: int):
    from repro.serving.scheduler import Request

    return [Request(rid=a.rid, prompt=p, max_new=a.max_new,
                    arrival=a.arrival, seed=a.rid)
            for a, p in zip(asks, traffic.tokens(asks, vocab, seed))]


def _beyond(values, q) -> int:
    """How many samples lie above the ``q``-th percentile."""
    cut = gaps.percentile(values, q)
    return sum(v > cut for v in values)


def _trace_file(log_dir: str) -> str:
    found = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace file, found {found}")
    return found[0]


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             err=sys.stderr) -> dict:
    """The result line of one run (a dict), after printing its notes and the
    numbers compared to ``err``. Raises ``NoChip`` before any work."""
    import jax

    devs = devices(cell.chips, require_tpu)
    counter = _CompileCounter()
    t_runtime = time.perf_counter()
    eng, cfg, options = build(cell.config, cell.traffic, seed, devs)
    used = serving_devices(eng, options)
    if used != set(devs):
        raise RuntimeError(f"cell asks for {cell.chips} chip(s) but serves "
                           f"on {len(used)} device(s)")
    t_weights = time.perf_counter()
    asks = traffic.shape(cell.traffic, seconds)
    eng.serve(requests(traffic.warm_asks(cell.traffic, asks[0]), cfg.vocab,
                       seed + 1), options=options)
    t_warm = time.perf_counter()

    reqs = requests(asks, cfg.vocab, seed)
    rec = observe.Recorder()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    counter.phase = "window"
    try:
        with rec.attached(spans=trace), \
                jax.profiler.TraceAnnotation("chipbench.window"):
            t0 = time.perf_counter()
            report = eng.serve(reqs, options=options)
            t1 = time.perf_counter()
    finally:
        counter.phase = "check"
        if trace:
            jax.profiler.stop_trace()
    mem = [d.memory_stats() or {} for d in devs]
    peak_mem = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    reduced = None
    if trace:
        from chipbench import tracing

        try:
            reduced = tracing.reduce(_trace_file(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    by_rid = report.by_rid()
    kind = {r.rid: "tail" if by_rid[r.rid].shared_prefix > 0 else "full"
            for r in reqs}
    gapset = gaps.classify(rec.emits, kind)
    failed = [r.rid for r in reqs
              if len(rec.emits.get(r.rid, ())) != r.max_new
              or len(by_rid[r.rid].tokens) != r.prompt_len + r.max_new]
    kind_name = devs[0].device_kind
    run = Run(config=cell.config, traffic=cell.traffic, report=report,
              due=rec.due, emits=rec.emits, gaps=gapset,
              window_s=t1 - t0, setup_s=t0 - t_start, trace=reduced,
              peaks=(peaks.peaks(kind_name) if require_tpu
                     else peaks.PEAKS.get(kind_name)))
    ttft = [rec.emits[r][0] - rec.due[r] for r in rec.emits if rec.emits[r]]
    metrics = spec.read_metrics(cell.per_layer if trace else cell.end_to_end,
                                run)

    # the check runs on the weights alone: the program's state is freed
    t_check = time.perf_counter()
    prompts = {r.rid: np.asarray(r.prompt, np.int32) for r in reqs}
    served = {r.rid: np.asarray(by_rid[r.rid].tokens[r.prompt_len:], np.int32)
              for r in reqs if r.rid not in failed}
    rids = check.sample({r: len(s) for r, s in served.items()},
                        {r: len(prompts[r]) + len(s)
                         for r, s in served.items()},
                        seed, TARGET_CHECK_TOKENS)
    ref = check.Reference(
        check.load_reference(spec.BENCH_DIR, cell.config["reference"]),
        cell.config, eng.params, int(cell.traffic["answer_len"]["max"]))
    del eng
    got = check.compare(ref, prompts, served, rids)
    # the numbers the configuration holds a limit for (PERF.md gives the
    # readings each was set from), and the run's own integrity
    checks, correct = judge(cell.config["limits"], got, len(failed),
                            report.leaked_blocks)

    notes = {
        "requests": len(reqs), "tokens": sum(r.max_new for r in reqs),
        "decode_steps": report.steps, "window_wall_s": t1 - t0,
        "setup": {"runtime_s": t_runtime - t_start,
                  "weights_engine_s": t_weights - t_runtime,
                  "compile_warm_s": t_warm - t_weights},
        "compile_events": counter.counts,
        "gaps": gapset.summary(),
        "ttft_beyond_p90": _beyond(ttft, 90),
        "prefill_tokens": report.prefill_tokens,
        "shared_prefill_tokens": report.shared_prefill_tokens,
        "check_s": time.perf_counter() - t_check,
        "checked_requests": len(rids),
        "checked_tokens": got["checked_tokens"],
        "logit_gap": got["logit_gap"],
        "mean_logit_gap": got["mean_logit_gap"],
        "failed_rids": failed,
    }
    print(json.dumps({"notes": notes}), file=err)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} limit {c['limit']}", file=err)

    device = {"platform": devs[0].platform, "kind": kind_name,
              "count": len(devs), "memory_peak_bytes": peak_mem}
    result = {"correct": bool(correct), "attempted": len(reqs),
              "failed": len(failed), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result
