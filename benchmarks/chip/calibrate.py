"""Readings that the limits of a cell's check are set from.

    python3 benchmarks/chip/calibrate.py --workload olmo-1b.doc-qa \
        --seeds 11,12,13 --control 3 --seconds 20

One process, one engine: for each seed it makes that seed's weights, serves
the cell's mix sized for ``--seconds`` through the same compiled programs
as a run, and compares a sample of what was served with the reference, as a
run does, judging it by the configuration's limits as a run does. For the
first ``--control`` seeds it also reads the control (the reference in fp8)
on the same tokens and judges it, in the program's place, by the same
limits: ``control_correct`` has to come out false. Prints one JSON line per
seed, then the lower reading of each number (the largest over the program's
seeds) and the upper one (the smallest over the control's). Runs only on a
TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def calibrate(cell, seeds, n_control, seconds, require_tpu=True,
              out=sys.stdout):
    import numpy as np

    from chipbench import check, harness, spec, traffic, weights

    devs = harness.devices(cell.chips, require_tpu)
    eng, cfg, options = harness.build(cell.config, cell.traffic, seeds[0],
                                      devs)
    layout = harness.serving_layout(eng.model, devs)[1]
    asks = traffic.shape(cell.traffic, seconds)
    eng.serve(harness.requests(traffic.warm_asks(cell.traffic, asks[0]),
                               cfg.vocab, seeds[0] + 1), options=options)
    module = check.load_reference(spec.BENCH_DIR, cell.config["reference"])
    n_out = int(cell.traffic["answer_len"]["max"])
    limits = cell.config["limits"]
    rows = []
    for i, seed in enumerate(seeds):
        if i:
            eng.params = None
            eng.params = weights.make(eng.model, seed, layout)
        reqs = harness.requests(asks, cfg.vocab, seed)
        t0 = time.perf_counter()
        report = eng.serve(reqs, options=options)
        t1 = time.perf_counter()
        rep = report.by_rid()
        prompts = {r.rid: np.asarray(r.prompt, np.int32) for r in reqs}
        served = {r.rid: np.asarray(rep[r.rid].tokens[r.prompt_len:],
                                    np.int32) for r in reqs}
        failed = sum(len(served[r.rid]) != r.max_new for r in reqs)
        rids = check.sample({r: len(s) for r, s in served.items()},
                            {r: len(prompts[r]) + len(s)
                             for r, s in served.items()},
                            seed, harness.TARGET_CHECK_TOKENS)
        ref = check.Reference(module, cell.config, eng.params, n_out)
        got = check.compare(ref, prompts, served, rids,
                            control=i < n_control)
        # the program is judged as a run judges it; the control, put in its
        # place, by the same limits (it serves no tokens of its own, so it
        # has none failed and no blocks leaked)
        checks, correct = harness.judge(limits, got, failed,
                                        report.leaked_blocks)
        row = dict(got, seed=seed, requests=len(reqs), serve_s=t1 - t0,
                   check_s=time.perf_counter() - t1, correct=correct,
                   checks=checks)
        if i < n_control:
            row["control_checks"], row["control_correct"] = harness.judge(
                limits, {n: got["control_" + n] for n in limits}, 0, 0)
        rows.append(row)
        print(json.dumps(row), file=out, flush=True)
        del ref        # one seed's weights at a time on the device
    summary = {"workload": cell.name,
               "correct": [r["correct"] for r in rows],
               "control_correct": [r["control_correct"] for r in rows
                                   if "control_correct" in r]}
    for name in ("logit_gap", "mean_logit_gap"):
        prog = [r[name] for r in rows]
        ctrl = [r["control_" + name] for r in rows if "control_" + name in r]
        summary[name] = {"lower": max(prog),
                         "upper": min(ctrl) if ctrl else None,
                         "program": prog, "control": ctrl}
    print(json.dumps(summary), file=out, flush=True)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    ap.add_argument("--control", type=int, default=3,
                    help="read the control on this many of the seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from chipbench import harness, spec

    harness.use_compile_cache()

    cell = spec.load_cell(ROOT, args.workload)
    try:
        calibrate(cell, [int(s) for s in args.seeds.split(",")], args.control,
                  args.seconds)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
