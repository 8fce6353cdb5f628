"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload olmo-1b.chat --seed 7 \
        --seconds 45 --trace 0

Prints the run's notes and the numbers compared with their limits on
standard error, and the result as one JSON line, last, on standard output:
the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics (and
the device's busy time and a breakdown, from a profiler trace) with
``--trace 1``. Exits non-zero, printing no result, when JAX finds no TPU or
fewer chips than the cell needs. JAX's persistent compilation cache lives in
``.jax_cache`` at the root of the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    from chipbench import harness, spec

    harness.use_compile_cache()

    cell = spec.load_cell(ROOT, args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
