"""Run one benchmark cell and print its result as the last line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run it from the root of a checkout on a host with the chips the cell
asks for; it exits non-zero and prints no result where JAX finds no TPU.
With --trace 0 the result carries the cell's end-to-end metrics, with
--trace 1 its per-layer metrics from a profiled window. JAX's persistent
compile cache lives in `.jax_cache/` at the checkout's root.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # Set before JAX loads. The cache stays inside this checkout at a fixed
    # path even where the caller's environment names another directory:
    # one named by the host would be shared by every checkout measured
    # there, so one side's set-up would read the other's programs. The
    # program keeps its cache where this variable points.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    # cache every program set-up compiles, the small ones too
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path.insert(0, ROOT)
    from benchmark.harness import SetupError, run_cell

    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), root=ROOT)
    except SetupError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    return 0 if result is not None else 1


if __name__ == "__main__":
    sys.exit(main())
