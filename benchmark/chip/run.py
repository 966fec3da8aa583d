#!/usr/bin/env python3
"""The chip benchmark's entry point: one cell, one run, one result line.

    python3 benchmark/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Looks the cell up in ``BENCHMARK.json`` and finds its configuration, traffic
mix, runner, task and per-layer metric readers by name (see
``chipbench/harness.py``). Runs only on a TPU whose kind the peaks table
knows: anything else exits non-zero and prints no result line. The last
line of stdout is the result object; the lines before it are for the reader.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    # before jax is imported: the compile cache lives at a fixed path inside
    # the benchmark's own directory, with no size cap (the chip machine
    # caps its own at 192 MiB, below what one cell writes, and a capped
    # cache evicts what the next run needs)
    cache = os.path.join(HERE, ".cache", "jax")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, ROOT]
    from chipbench import harness

    try:
        return harness.main(args, HERE, ROOT, T_START)
    except harness.BenchError as e:
        print(f"chipbench: no result: {e}", file=sys.stderr, flush=True)
        return 3


if __name__ == "__main__":
    sys.exit(main())
