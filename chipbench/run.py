#!/usr/bin/env python3
"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` on the TPU this process finds: weights and
traffic from `--seed`, warm-up of the cell's own shapes (set-up), a measured
window of `--seconds`, the output check against the plain reference, and as the
LAST line of stdout one JSON object: `correct`, `attempted`, `failed`, `metrics`
(the cell's end-to-end metrics with `--trace 0`, its per-layer metrics with
`--trace 1`), `device`, and with `--trace 1` `breakdown`. Earlier lines are
free-form JSON (cache hits and misses, the check's numbers beside their limits).
There is no CPU mode: without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import harness

    cell, device, ledger, cache_dir = harness.open_cell(args.workload)
    harness.log(cell=cell.name, seed=args.seed, seconds=args.seconds, trace=args.trace,
                compile_cache_dir=cache_dir, **device)
    driver = harness.load_module("drivers", cell.spec["driver"])
    line = driver.run(cell, args, device, ledger, T_PROCESS_START)
    import jax

    harness.log(phase="done", wall_s=round(time.perf_counter() - T_PROCESS_START, 3),
                memory_stats=jax.devices()[0].memory_stats(), **ledger.line())
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
