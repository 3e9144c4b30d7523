#!/usr/bin/env python3
"""Readings for a cell's limits: the check's numbers of the sound program on
many seeds, and of each of the cell's controls, all in one process.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 12 --controls all

A control (the cell file's `controls`) is the next precision down: the program
with a lower-precision path of its own switched on, or the plain reference put
in the program's place at that precision. Its numbers have to fail a limit;
`PERF.md` keeps the readings each limit was set from. Not part of a benchmark
run. Needs the TPU, like `run.py`.
"""

from __future__ import annotations

import argparse
import gc
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, driver, seeds, seconds: float, names, ledger) -> list:
    """`[{"control": name or None, "seed": n, **numbers}, ...]`."""
    from chipbench import harness

    rows = []
    for name in names:
        spec = None if name is None else cell.spec["controls"][name]
        for seed in seeds:
            t = time.perf_counter()
            try:
                numbers = driver.control(cell, seed, seconds, spec, ledger)
            except Exception as error:  # a control that crashes has failed, and sets no upper end
                if name is None:
                    raise
                harness.log(control=name, seed=seed, crashed=repr(error)[:400])
                continue
            finally:
                gc.collect()
            row = {"control": name, "seed": seed, **numbers, "took_s": round(time.perf_counter() - t, 1)}
            harness.log(**row)
            rows.append(row)
    return rows


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated, for the sound program")
    parser.add_argument("--control-seeds", default=None, help="comma-separated; default: the first three of --seeds")
    parser.add_argument("--seconds", type=float, default=12.0)
    parser.add_argument("--controls", default="all", help="'all', 'none', or comma-separated names")
    args = parser.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import harness

    cell, _device, ledger, _cache_dir = harness.open_cell(args.workload)
    driver = harness.load_module("drivers", cell.spec["driver"])
    seeds = [int(x) for x in args.seeds.split(",") if x]
    control_seeds = [int(x) for x in args.control_seeds.split(",")] if args.control_seeds else seeds[:3]
    available = list(cell.spec.get("controls", {}))
    names = {"all": available, "none": []}.get(args.controls, [x for x in args.controls.split(",") if x])
    rows = readings(cell, driver, seeds, args.seconds, [None], ledger)
    rows += readings(cell, driver, control_seeds, args.seconds, names, ledger)
    keys = [k for k in rows[0] if k not in ("control", "seed", "took_s")]
    for name in [None] + names:
        mine = [r for r in rows if r["control"] == name]
        if not mine:
            harness.log(summary=name, seeds=0, note="every run crashed: the control has failed and sets no upper end")
            continue
        harness.log(summary=name or "sound", seeds=len(mine),
                    **{k: {"min": min(r[k] for r in mine), "max": max(r[k] for r in mine)} for k in keys})
    return 0


if __name__ == "__main__":
    sys.exit(main())
