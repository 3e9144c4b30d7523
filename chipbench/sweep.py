#!/usr/bin/env python3
"""The rate sweep that places an open-loop cell: the cell's engine, built once,
offered its traffic at several fixed rates, one window each. Done once, when the
cell is defined; the rate it yields is written into the traffic file as a number
and the table into `PERF.md`. Not part of a benchmark run. Needs the TPU.

    python3 chipbench/sweep.py --workload <cell> --seed 1 --seconds 40 --rates 1.6,1.9,2.2

The knee is the highest rate at which >= 99% of the requests due in the window
finished and the queue at the window's end is no deeper than the slot count.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--rates", required=True, help="comma-separated requests/s")
    args = parser.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import harness, traffic_gen

    cell, _device, _ledger, _cache_dir = harness.open_cell(args.workload)
    serve = harness.load_module("drivers", "serve")
    reference = harness.load_module("reference", cell.config["family"])
    params = reference.init_params(cell.config, harness.seed_key(args.seed), cell.spec["dtype"])
    router = serve.build_router(cell, params, cell.spec["dtype"])
    engine = serve.router_engine(router)

    stream = traffic_gen.RequestStream(cell.traffic, cell.config["vocab_size"], args.seed)
    next_id = serve.warm_up(router, stream, first_id=0)
    harness.log(mean_output_len=stream.mean_output_len, slots=engine.num_slots)
    for k, rate in enumerate(float(x) for x in args.rates.split(",")):
        traffic = dict(cell.traffic, arrivals=dict(cell.traffic["arrivals"], rate_per_s=rate))
        stream = traffic_gen.RequestStream(traffic, cell.config["vocab_size"], args.seed + k)
        trace = harness.TraceWindow(False, 0.0, 0.0)
        queue_at_end = []
        t = time.perf_counter()
        window = serve.drive(router, stream, args.seconds, float(traffic.get("ramp_s", 0.0)), trace,
                             first_id=next_id,
                             at_close=lambda: queue_at_end.append((engine.queue_depth, engine.slots_in_use)))
        next_id += len(window["served"]) + 1
        t0 = window["t0"]
        due = [r for r in window["served"].values() if t0 <= r.due < t0 + args.seconds]
        done_in_window = [r for r in due if r.reason in serve.NORMAL_FINISH and r.last <= window["t1"]]
        e2e = serve.end_to_end(window, args.seconds)
        ttft = [(r.first - r.due) * 1e3 for r in e2e["ok"]]
        tpot = [(r.last - r.first) / (len(r.tokens) - 1) * 1e3 for r in e2e["ok"] if len(r.tokens) > 1]
        harness.log(rate_per_s=rate, due=len(due), finished_in_window_share=round(len(done_in_window) / max(len(due), 1), 4),
                    queue_at_end=queue_at_end[0][0], slots_at_end=queue_at_end[0][1], failed=e2e["failed"],
                    tokens_per_s=round(e2e["values"]["serve_tokens_per_s"], 1),
                    ttft_ms={q: round(harness.percentile(ttft, q), 1) for q in (50, 90, 95, 99)},
                    tpot_ms={q: round(harness.percentile(tpot, q), 2) for q in (50, 90, 95, 99)},
                    generator_late_p95_ms=round(harness.percentile(window["lateness"], 95) * 1e3, 2),
                    took_s=round(time.perf_counter() - t, 1))
    router.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
