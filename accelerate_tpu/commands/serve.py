"""`accelerate-tpu serve` — drive a replicated serving fleet from the CLI.

Builds a `router.Router` over `--replicas` in-process engines (the
`ContinuousBatcher` slot/paged machinery behind a health-routed front-end:
least-loaded routing, bounded per-replica backpressure, never-streamed retry,
`finish_reason=replica_lost` for streamed requests on a dead replica, rolling
`swap_weights` — docs/serving.md "Replication") and serves a batch of requests
through it, emitting one JSON line per finished request on stdout::

    accelerate-tpu serve --model llama-tiny --replicas 3 --requests 16 \
        --max-new 32 --deadline-s 60

Prompts are synthetic token ids by default (`--requests N --seed S`, the bench
workload shape); ``--prompts-file FILE`` reads one JSON object per line with a
``"tokens": [int, ...]`` field instead. Exit code 0 when every request reached
a normal terminal reason, 1 when any finished `error`/`replica_lost`/`timeout`.

`--replicas` defaults to the launch env protocol
(``ACCELERATE_TPU_SERVE_REPLICAS``, exported by ``accelerate-tpu launch
--replicas N``), so a supervised serving job sizes its fleet from the launcher.

``--out-of-process`` runs each replica as a real subprocess engine worker
(`accelerate_tpu.worker`) — process-level fault domains with warm
restart/rejoin; ``--min-replicas``/``--max-replicas`` arm the queue/TTFT
autoscaler, and ``--hedge-quantile`` derives the hedge threshold from the
live TTFT histogram (docs/serving.md "Out-of-process workers").

``--transport socket`` carries the same worker frames over TCP with
reconnect-with-backoff (a torn link reconnects and resumes streams; only an
exhausted ``--reconnect-deadline`` budget respawns the worker), and
``--connect HOST:PORT[,...]`` adopts externally launched listener workers
(``python -m accelerate_tpu.worker --listen HOST:PORT``) — one replica per
address (docs/serving.md "Socket transport").
"""

from __future__ import annotations

import json
import sys


def register_subcommand(subparsers):
    parser = subparsers.add_parser(
        "serve",
        help="Serve a batch of requests through a replicated (health-routed) engine fleet",
        description=__doc__,
    )
    parser.add_argument("--model", default="llama-tiny", help="Named model (accelerate_tpu.models)")
    parser.add_argument(
        "--replicas", type=int, default=None,
        help="Engine fleet size (default: $ACCELERATE_TPU_SERVE_REPLICAS, else 2)",
    )
    parser.add_argument("--num-slots", type=int, default=4, help="Slots per replica engine")
    parser.add_argument("--chunk-size", type=int, default=8, help="Decode tokens per dispatch")
    parser.add_argument("--max-length", type=int, default=None, help="Per-slot cache length")
    parser.add_argument(
        "--max-queue", type=int, default=64,
        help="Bounded wait queue PER REPLICA (backpressure surfaces as queue_full)",
    )
    parser.add_argument(
        "--deadline-s", type=float, default=120.0,
        help="Default per-request wall-clock deadline (finish_reason=timeout past it)",
    )
    parser.add_argument(
        "--hedge-after-s", type=float, default=None,
        help="TTFT hedging: duplicate a still-queued request onto a second replica "
        "after this many seconds (default: disabled)",
    )
    parser.add_argument(
        "--hedge-quantile", type=float, default=None,
        help="derive the hedge threshold from the live TTFT histogram at this "
        "quantile instead of a static --hedge-after-s (enabled once enough "
        "samples exist; mutually exclusive with --hedge-after-s)",
    )
    parser.add_argument(
        "--out-of-process", action="store_true",
        help="run each replica as a REAL subprocess engine worker "
        "(accelerate_tpu.worker IPC): process-level fault domains — a worker "
        "SIGKILL/hang ejects one replica, never the fleet",
    )
    parser.add_argument(
        "--transport", default="pipe", choices=["pipe", "socket"],
        help="out-of-process worker transport: 'pipe' = stdio frames on the "
        "spawned child, 'socket' = the same frames over TCP loopback with "
        "reconnect-with-backoff on torn links (a healed partition reconnects "
        "and resumes streams instead of respawning the worker)",
    )
    parser.add_argument(
        "--connect", default=None, metavar="HOST:PORT[,HOST:PORT...]",
        help="adopt EXTERNALLY launched listener workers (python -m "
        "accelerate_tpu.worker --listen HOST:PORT) instead of spawning: one "
        "replica per address, socket transport implied; the model's params "
        "path must be reachable on each worker's host (digest-verified)",
    )
    parser.add_argument(
        "--reconnect-deadline", type=float, default=None, dest="reconnect_deadline_s",
        help="socket-transport reconnect budget in seconds before a torn link "
        "escalates to the worker-death/respawn path (default: 10.0)",
    )
    parser.add_argument(
        "--min-replicas", type=int, default=None,
        help="autoscaler floor (with --max-replicas): the fleet never shrinks below this",
    )
    parser.add_argument(
        "--max-replicas", type=int, default=None,
        help="autoscaler ceiling: enables traffic-adaptive scaling between "
        "--min-replicas (default: --replicas) and this on queue-depth/TTFT pressure",
    )
    parser.add_argument("--requests", type=int, default=8, help="Synthetic request count")
    parser.add_argument("--max-new", type=int, default=32, help="max_new_tokens per request")
    parser.add_argument("--prompt-min", type=int, default=4)
    parser.add_argument("--prompt-max", type=int, default=32)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--prompts-file", default=None,
        help='JSONL with one {"tokens": [...], "max_new_tokens": N?} object per line '
        "(replaces the synthetic workload)",
    )
    parser.add_argument(
        "--weight-dtype", default="bf16", choices=["bf16", "int8"],
        help="weight storage dtype: int8 quantizes per-output-channel at load "
        "time and runs every Dense through the fused int8-epilogue matmul "
        "(ops/quantization.py) — ~2x less weight HBM traffic per decode step",
    )
    parser.add_argument(
        "--kv-cache-dtype", default="bf16", choices=["bf16", "int8", "fp8_e4m3"],
        help="KV page-pool storage dtype: int8/fp8_e4m3 "
        "store pages quantized with per-page-per-head scales, cutting "
        "cache-read bytes 2x vs bf16 and multiplying pool capacity",
    )
    parser.add_argument(
        "--tp", type=int, default=1,
        help="tensor-parallel degree PER ENGINE: each replica spans its own "
        "tp-device submesh (weights Megatron-sharded, the KV pool sharded by "
        "KV head — docs/serving.md \"Tensor-parallel engines\"); replicas "
        "get disjoint device groups when the topology allows, so "
        "--replicas R --tp N uses R*N chips",
    )
    parser.add_argument(
        "--sharding", default="rules", choices=["rules", "auto"],
        help="tensor-parallel partition source: \"rules\" = the model "
        "family's hand-written table, \"auto\" = the cost-model planner "
        "searches the layout and emits an equivalent table "
        "(accelerate-tpu plan shows what it would pick)",
    )
    parser.set_defaults(func=serve_command)
    return parser


def _load_requests(args, vocab_size):
    import numpy as np

    from ..serving import Request

    if args.prompts_file:
        requests = []
        with open(args.prompts_file) as f:
            for i, line in enumerate(f):
                line = line.strip()
                if not line:
                    continue
                record = json.loads(line)
                requests.append(Request(
                    i, np.asarray(record["tokens"], np.int32),
                    max_new_tokens=int(record.get("max_new_tokens", args.max_new)),
                ))
        return requests
    rng = np.random.default_rng(args.seed)
    lo, hi = args.prompt_min, max(args.prompt_min, args.prompt_max)
    return [
        Request(
            i,
            rng.integers(1, vocab_size, (int(rng.integers(lo, hi + 1)),)).astype(np.int32),
            max_new_tokens=args.max_new,
        )
        for i in range(args.requests)
    ]


def serve_command(args):
    from ..models import create_named_model, get_model_family
    from ..router import Router

    if args.sharding == "auto" and args.tp <= 1:
        print(
            "accelerate-tpu serve: --sharding auto plans a tensor-parallel "
            "layout — pass --tp N (N > 1); a single-device engine has nothing "
            "to partition",
            file=sys.stderr,
        )
        raise SystemExit(2)
    if args.tp > 1 and args.out_of_process:
        print(
            "accelerate-tpu serve: --tp composes with in-process replicas only "
            "for now — subprocess workers pin their own device view (multi-host "
            "TP workers are ROADMAP item 2)",
            file=sys.stderr,
        )
        raise SystemExit(2)
    connect = (
        [a.strip() for a in args.connect.split(",") if a.strip()]
        if args.connect else None
    )
    if connect:
        # Adopting external listeners IS the out-of-process socket path.
        args.out_of_process = True
        args.transport = "socket"
        if args.replicas is None:
            args.replicas = len(connect)
    if args.transport == "socket" and not args.out_of_process:
        print(
            "accelerate-tpu serve: --transport socket needs worker processes — "
            "pass --out-of-process (or --connect for external workers)",
            file=sys.stderr,
        )
        raise SystemExit(2)
    worker_kwargs = {}
    if args.out_of_process:
        worker_kwargs["transport"] = args.transport
        if connect:
            worker_kwargs["connect"] = connect
        if args.reconnect_deadline_s is not None:
            worker_kwargs["reconnect_deadline_s"] = args.reconnect_deadline_s
    _fam, cfg = get_model_family(args.model)
    requests = _load_requests(args, cfg.vocab_size)
    if not requests:
        print("accelerate-tpu serve: no requests to serve", file=sys.stderr)
        raise SystemExit(2)
    longest = max(int(len(r.input_ids)) + r.max_new_tokens for r in requests)
    max_length = args.max_length or min(cfg.max_position_embeddings, longest)
    model = create_named_model(args.model, seq_len=min(128, max_length))
    router = Router(
        model,
        replicas=args.replicas,
        num_slots=args.num_slots,
        max_length=max_length,
        chunk_size=args.chunk_size,
        max_queue=args.max_queue,
        default_deadline_s=args.deadline_s,
        hedge_after_s=args.hedge_after_s,
        hedge_quantile=args.hedge_quantile,
        min_replicas=args.min_replicas,
        max_replicas=args.max_replicas,
        out_of_process=args.out_of_process,
        worker_kwargs=worker_kwargs or None,
        weight_dtype=args.weight_dtype,
        kv_cache_dtype=args.kv_cache_dtype,
        tp=args.tp,
        sharding_rules=args.sharding,
    )
    print(
        f"[serve] model {args.model} | "
        f"{f'out-of-process ({args.transport}), ' if args.out_of_process else ''}"
        f"{router.num_replicas} replica(s) x "
        f"{args.num_slots} slots, chunk {args.chunk_size}, cache {max_length}"
        + (f", tp {args.tp}" if args.tp > 1 else "")
        + f" | {len(requests)} request(s)",
        file=sys.stderr, flush=True,
    )
    # Pace submissions against the fleet's backpressure: a workload larger
    # than replicas * max_queue must wait for capacity, not crash on the
    # QueueFull signal the bounded queues exist to raise.
    from collections import deque

    from ..serving import QueueFull

    pending = deque(requests)
    while pending or router.pending:
        while pending:
            try:
                router.submit(pending[0])
            except QueueFull:
                break
            pending.popleft()
        router.step()
    results = router.drain()
    abnormal = 0
    for rid in sorted(results):
        result = results[rid]
        if result.finish_reason not in ("eos", "length"):
            abnormal += 1
        print(json.dumps({
            "request_id": rid,
            "finish_reason": result.finish_reason,
            "tokens": [int(t) for t in result.tokens],
        }))
    stats = router.stats
    print(
        f"[serve] done: {len(results)} finished ({abnormal} abnormal) | "
        f"retries {stats['retries']} hedges {stats['hedges']} "
        f"ejected {stats['ejected']} | states {stats['replica_states']}",
        file=sys.stderr, flush=True,
    )
    router.close()
    raise SystemExit(0 if abnormal == 0 else 1)
