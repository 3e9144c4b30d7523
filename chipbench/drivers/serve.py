"""Driver of the cells that serve a model: `Router(replicas=1)` over the
program's `ContinuousBatcher`, fed by one client loop in this process.

The loop is `commands/serve.py`'s: submit what is due until the queue refuses,
then `step()`. Tokens count when `step()` hands them back, which is when a
client would see them. The window opens on a step boundary after `ramp_s` of the
same traffic (so the slots and the queue are in their steady state) and closes
on the first step boundary `seconds` later; a rate is all the tokens over all
that time, a tail is over every request that was DUE in the window, timed from
when it was due.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import harness, shapes, traffic_gen

NORMAL_FINISH = ("eos", "length")


class Served:
    """What the client knows of one request."""

    __slots__ = ("rid", "due", "prompt_len", "out_len", "submitted", "first", "last",
                 "tokens", "reason")

    def __init__(self, rid, due, prompt_len, out_len):
        self.rid, self.due, self.prompt_len, self.out_len = rid, due, prompt_len, out_len
        self.submitted = self.first = self.last = None
        self.tokens: list = []
        self.reason = None


def build_router(cell, params, dtype: str, **overrides):
    from accelerate_tpu.router import Router

    adapter = harness.load_module("adapters", cell.config["family"], cell.root)
    model = adapter.build_model(cell.config, params, dtype)
    engine_args = dict(cell.spec["engine"], **overrides)
    return Router(model, replicas=1, **engine_args)


def make_request(rid: int, prompt, out_len: int):
    """A greedy request forced to its length (no EOS: random weights never end)."""
    from accelerate_tpu.serving import Request

    return Request(rid, prompt, max_new_tokens=int(out_len), eos_token_id=None)


def warm_up(router, stream, first_id: int) -> int:
    """Compile the decode chunk and every insert bucket the mix's prompt
    lengths map to, through the engine's own admission: one short request a
    distinct power-of-two ceiling. Returns the next free request id."""
    lengths = {}
    for i in range(stream.pool):
        _due, prompt, _out = stream.request(i)
        lengths.setdefault(1 << (len(prompt) - 1).bit_length(), len(prompt))
    rid = first_id
    rng = np.random.default_rng([stream.seed, 6])
    for _bucket, n in sorted(lengths.items()):
        ids = rng.integers(1, stream.vocab_size, n).astype(np.int32)
        router.submit(make_request(rid, ids, 2 * router_engine(router).chunk_size + 1))
        rid += 1
    results = router.drain()
    for warm_id in range(first_id, rid):
        if results[warm_id].finish_reason not in NORMAL_FINISH:
            raise RuntimeError(f"warm-up request finished {results[warm_id].finish_reason!r}: "
                               f"{results[warm_id].error}")
        router.release(warm_id)
    return rid


def router_engine(router):
    return router.replica_set.replicas[0].engine


def drive(router, stream, seconds: float, ramp_s: float, trace, first_id: int = 0, clock=time.perf_counter, drain_limit_s: float = 120.0,
          at_close=None) -> dict:
    """The client loop. Returns the window's records: every `Served`, the
    per-step samples, and the window's bounds on `clock`."""
    from accelerate_tpu.serving import QueueFull

    engine = router_engine(router)
    tracing = trace.enabled
    served: dict = {}
    steps: list = []  # (start, end, slots_in_use, pages_in_use, live_tokens, new_tokens)
    backlog = stream.backlog
    t_start = clock()
    next_i, built = 0, None
    live_tokens = 0
    t0 = None
    window_tokens = 0
    lateness: list = []

    def submit_due(now: float, until: float | None) -> None:
        nonlocal next_i, built
        while True:
            if built is None:
                due, prompt, out_len = stream.request(next_i)
                built = (0.0 if backlog else due, prompt, out_len)
            due, prompt, out_len = built
            if due > now - t_start or (until is not None and due >= until):
                return
            rid = first_id + next_i
            try:
                router.submit(make_request(rid, prompt, out_len))
            except QueueFull:
                return
            record = Served(rid, t_start + due, len(prompt), out_len)
            record.submitted = clock()
            if not backlog:
                lateness.append(record.submitted - record.due)
            served[rid] = record
            next_i += 1
            built = None

    def take(events, now: float) -> int:
        nonlocal live_tokens
        new = 0
        for rid, tokens in events:
            record = served.get(rid)
            if record is None or not tokens:
                continue
            if record.first is None:
                record.first = now
                live_tokens += record.prompt_len
            record.last = now
            record.tokens.extend(int(t) for t in tokens)
            live_tokens += len(tokens)
            new += len(tokens)
        for rid in [rid for rid, _ in events]:
            record = served.get(rid)
            result = router.results.get(rid)
            if record is not None and record.reason is None and result is not None and result.finished:
                record.reason = result.finish_reason
                live_tokens -= record.prompt_len + len(record.tokens)
                router.release(rid)
        return new

    def one_step() -> float:
        a = clock()
        live_before = live_tokens
        with harness.span("bench.step", tracing):
            events = router.step()
        b = clock()
        new = take(events, b)
        steps.append((a, b, engine.slots_in_use, engine.pool.pages_in_use if engine.paged else 0,
                      live_before, new))
        return b

    def settle() -> None:
        """Requests that ended without a token event (an isolated error)."""
        for record in served.values():
            result = router.results.get(record.rid) if record.reason is None else None
            if result is not None and result.finished:
                record.reason = result.finish_reason
                router.release(record.rid)

    closing = None
    while True:
        now = clock()
        if t0 is not None and now - t0 >= seconds:
            break
        with harness.span("bench.submit", tracing):
            submit_due(now, closing)
        if router.pending:
            b = one_step()
            if t0 is None and b - t_start >= ramp_s:
                t0 = b
                closing = None if backlog else (t0 - t_start) + seconds
            elif t0 is not None:
                window_tokens += steps[-1][5]
        else:
            if t0 is None and now - t_start >= ramp_s:
                t0 = now
                closing = None if backlog else (t0 - t_start) + seconds
            if built is None:
                due, prompt, out_len = stream.request(next_i)
                built = (0.0 if backlog else due, prompt, out_len)
            wake = t_start + built[0]
            if t0 is not None:
                wake = min(wake, t0 + seconds)
            with harness.span("bench.wait_due", tracing):
                time.sleep(max(0.0, wake - clock()))
        if t0 is not None:
            trace.poll(clock() - t0)
    t1 = now
    trace.stop()
    if at_close is not None:
        at_close()

    # An open loop owes an answer to every request that was due in the window:
    # keep stepping (and submitting what was due but refused) until they finish.
    if not backlog:
        t_limit = clock() + drain_limit_s
        while clock() < t_limit:
            submit_due(clock(), closing)
            settle()
            owed = [r for r in served.values() if t0 <= r.due < t0 + seconds and r.reason is None]
            due_unsent = built is not None and built[0] < closing and built[0] <= clock() - t_start
            if not owed and not due_unsent:
                break
            if router.pending:
                one_step()
            else:
                time.sleep(0.001)
    settle()
    return {"served": served, "steps": steps, "t0": t0, "t1": t1, "t_start": t_start,
            "window_tokens": window_tokens, "lateness": lateness, "backlog": backlog}


def end_to_end(window: dict, seconds: float) -> dict:
    """The end-to-end numbers of one window, from the client's records alone."""
    t0, t1 = window["t0"], window["t1"]
    served = window["served"].values()
    values = {"serve_tokens_per_s": window["window_tokens"] / (t1 - t0)}
    if window["backlog"]:
        counted = [r for r in served if r.reason is not None and r.last is not None and t0 <= r.last <= t1]
    else:
        counted = [r for r in served if t0 <= r.due < t0 + seconds]
    failed = [r for r in counted if r.reason not in NORMAL_FINISH]
    ok = [r for r in counted if r.reason in NORMAL_FINISH]
    ttft = [(r.first - r.due) * 1e3 for r in ok]
    tpot = [(r.last - r.first) / (len(r.tokens) - 1) * 1e3 for r in ok if len(r.tokens) > 1]
    if not window["backlog"] and ttft:
        values["ttft_p95_ms"] = harness.percentile(ttft, 95)
        values["ttft_p50_ms"] = harness.median(ttft)
    if not window["backlog"] and tpot:
        values["tpot_p95_ms"] = harness.percentile(tpot, 95)
        values["tpot_p50_ms"] = harness.median(tpot)
    return {"values": values, "attempted": len(counted), "failed": len(failed), "ok": ok}


def check_outputs(cell, ok: list, stream, seed: int, reference, params, first_id: int = 0) -> dict:
    """`correct`: a seeded sample of the window's finished requests, the longest
    among them, teacher-forced once through the float32 reference. Each number
    is printed beside its limit."""
    limits = cell.spec["correct"]
    if not ok:
        harness.log(check="no request finished in the window", correct=False)
        return {"correct": False, "numbers": {}}
    rng = np.random.default_rng([int(seed), 7])
    ok = sorted(ok, key=lambda r: r.rid)
    longest = max(ok, key=lambda r: (r.prompt_len + len(r.tokens), r.rid))
    others = [r for r in ok if r is not longest]
    picks = [longest] + [others[j] for j in rng.permutation(len(others))[: limits["sample"] - 1]]
    wrong_length = sum(len(r.tokens) != r.out_len for r in picks)
    pad_to = int(cell.spec["engine"]["max_length"])
    rows = stream.max_output_len
    pairs = [(stream.prompt(r.rid - first_id), r.tokens[: r.out_len]) for r in picks]
    t = time.perf_counter()
    gaps = np.concatenate(reference.served_token_gaps(params, cell.config, pairs, pad_to, rows))
    numbers = {
        "mean_gap": (float(gaps.mean()), limits["mean_gap_limit"]),
        "max_gap": (float(gaps.max()), limits["max_gap_limit"]),
        "wrong_length": (float(wrong_length), 0.0),
    }
    correct = all(value <= limit for value, limit in numbers.values())
    harness.log(check="served tokens against the float32 reference", requests=len(picks),
                tokens=int(gaps.size), reference_s=round(time.perf_counter() - t, 3),
                **{k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()}, correct=correct)
    return {"correct": correct, "numbers": numbers}


def serve_once(cell, seed: int, seconds: float, trace, ledger, t_process_start: float,
               engine_overrides: dict | None = None) -> dict:
    """One engine, one warm-up, one window, one check: a benchmark run, or with
    `engine_overrides` a control (the program with a lower-precision path on)."""
    import jax

    reference = harness.load_module("reference", cell.config["family"], cell.root)
    dtype = cell.spec["dtype"]
    params = reference.init_params(cell.config, harness.seed_key(seed), dtype)
    jax.block_until_ready(params)
    harness.log(phase="weights", at_s=round(time.perf_counter() - t_process_start, 3))
    router = build_router(cell, params, dtype, **(engine_overrides or {}))
    engine = router_engine(router)
    stream = traffic_gen.RequestStream(cell.traffic, cell.config["vocab_size"], seed)
    harness.log(phase="engine", at_s=round(time.perf_counter() - t_process_start, 3))
    first_id = warm_up(router, stream, first_id=0)
    harness.log(phase="warm-up", at_s=round(time.perf_counter() - t_process_start, 3), **ledger.line())
    compiles_before = ledger.compiles
    window = drive(router, stream, float(seconds), float(cell.traffic.get("ramp_s", 0.0)),
                   trace, first_id=first_id)
    compiles_in_window = ledger.compiles - compiles_before
    peak = harness.memory_peak_bytes(cell.chips)
    e2e = end_to_end(window, float(seconds))
    values = dict(e2e["values"], setup_s=window["t0"] - t_process_start)
    harness.log(phase="window", seconds=round(window["t1"] - window["t0"], 3),
                requests=e2e["attempted"], failed=e2e["failed"], steps=len(window["steps"]),
                compiles_in_window=compiles_in_window,
                generator_late_p95_ms=(harness.percentile(window["lateness"], 95) * 1e3
                                       if window["lateness"] else None),
                **{k: round(v, 4) for k, v in values.items()}, **ledger.line())
    context = {
        "cell": cell, "window": window, "seconds": float(seconds), "num_slots": engine.num_slots,
        "pages_total": engine.pool.pages_total if engine.paged else 0, "peak_bytes": peak,
        "compiles_in_window": compiles_in_window, "chunk_size": engine.chunk_size,
        "kv_bytes_per_token": shapes.kv_bytes_per_token(cell.config, dtype),
    }
    router.close()
    del router, engine
    gc.collect()
    reduced = trace.reduce(cell.chips)
    context.update(trace=reduced, trace_span=(trace.started_at, trace.stopped_at))
    check = check_outputs(cell, e2e["ok"], stream, seed, reference, params, first_id)
    return {"values": values, "e2e": e2e, "context": context, "reduced": reduced, "peak": peak,
            "check": check, "correct": check["correct"] and e2e["failed"] == 0}


def run(cell, args, device: dict, ledger, t_process_start: float) -> dict:
    spec_trace = cell.spec.get("trace", {})
    trace = harness.TraceWindow(bool(args.trace), spec_trace.get("start_after_s", 2.0),
                                spec_trace.get("length_s", 3.0))
    out = serve_once(cell, args.seed, args.seconds, trace, ledger, t_process_start)
    values = out["values"]
    if args.trace:
        context = dict(out["context"], peaks=harness.peaks_for(device["kind"], cell.root))
        values.update(harness.read_per_layer(cell, context))
    return harness.result_line(cell, bool(args.trace), device, out["correct"], out["e2e"]["attempted"],
                               out["e2e"]["failed"], values, out["reduced"], out["peak"])


def control(cell, seed: int, seconds: float, control_spec: dict | None, ledger) -> dict:
    """The check's numbers of one short window: of the program as the cell runs
    it (`control_spec` None), or with a lower-precision path of its own on."""
    trace = harness.TraceWindow(False, 0.0, 0.0)
    overrides = None if control_spec is None else control_spec["engine"]
    out = serve_once(cell, seed, seconds, trace, ledger, time.perf_counter(), overrides)
    return {k: v for k, (v, _limit) in out["check"]["numbers"].items()}
