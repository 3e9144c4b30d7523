"""From a profiler capture to numbers: device busy and idle time, device time a
module, the operations that took most time, and idle gaps attributed to what
the host was doing.

A capture is reduced in two steps so that the arithmetic can be tested without a
chip: `load_events` turns an `.xplane.pb` into plain tuples, `reduce_events`
turns tuples into numbers. `tests/chipbench/data/` keeps tuples trimmed from a
chip run.

Planes, as the v5e's captures have them: `/device:TPU:<n>` with the lines
`XLA Modules` (one event a program execution) and `XLA Ops` (one event an
operation on the TensorCore's one instruction stream; a `while` or a call
covers the events of its body, so an operation's own time is its duration minus
its direct children's);
`/host:CPU` with one line a thread, where `jax.profiler.TraceAnnotation` spans
appear under their own names. All times are nanoseconds on one clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Host spans the benchmark writes around its calls into the program.
HOST_SPAN_PREFIX = "bench."
#: Gaps shorter than this are launch latency between back-to-back operations.
MIN_GAP_NS = 20_000


def load_events(path: str) -> list:
    """`[(plane, line, name, start_ns, duration_ns), ...]` of the device planes'
    module and op lines and of the host's `bench.*` spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        on_device = DEVICE_PLANE.match(plane.name) is not None
        if not on_device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for event in line.events:
                name = event.name
                if not on_device and not name.startswith(HOST_SPAN_PREFIX):
                    continue
                events.append((plane.name, line.name if on_device else "host", name,
                               int(event.start_ns), int(event.duration_ns)))
    return events


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str, chips: int) -> dict:
    return reduce_events(load_events(find_xplane(trace_dir)), chips)


def union(intervals) -> list:
    """Merge `(start, end)` pairs into disjoint sorted intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def total(intervals) -> int:
    return sum(end - start for start, end in intervals)


def module_name(name: str) -> str:
    """`jit_decode_chunk(1234567)` -> `jit_decode_chunk`."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_name(name: str) -> str:
    """The trace names an operation by its whole HLO line (`%fusion.12 = bf16[...]
    fusion(...)`, kilobytes for a loop): keep the instruction's name, without the
    numbering XLA appends, so that the repeats of one kind add up."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)[:80]


def self_times(ops: list) -> list:
    """`[(name, self_ns), ...]` of one line's events: each event's duration
    minus the durations of the events directly nested in it."""
    out, stack = [], []  # stack of [end, index into out]
    for e in sorted(ops, key=lambda e: (e[3], -e[4])):
        start, end = e[3], e[3] + e[4]
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= e[4]
        out.append([e[2], e[4]])
        stack.append([end, len(out) - 1])
    return out


def overlap(a_start: int, a_end: int, b_start: int, b_end: int) -> int:
    return max(0, min(a_end, b_end) - max(a_start, b_start))


def reduce_events(events: list, chips: int) -> dict:
    """Numbers of one capture. Seconds throughout; shares are of the window,
    which runs from the first to the last instant any kept event covers."""
    device_planes = sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])},
                           key=lambda p: int(DEVICE_PLANE.match(p).group(1)))[:chips]
    if not device_planes:
        raise ValueError("the capture holds no device plane: no operation ran on the device")
    kept = [e for e in events if e[0] in device_planes or e[1] == "host"]
    t0 = min(e[3] for e in kept)
    t1 = max(e[3] + e[4] for e in kept)

    busy_ns = []
    by_op: dict = {}
    by_module: dict = {}
    module_runs: dict = {}
    first_busy = None
    for plane in device_planes:
        ops = [e for e in events if e[0] == plane and e[1] == OPS_LINE]
        modules = [e for e in events if e[0] == plane and e[1] == MODULES_LINE]
        merged = union((e[3], e[3] + e[4]) for e in (ops or modules))
        busy_ns.append(total(merged))
        if first_busy is None:
            first_busy = merged
        for name, ns in self_times(ops):
            name = op_name(name)
            by_op[name] = by_op.get(name, 0) + ns
        for e in modules:
            name = module_name(e[2])
            by_module[name] = by_module.get(name, 0) + e[4]
            module_runs[name] = module_runs.get(name, 0) + 1

    n = len(device_planes)
    # Idle gaps on the first chip, each given to the host span that covers most of it.
    host = [e for e in events if e[1] == "host"]
    gaps: dict = {}
    edges = [t0] + [x for iv in first_busy for x in iv] + [t1]
    for gap_start, gap_end in zip(edges[0::2], edges[1::2]):
        if gap_end - gap_start < MIN_GAP_NS:
            continue
        best, best_ns = "(no bench span)", 0
        for e in host:
            ns = overlap(gap_start, gap_end, e[3], e[3] + e[4])
            if ns > best_ns:
                best, best_ns = e[2], ns
        gaps[best] = gaps.get(best, 0) + (gap_end - gap_start)

    def top(table: dict, scale: float = 1.0) -> list:
        return [[k, v / 1e9 * scale] for k, v in sorted(table.items(), key=lambda kv: -kv[1])]

    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "chips": n,
        # per-chip means, so a four-chip capture reads like a one-chip one
        "device_ops": top(by_op, 1.0 / n),
        "modules": {k: {"seconds": v / n / 1e9, "runs": module_runs[k] // n} for k, v in by_module.items()},
        "idle_gaps": top(gaps),
    }


def module_seconds(reduced: dict, pattern: str) -> tuple:
    """`(seconds, runs)` summed over the modules whose name matches `pattern`."""
    seconds, runs = 0.0, 0
    for name, entry in reduced["modules"].items():
        if re.search(pattern, name):
            seconds += entry["seconds"]
            runs += entry["runs"]
    return seconds, runs
