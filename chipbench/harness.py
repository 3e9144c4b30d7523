"""What every cell shares: finding a cell's files by name, the device check, the
compile ledger, the host-clock statistics, the traced sub-window and the one
result line. Nothing here knows a configuration, a traffic mix or a metric by
name — those are files (`configs/`, `traffic/`, `workloads/`, `readers/`) that
`BENCHMARK.json` names and this module only looks up.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import re
import shutil
import statistics
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = ROOT):
    """`chipbench/<kind>/<name>.py`, found by name (a metric named
    `hbm_peak_gb.serve` is not an importable dotted path, so load by file)."""
    if not _NAME.match(name):
        raise ValueError(f"not a name: {name!r}")
    path = os.path.join(root, "chipbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of `workloads`, with the files its names resolve to."""

    def __init__(self, workload: str, root: str = ROOT):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        entry = next((w for w in self.bench["workloads"] if w["name"] == workload), None)
        if entry is None:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        self.name = workload
        self.chips = int(entry["chips"])
        config_entry = next(c for c in self.bench["configs"] if c["name"] == entry["config"])
        self.config = load_json(os.path.join(root, config_entry["file"]))
        self.traffic = load_json(os.path.join(root, "chipbench", "traffic", entry["traffic"] + ".json"))
        self.spec = load_json(os.path.join(root, "chipbench", "workloads", workload + ".json"))

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    @property
    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    @property
    def per_layer(self) -> list:
        mine = {m["name"] for m in self.end_to_end}
        return [m for m in self.bench["per_layer"] if m["moves"] in mine and self._reports(m)]


# ------------------------------------------------------------------------ device
def require_chips(chips: int) -> dict:
    """The device as JAX reports it; no TPU or too few chips ends the run with
    exit code 1 and no result line. Nothing re-runs on CPU."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (platform {platform!r}); there is no CPU mode")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: this cell needs {chips} chip(s), JAX reports {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)}


def place_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at ONE fixed directory inside the
    checkout, uncapped, holding every program however fast it compiled — so that
    only a checkout's first run of a cell compiles, and two checkouts share
    nothing. The program's own `configure_compile_cache()` names the same
    directory, and sets nothing where the environment names another."""
    import jax

    path = os.path.join(root, ".jax_compile_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def open_cell(workload: str) -> tuple:
    """What `run.py`, `control.py` and `sweep.py` all start with: the cell, the
    system under test (a checkout that holds only the benchmark ends here), the
    compile cache, the chips. Returns `(cell, device, ledger, cache_dir)`."""
    cell = Cell(workload)
    import accelerate_tpu  # noqa: F401

    cache_dir = place_compile_cache()
    device = require_chips(cell.chips)
    return cell, device, CompileLedger(), cache_dir


def peaks_for(kind: str, root: str = ROOT) -> dict:
    table = load_json(os.path.join(root, "chipbench", "peaks.json"))
    if kind not in table:
        raise SystemExit(f"chipbench: device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    """Peak device memory of the fullest chip. The TPU runtime keeps two pools:
    `peak_bytes_in_use` counts live arrays (11.81 GB for the serving cell, 2.32 GB
    for BERT at any batch), `peak_bytes_reserved` what running programs reserve
    for their temporaries (0.78 GB for serving, where the compiler's own
    analysis says 0.80; 12.47 GB for BERT's batch-256 step). The two peaks need
    not coincide, so the larger of them is reported: a lower bound of the chip's
    true peak."""
    import jax

    peaks = []
    for device in jax.devices()[:chips]:
        stats = device.memory_stats() or {}
        peaks.append(max(int(stats.get("peak_bytes_in_use", 0)), int(stats.get("peak_bytes_reserved", 0))))
    return max(peaks)


class CompileLedger:
    """Compilations and persistent-cache traffic from `jax.monitoring`. A
    program fetched from the persistent cache still counts as a compile event:
    inside a measured window either is a stall."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if event == self._COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def line(self) -> dict:
        return {"compiles": self.compiles, "compile_s": round(self.compile_s, 3),
                "cache_requests": self.requests, "cache_hits": self.hits,
                "cache_misses": self.requests - self.hits}


def seed_key(seed: int):
    """A PRNG key from any whole number up to past 2**32 (the driver's seeds
    are larger than a signed 32-bit int holds)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def log(**record) -> None:
    print(json.dumps(record), flush=True)


# -------------------------------------------------------------------- statistics
def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics, as `numpy.percentile`'s default does."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values) -> float:
    return statistics.median(values)


def time_weighted_mean(samples) -> float:
    """Mean of a step function sampled as (duration, value) pairs."""
    total = sum(d for d, _ in samples)
    return sum(d * v for d, v in samples) / total if total > 0 else 0.0


# ----------------------------------------------------------------- traced window
class TraceWindow:
    """A short profiler capture inside the measured window of a `--trace 1` run.
    `poll` is called once a loop turn; the capture runs from
    `start_after` seconds into the window for `length` seconds, and is reduced
    by `trace_reduce` after the window has closed."""

    def __init__(self, enabled: bool, start_after: float, length: float):
        self.enabled = enabled
        self.start_after = start_after
        self.length = length
        self.dir = None
        self.started_at = None
        self.stopped_at = None

    def poll(self, since_window_start: float) -> None:
        if not self.enabled or self.stopped_at is not None:
            return
        import jax

        if self.started_at is None:
            if since_window_start >= self.start_after:
                self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # the Python tracer slows the host it measures
                jax.profiler.start_trace(self.dir, profiler_options=options)
                self.started_at = time.perf_counter()
        elif time.perf_counter() - self.started_at >= self.length:
            self.stop()

    def stop(self) -> None:
        if self.started_at is None or self.stopped_at is not None:
            return
        import jax

        jax.profiler.stop_trace()
        self.stopped_at = time.perf_counter()

    def reduce(self, chips: int) -> dict | None:
        """Parse the capture and delete it. Runs after the window."""
        if not self.enabled:
            return None
        self.stop()
        if self.dir is None:
            raise RuntimeError("the traced window never started: the measured window was too short")
        from chipbench import trace_reduce

        try:
            return trace_reduce.reduce_dir(self.dir, chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@contextlib.contextmanager
def span(name: str, enabled: bool):
    """A host span on the profiler's clock (`jax.profiler.TraceAnnotation`);
    free when the run is not traced."""
    if not enabled:
        yield
        return
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


# -------------------------------------------------------------------- the result
def result_line(cell: Cell, trace: bool, device: dict, correct: bool, attempted: int,
                failed: int, values: dict, reduced: dict | None, peak_bytes: int) -> dict:
    """The contract's last line. `values` holds every number the run measured,
    by metric name; only this cell's metrics of this mode go on the line."""
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = values.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=int(peak_bytes))
    line = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                             "idle_gaps": reduced["idle_gaps"][:10]}
    return line


def load_reader(metric: str, root: str = ROOT):
    """`readers/<metric>.py`. A quantity split by the end-to-end metric it moves
    (`device_idle_pct.serve`, `device_idle_pct.train`) may keep one reader under
    the quantity's name (`readers/device_idle_pct.py`)."""
    try:
        return load_module("readers", metric, root)
    except FileNotFoundError:
        if "." not in metric:
            raise
        return load_module("readers", metric.rsplit(".", 1)[0], root)


def read_per_layer(cell: Cell, context: dict) -> dict:
    """Run this cell's per-layer readers (`read(context)`). A reader that finds
    nothing returns None and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"], cell.root).read(context)
        if value is not None:
            out[m["name"]] = value
    return out
