"""Environment parsing and hardware probing.

Parity: reference utils/environment.py (str_to_bool :58, get_int_from_env :73,
parse_flag_from_env :82, GPU probing :100-143, NUMA affinity :220-296). The hardware
probes here are TPU-shaped: ICI mesh topology from the JAX device list instead of
nvidia-smi, and host memory from /proc instead of pynvml.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from dataclasses import dataclass


def str_to_bool(value: str) -> int:
    """Convert a string (env-var) truth value to 1/0. Raises on unrecognized values."""
    value = value.lower()
    if value in ("y", "yes", "t", "true", "on", "1"):
        return 1
    if value in ("n", "no", "f", "false", "off", "0"):
        return 0
    raise ValueError(f"invalid truth value {value}")


def get_int_from_env(env_keys, default):
    """Return the first positive int found under any of `env_keys`."""
    for e in env_keys:
        val = int(os.environ.get(e, -1))
        if val >= 0:
            return val
    return default


def parse_flag_from_env(key: str, default: bool = False) -> bool:
    value = os.environ.get(key, str(default))
    return bool(str_to_bool(value))


def parse_choice_from_env(key: str, default: str = "no") -> str:
    return os.environ.get(key, str(default))


def get_host_memory_bytes() -> int:
    """Total host RAM in bytes (used by the big-model device-map planner)."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0


def get_available_host_memory_bytes() -> int:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return get_host_memory_bytes() // 2


_FENCE_ON_CPU: bool | None = None


def fence_if_cpu(tree) -> None:
    """Host-sync `tree` when running on the XLA:CPU backend (the virtual-mesh
    dev/test surface); no-op on TPU/GPU.

    XLA:CPU deadlocks under async dispatch of partitioned programs: with K
    optimizer steps in flight, partitions of DIFFERENT steps hold the client's
    worker threads waiting on DIFFERENT channel-collective rendezvous, and on
    a small host the next step's partitions can starve the previous step's
    last participant forever (observed: 3/4 partitions joined, termination at
    the full rendezvous deadline on an idle box). One host sync per step caps
    in-flight programs at one step. Real TPU/GPU runtimes schedule per-device
    queues and need (and get) no such fence."""
    global _FENCE_ON_CPU
    if _FENCE_ON_CPU is None:
        import jax

        _FENCE_ON_CPU = jax.devices()[0].platform == "cpu"
    if _FENCE_ON_CPU:
        import jax

        jax.block_until_ready(tree)


@dataclass
class TpuTopology:
    """ICI topology discovered from the JAX device list (replaces nvidia-smi probing,
    reference utils/environment.py:100-143)."""

    num_devices: int
    num_hosts: int
    local_device_count: int
    device_kind: str
    coords: list | None = None

    @property
    def devices_per_host(self) -> int:
        return self.local_device_count


def get_tpu_topology() -> TpuTopology:
    import jax

    devices = jax.devices()
    coords = [getattr(d, "coords", None) for d in devices]
    return TpuTopology(
        num_devices=len(devices),
        num_hosts=jax.process_count(),
        local_device_count=jax.local_device_count(),
        device_kind=devices[0].device_kind if devices else "cpu",
        coords=coords if all(c is not None for c in coords) else None,
    )


# Peak dense-bf16 FLOP/s per chip, by device kind, for MFU accounting. Public numbers from
# cloud.google.com/tpu/docs (v4: 275e12, v5e: 197e12, v5p: 459e12, v6e "Trillium": 918e12).
DEVICE_PEAK_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v4 lite": 275e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v6e": 918e12,
    "TPU v6 lite": 918e12,
    "TPU7x": 2307e12,
}


def get_device_peak_flops(device_kind: str, dtype: str = "bf16") -> float:
    """Peak FLOP/s for a device kind. An unknown kind raises: a silent 0.0
    used to make MFU quietly go unreported on the measurement path.

    Longest name first, so "TPU v5 lite" matches its own entry rather than "TPU v5".
    """
    kind = device_kind.lower()
    for k in sorted(DEVICE_PEAK_FLOPS, key=len, reverse=True):
        if kind.startswith(k.lower()) or k.lower() in kind:
            return DEVICE_PEAK_FLOPS[k]
    raise ValueError(
        f"no peak FLOP/s for device_kind {device_kind!r} (known: "
        f"{sorted(DEVICE_PEAK_FLOPS)}); add it to DEVICE_PEAK_FLOPS with its source"
    )


#: JAX's own variable for the persistent compile cache. When it is set, JAX reads
#: it and this package sets nothing: whoever runs the program places the cache.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: The one fixed cache directory otherwise, inside the checkout (`.gitignore`
#: lists it). The directory is part of the cache key's lookup, so it is never a
#: temp name, a pid or a timestamp.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def configure_compile_cache(cache_dir: str | None = None) -> str:
    """Resolve the persistent compile cache directory — the ONE place that
    decides it (chip_smoke.py, bench.py, the benchmarks, the worker entry,
    `Accelerator(compilation_config=...)` and tests/conftest.py all call this).

    `JAX_COMPILATION_CACHE_DIR` set: nothing is set in code, and neither
    `cache_dir` nor any default overrides it. Unset: `cache_dir` when the
    caller passed one, else `DEFAULT_COMPILE_CACHE_DIR`. Returns the directory
    in use."""
    from_env = os.environ.get(COMPILE_CACHE_ENV)
    if from_env:
        return from_env
    import jax

    path = cache_dir or DEFAULT_COMPILE_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def set_host_device_count_flag(flags: str, num_devices: int, override: bool = True) -> str:
    """Return XLA_FLAGS with `--xla_force_host_platform_device_count=N` set.
    `override=False` keeps an existing count (explicit-beats-inherited contract
    shared by the launch CLI and the test harness)."""
    import re

    if "--xla_force_host_platform_device_count" not in flags:
        return (flags + f" --xla_force_host_platform_device_count={num_devices}").strip()
    if not override:
        return flags
    return re.sub(
        r"--xla_force_host_platform_device_count=\d+",
        f"--xla_force_host_platform_device_count={num_devices}",
        flags,
    )


@contextmanager
def clear_environment():
    """Temporarily empty os.environ (parity: reference utils/other.py:211)."""
    _old = os.environ.copy()
    os.environ.clear()
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(_old)


@contextmanager
def patch_environment(**kwargs):
    """Temporarily set env vars (upper-cased keys); restores previous values on exit
    (parity: reference utils/other.py:246)."""
    existing = {}
    for key, value in kwargs.items():
        key = key.upper()
        if key in os.environ:
            existing[key] = os.environ[key]
        os.environ[key] = str(value)
    try:
        yield
    finally:
        for key in kwargs:
            key = key.upper()
            if key in existing:
                os.environ[key] = existing[key]
            else:
                os.environ.pop(key, None)
