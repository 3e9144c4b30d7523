"""The contract on bench.py: stdout carries exactly ONE JSON line with
{"metric", "value", "unit", "vs_baseline"}, the measurement runs in-process, a
failure is the script's own non-zero exit, and no second platform is ever tried.
Runs the real script as a subprocess on CPU at smoke sizes."""

import json
import os
import subprocess
import sys

import pytest

from accelerate_tpu.test_utils.testing import cpu_mesh_env, execute_subprocess

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench.py")


def run_bench(*args):
    proc = execute_subprocess([sys.executable, BENCH, *args], env=cpu_mesh_env(num_devices=1), timeout=900)
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"stdout must carry exactly one line, got {lines!r}"
    return json.loads(lines[0])


@pytest.mark.slow_launch
def test_train_bench_contract():
    row = run_bench("--model", "bert-tiny", "--steps", "4", "--trials", "1", "--warmup", "1")
    assert set(row) >= {"metric", "value", "unit", "vs_baseline", "extra"}
    assert isinstance(row["value"], (int, float)) and row["value"] > 0
    assert row["unit"] == "samples/sec/chip"
    # CPU runs must self-tag and zero the baseline ratio (an untagged smoke
    # number masquerading as chip performance was a round-2 verdict item).
    assert row["metric"].startswith("cpu-smoke")
    assert row["vs_baseline"] == 0.0
    assert row["extra"]["device_kind"] == "cpu"
    assert row["extra"]["attention_impl"] in ("xla", "flash", None)
    # Platform-dependent defaults are printed with the result.
    assert row["extra"]["sizes"]["platform_defaults"] == {"batch_size": 4, "steps_per_call": 1}


@pytest.mark.slow_launch
def test_inference_bench_contract():
    row = run_bench("--mode", "inference", "--model", "llama-tiny")
    assert set(row) >= {"metric", "value", "unit", "vs_baseline", "extra"}
    assert isinstance(row["value"], (int, float)) and row["value"] > 0
    assert row["unit"] == "ms/token"
    assert row["metric"].startswith("cpu-smoke")
    assert row["vs_baseline"] == 0.0
    assert row["extra"]["ttft_p50_ms"] > 0


def test_failure_propagates_and_no_second_platform_is_tried():
    """A backend that cannot start is the script's own non-zero exit: no retry,
    no re-execution under JAX_PLATFORMS=cpu, no CPU number on stdout."""
    env = cpu_mesh_env(num_devices=1)
    env["JAX_PLATFORMS"] = "no_such_backend"
    proc = subprocess.run(
        [sys.executable, BENCH, "--model", "bert-tiny", "--steps", "1", "--trials", "1"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", f"a failed run must print no result: {proc.stdout!r}"
    assert "no_such_backend" in proc.stderr
    assert "cpu-smoke" not in proc.stderr and "fallback" not in proc.stderr.lower()


def test_bench_has_no_supervisor_left():
    """The supervisor, its probe memo and its flags are gone: bench.py spawns
    nothing and parses no flag that selected the old wrapper."""
    with open(BENCH) as f:
        source = f.read()
    for gone in ("subprocess", "supervise", "_worker", "BENCH_DEADLINE_S", "preflight"):
        assert gone not in source, gone
