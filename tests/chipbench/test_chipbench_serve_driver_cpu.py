"""Each driver rehearsed end to end on CPU at tiny sizes, by calling the
functions `run.py` calls with the device check skipped (the command itself has
no CPU mode). The cells driven here exist only as NEW files in a copy of the
benchmark (see conftest.py): that they run is the proof that a configuration, a
cell and a per-layer metric can be added without editing a file that is there.

No number here is a speed: the rates printed on CPU are never compared."""

import argparse
import time

import numpy as np
import pytest

from chipbench import control, harness

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _run(root, ledger, workload, seed, seconds=1.5):
    cell = harness.Cell(workload, root)
    driver = harness.load_module("drivers", cell.spec["driver"], root)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    return cell, driver.run(cell, args, CPU, ledger, time.perf_counter())


@pytest.mark.parametrize("workload,metrics", [
    ("neox-tiny.tiny-backlog", {"serve_tokens_per_s", "setup_s"}),
    ("neox-tiny.tiny-open", {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}),
])
def test_a_cell_added_as_new_files_runs_and_is_correct(tiny_root, ledger, workload, metrics):
    _cell, line = _run(tiny_root, ledger, workload, seed=2**31 + 11)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 and m["unit"] for m in line["metrics"].values())


def test_a_metric_added_as_a_new_reader_is_read(tiny_root):
    cell = harness.Cell("neox-tiny.tiny-backlog", tiny_root)
    assert "steps_in_window" in {m["name"] for m in cell.per_layer}
    step = (1.0, 2.0, 4, 0, 10, 8)
    context = {"window": {"steps": [step, step, step], "t0": 0.0}, "num_slots": 4, "pages_total": 0,
               "compiles_in_window": 0, "peak_bytes": 1e9, "trace": {"busy_s": 1.0, "window_s": 2.0,
               "modules": {}}, "cell": cell, "trace_span": (0, 0), "chunk_size": 4}
    values = harness.read_per_layer(cell, context)
    assert values["steps_in_window"] == 3.0
    assert "decode_roofline_pct" not in values  # its reader found nothing to read: left out
    line = harness.result_line(cell, True, CPU, True, 1, 0, values,
                               {"busy_s": 1.0, "window_s": 2.0, "device_ops": [], "idle_gaps": []}, 10)
    assert "steps_in_window" in line["metrics"] and "serve_tokens_per_s" not in line["metrics"]
    assert line["device"]["busy_s"] == 1.0 and set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_serve_with_an_altered_token_is_not_correct(tiny_root, ledger, monkeypatch):
    """The timed path broken underneath: one served token altered where the
    engine hands its tokens out."""
    from accelerate_tpu.serving import ContinuousBatcher

    real_step = ContinuousBatcher.step

    def step(self):
        events = real_step(self)
        return [(rid, [(t + 1) % 8000 + 1 if j == 2 else t for j, t in enumerate(tokens)])
                for rid, tokens in events]

    monkeypatch.setattr(ContinuousBatcher, "step", step)
    _cell, line = _run(tiny_root, ledger, "neox-tiny.tiny-backlog", seed=5)
    assert line["correct"] is False


def test_serve_that_cuts_requests_short_is_not_correct(tiny_root, ledger, monkeypatch):
    from accelerate_tpu.serving import Request

    real_init = Request.__init__

    def init(self, *args, **kwargs):
        real_init(self, *args, **kwargs)
        if self.max_new_tokens > 9:
            self.max_new_tokens -= 1

    monkeypatch.setattr(Request, "__init__", init)
    _cell, line = _run(tiny_root, ledger, "neox-tiny.tiny-backlog", seed=6)
    assert line["correct"] is False


@pytest.mark.parametrize("workload,name", [
    ("neox-tiny.tiny-backlog", "weights_int8"),
])
def test_the_control_fails_a_limit_and_the_sound_program_passes(tiny_root, ledger, workload, name):
    """The next precision down, at a size a test run can hold: the program with
    the engine's own int8 weights on. It has to fail one number."""
    cell = harness.Cell(workload, tiny_root)
    driver = harness.load_module("drivers", cell.spec["driver"], tiny_root)
    rows = control.readings(cell, driver, [3, 4], 1.5, [None, name], ledger)
    limits = {k[: -len("_limit")]: v for k, v in cell.spec["correct"].items() if k.endswith("_limit")}
    for row in rows:
        over = [k for k in limits if k in row and row[k] > limits[k]]
        if row["control"] is None:
            assert not over, f"sound run over its limits: {row}"
        else:
            assert over, f"control {name} passed every limit: {row}"
