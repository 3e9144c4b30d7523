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
    ("bert-tiny.tiny-mrpc", {"train_tokens_per_s", "setup_s"}),
])
def test_a_cell_added_as_new_files_runs_and_is_correct(tiny_root, ledger, workload, metrics):
    _cell, line = _run(tiny_root, ledger, workload, seed=2**31 + 11)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 and m["unit"] for m in line["metrics"].values())


def test_train_step_that_leaves_its_state_unchanged_is_not_correct(tiny_root, ledger):
    cell = harness.Cell("bert-tiny.tiny-mrpc", tiny_root)
    driver = harness.load_module("drivers", "train", tiny_root)
    job = driver.Job(cell, 7)
    real_step, params = job.step_fn, job.pmodel.params

    def frozen(batch):
        # computes its loss, and hands back the state it was given
        import jax

        snapshot = jax.tree_util.tree_map(lambda x: x.copy(), job.pmodel.params)
        opt = jax.tree_util.tree_map(lambda x: x.copy() if hasattr(x, "copy") else x, job.popt.opt_state)
        loss = real_step(batch)
        job.pmodel.params, job.popt.opt_state = snapshot, opt
        return loss

    job.step_fn = frozen
    first = driver.first_steps(job)
    window = driver.measure(job, 0.3, harness.TraceWindow(False, 0, 0), 2)
    import jax
    losses = np.asarray(jax.device_get(window["losses"]), np.float64)
    outcome = driver.check(job, first, losses)
    job.close()
    assert outcome["correct"] is False
    assert outcome["numbers"]["change_norm_gap"] > 0.9  # nothing moved where the reference moved


def test_train_step_that_drops_half_the_batch_is_not_correct(tiny_root, ledger):
    cell = harness.Cell("bert-tiny.tiny-mrpc", tiny_root)
    driver = harness.load_module("drivers", "train", tiny_root)
    job = driver.Job(cell, 8)
    real_step = job.step_fn

    def half(batch):
        import jax.numpy as jnp

        return real_step({k: jnp.concatenate([v[: len(v) // 2]] * 2) for k, v in batch.items()})

    job.step_fn = half
    first = driver.first_steps(job)
    job.close()
    expected = driver.reference_steps(job, first)
    numbers = driver.compare(first, expected)
    limits = cell.spec["correct"]
    assert any(numbers[k] > limits[k + "_limit"] for k in numbers)


@pytest.mark.parametrize("workload,name", [
    ("bert-tiny.tiny-mrpc", "float8_e4m3fn"),
    ("bert-tiny.tiny-mrpc", "program_fp8"),
])
def test_the_control_fails_a_limit_and_the_sound_program_passes(tiny_root, ledger, workload, name):
    """The next precision down, at a size a test run can hold: the program with
    its own lower-precision path on (`mixed_precision="fp8"`), and the
    reference put in the program's place at that precision. Each has to fail
    one number."""
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
