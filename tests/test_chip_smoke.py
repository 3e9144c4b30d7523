"""chip_smoke.py off the chip: its contract when there is no TPU, and its phase
functions rehearsed at tiny sizes on the virtual CPU mesh (the
`on-chip-measurement` guide's rehearsals 1 and 2). The script has no CPU option:
these tests import the phase functions and hand them `TINY`."""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import pytest

import jax

from accelerate_tpu.test_utils.testing import cpu_mesh_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke_under_test", SMOKE)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def ledger(smoke):
    return smoke.CompileLedger()


def _phase_lines(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]


def test_plain_cpu_run_fails_with_ok_false():
    """Run as the driver runs it, but where JAX finds no accelerator: non-zero
    exit, `"ok": false`, and no result line."""
    proc = subprocess.run(
        [sys.executable, SMOKE], env=cpu_mesh_env(num_devices=1),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    lines = [json.loads(line) for line in proc.stdout.splitlines() if line.strip()]
    assert lines, proc.stderr
    assert lines[-1]["ok"] is False
    assert lines[-1]["platform"] == "cpu"
    assert not any(line.get("ok") is True for line in lines)


def test_serve_phases_tiny(smoke, ledger, capsys):
    """serve + serve-kernel at llama-tiny: the engine against the static
    Generator, the Pallas engines (interpreter here) against their XLA oracles."""
    ctx = {}
    smoke.phase_serve(ledger, smoke.TINY, 0, ctx)
    smoke.phase_serve_kernel(ledger, smoke.TINY, ctx)
    serve, kernel = _phase_lines(capsys)
    assert serve["phase"] == "serve" and serve["ok"]
    assert serve["recompiles_after_warmup"] == 0
    assert serve["host_transfers_after_warmup"] == 0
    assert serve["vs_static_generator"]["token_agreement"] == 1.0
    assert kernel["phase"] == "serve-kernel" and kernel["ok"]
    for pool in ("bf16", "int8"):
        assert kernel[pool]["last_dispatch"] == "pallas_paged"
        assert kernel[pool]["tpu_custom_call"] is False  # interpret mode off the chip
        assert kernel[pool]["token_agreement"] == 1.0


def test_compare_tokens_rejects_a_divergence_the_logits_do_not_excuse(smoke):
    """A flipped token passes only when the reference logit gap is under the
    tolerance: the parity check must still catch a wrong kernel."""
    import numpy as np

    from accelerate_tpu.serving import Request

    request = Request(0, np.arange(4, dtype=np.int32), max_new_tokens=3)
    ref = np.zeros((3, 8), np.float32)
    ref[:, 1] = 2.0  # the reference's greedy token everywhere
    ref[1, 2] = 1.9  # a near-tie at step 1
    oracle = {0: np.array([1, 1, 1], np.int32)}
    near_tie = {0: np.array([1, 2, 1], np.int32)}
    report = smoke.compare_tokens("t", [request], near_tie, oracle, {0: ref}, tol=0.25)
    assert report["requests_identical"] == "0/1"
    assert report["max_gap_at_divergence"] == pytest.approx(0.1)
    wrong = {0: np.array([1, 5, 1], np.int32)}
    with pytest.raises(AssertionError, match="below the float32 reference"):
        smoke.compare_tokens("t", [request], wrong, oracle, {0: ref}, tol=0.25)


def test_train_phases_tiny(smoke, ledger, capsys):
    """train (fused then eager) at bert-tiny, train-flash's path at llama-tiny
    (the XLA branch here: flash is the TPU-only dispatch)."""
    smoke.phase_train(ledger, smoke.TINY, 0)
    smoke.release_device_memory()
    smoke.phase_train_flash(ledger, smoke.TINY, 0)
    train, flash = _phase_lines(capsys)
    assert train["phase"] == "train" and train["ok"]
    assert train["native_data_plane"] in ("c++", "numpy fallback")
    first, last = train["fused_loss_first_last_epoch"]
    assert last < first
    assert flash["phase"] == "train-flash" and flash["ok"]
    assert flash["last_dispatch"] == "xla"


@pytest.mark.skipif(jax.device_count() < 4, reason="needs 4 virtual devices")
def test_multichip_phase_on_four_virtual_devices(smoke, ledger, capsys):
    """`--chips 4`'s phases on four virtual CPU devices: tp=4 == tp=1 with ~1/4
    per chip, DP/ZeRO losses == one device, one replica per device."""
    sizes = dataclasses.replace(smoke.TINY, serve_model="gpt-neox-tiny")  # 4 KV heads
    smoke.phase_multichip(ledger, sizes, 0, 4)
    tp, dp, replicas = _phase_lines(capsys)
    assert tp["phase"] == "tp-serve" and tp["vs_tp1"]["token_agreement"] == 1.0
    assert max(tp["per_chip_weight_bytes"]) < tp["total_weight_bytes"] / 3
    assert dp["phase"] == "dp-zero-train" and dp["max_loss_drift"] < 5e-3
    assert any("data" in spec for spec in dp["optimizer_moment_bytes_by_spec"])
    homes = {tuple(p["params"]) for p in replicas["placement"].values()}
    assert len(homes) == 4
