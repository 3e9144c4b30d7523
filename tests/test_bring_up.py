"""Bring-up contracts that keep the device from being hidden: where the compile
cache lives, what an unknown chip does to the peak table and the planner, and
which processes may touch the chip."""

import os
import subprocess
import sys

import pytest

import jax

from accelerate_tpu.utils.environment import (
    COMPILE_CACHE_ENV,
    DEFAULT_COMPILE_CACHE_DIR,
    configure_compile_cache,
    get_device_peak_flops,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- compile cache
@pytest.fixture
def cache_config(monkeypatch):
    """Record `jax.config.update` calls instead of applying them (the suite's own
    cache directory must survive the test)."""
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_env_set_means_nothing_is_set_in_code(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv(COMPILE_CACHE_ENV, str(tmp_path / "from-env"))
    assert configure_compile_cache() == str(tmp_path / "from-env")
    # Not even an explicit cache_dir (or a CompilationConfig default) overrides it.
    assert configure_compile_cache(str(tmp_path / "explicit")) == str(tmp_path / "from-env")
    assert cache_config == []


def test_compile_cache_unset_is_the_fixed_in_checkout_path(monkeypatch, cache_config):
    monkeypatch.delenv(COMPILE_CACHE_ENV, raising=False)
    assert configure_compile_cache() == DEFAULT_COMPILE_CACHE_DIR
    assert cache_config == [("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)]
    assert os.path.dirname(DEFAULT_COMPILE_CACHE_DIR) == ROOT
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert os.path.basename(DEFAULT_COMPILE_CACHE_DIR) + "/" in f.read().split()


def test_compile_cache_explicit_dir_is_the_users_choice_when_env_unset(
    monkeypatch, cache_config, tmp_path
):
    from accelerate_tpu import Accelerator
    from accelerate_tpu.utils import CompilationConfig

    monkeypatch.delenv(COMPILE_CACHE_ENV, raising=False)
    Accelerator(compilation_config=CompilationConfig(cache_dir=str(tmp_path / "mine")))
    assert ("jax_compilation_cache_dir", str(tmp_path / "mine")) in cache_config
    del cache_config[:]
    monkeypatch.setenv(COMPILE_CACHE_ENV, str(tmp_path / "from-env"))
    from accelerate_tpu.state import AcceleratorState

    AcceleratorState._reset_state()
    Accelerator(compilation_config=CompilationConfig(cache_dir=str(tmp_path / "mine")))
    assert not [c for c in cache_config if c[0] == "jax_compilation_cache_dir"]


def test_no_other_code_sets_the_cache_dir():
    """One resolver: nothing else under the package, the benchmarks or the
    entry scripts names the config option."""
    offenders = []
    for top in ("accelerate_tpu", "benchmarks", "bench.py", "chip_smoke.py", "tests/conftest.py"):
        path = os.path.join(ROOT, top)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs if f.endswith(".py")
        ]
        for file in files:
            with open(file) as f:
                if "jax_compilation_cache_dir" in f.read():
                    offenders.append(os.path.relpath(file, ROOT))
    assert offenders == ["accelerate_tpu/utils/environment.py"]


# ------------------------------------------------- unknown chips are errors
def test_peak_table_knows_the_v5e_and_raises_on_an_unknown_kind():
    assert get_device_peak_flops("TPU v5 lite") == 197e12  # what a v5e chip reports
    with pytest.raises(ValueError, match="no peak FLOP/s for device_kind 'TPU v99'"):
        get_device_peak_flops("TPU v99")
    with pytest.raises(ValueError):
        get_device_peak_flops("cpu")


def test_planner_prices_the_chip_by_device_kind(monkeypatch):
    from accelerate_tpu.parallel import planner

    assert planner.chip_for_device_kind("TPU v5 lite") is planner.CHIPS["tpu-v5e"]
    assert planner.chip_for_device_kind("TPU v5 lite").hbm_bytes == 16e9
    assert planner.default_chip() is planner.CHIPS["cpu-smoke"]  # this suite runs on cpu
    with pytest.raises(ValueError, match="no ChipSpec for device_kind 'TPU v99'"):
        planner.chip_for_device_kind("TPU v99")

    class _Device:
        device_kind = "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Device()])
    with pytest.raises(ValueError, match="TPU v99"):
        planner.default_chip()


# --------------------------------------------------- one process per chip
def test_spawning_a_worker_beside_a_held_tpu_fails_at_once(monkeypatch):
    """`serve --out-of-process` builds the model (device arrays) in the parent:
    on a TPU the parent then holds the chip and a spawned worker could only
    fail or hang. The spawn is refused, with the reason, before any process starts."""
    from accelerate_tpu import worker

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(subprocess, "Popen", lambda *a, **k: pytest.fail("a worker was spawned"))
    with pytest.raises(RuntimeError, match="already holds the TPU"):
        worker.SubprocessEngine({"name": "llama-tiny"}, {}, worker_id=3)


def test_launch_and_supervisor_parents_stay_off_the_jax_backend(tmp_path):
    """`accelerate-tpu launch` (plain and under `Supervisor`) is the PARENT of the
    process that owns the chip: importing the CLI imports jax, but it must never
    initialise a backend — that would take the chip from its own child."""
    child = tmp_path / "child.py"
    child.write_text("print('child ran')\n")
    probe = (
        "import sys\n"
        "from jax._src import xla_bridge\n"
        "from accelerate_tpu.commands.accelerate_cli import main\n"
        f"for argv in (['launch', {str(child)!r}], ['launch', '--max_restarts', '1', {str(child)!r}]):\n"
        "    sys.argv = ['accelerate-tpu'] + argv\n"
        "    main()\n"
        "    assert not xla_bridge.backends_are_initialized(), argv\n"
        "print('parent stayed off jax')\n"
    )
    env = dict(os.environ, PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", probe], env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.count("child ran") == 2
    assert "parent stayed off jax" in proc.stdout


def test_package_import_sets_no_platform():
    """The package no longer re-pins `jax_platforms` at import: plain jax honours
    JAX_PLATFORMS by itself."""
    with open(os.path.join(ROOT, "accelerate_tpu", "__init__.py")) as f:
        assert "jax_platforms" not in f.read()
