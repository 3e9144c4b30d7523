"""Testing harness (parity: reference test_utils/testing.py).

The two pillars: (1) singleton hygiene — `AccelerateTestCase` resets the Borg state
between tests (reference testing.py:427-438); (2) capability-gated skips —
`require_multi_device` etc. let one suite run on 1-chip CI, the 8-device virtual CPU
mesh, or a pod (reference testing.py:239-301).
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import unittest
from pathlib import Path


def device_count() -> int:
    import jax

    return jax.device_count()


def skip(reason: str):
    return unittest.skip(reason)


def require_single_device(test_case):
    import jax

    return unittest.skipUnless(jax.device_count() == 1, "test requires exactly one device")(test_case)


def require_multi_device(test_case):
    import jax

    return unittest.skipUnless(jax.device_count() > 1, "test requires multiple devices")(test_case)


def require_tpu(test_case):
    import jax

    return unittest.skipUnless(jax.default_backend() == "tpu", "test requires a TPU")(test_case)


def require_multi_process(test_case):
    import jax

    return unittest.skipUnless(jax.process_count() > 1, "test requires multiple host processes")(
        test_case
    )


class AccelerateTestCase(unittest.TestCase):
    """Resets the state singletons in tearDown so tests can't leak topology/precision
    config into each other (reference testing.py:427-438)."""

    def tearDown(self):
        super().tearDown()
        from ..state import AcceleratorState, GradientState, PartialState

        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()


class TempDirTestCase(AccelerateTestCase):
    """Provides `self.tmpdir`, cleared per test (reference testing.py:394-424)."""

    clear_on_setup = True

    @classmethod
    def setUpClass(cls):
        super().setUpClass()
        cls._tmpdir_obj = tempfile.TemporaryDirectory()
        cls.tmpdir = Path(cls._tmpdir_obj.name)

    @classmethod
    def tearDownClass(cls):
        super().tearDownClass()
        cls._tmpdir_obj.cleanup()

    def setUp(self):
        super().setUp()
        if self.clear_on_setup:
            for path in sorted(self.tmpdir.glob("**/*"), reverse=True):
                if path.is_file():
                    path.unlink()
                elif path.is_dir() and not any(path.iterdir()):
                    path.rmdir()


def execute_subprocess(cmd, env=None, timeout: int = 600) -> subprocess.CompletedProcess:
    """Run a launched test script, raising with captured output on failure (reference
    execute_subprocess_async testing.py:501-560)."""
    result = subprocess.run(
        cmd,
        env=env if env is not None else os.environ.copy(),
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if result.returncode != 0:
        raise RuntimeError(
            f"Command {cmd} failed (exit {result.returncode})\n"
            f"--- stdout ---\n{result.stdout}\n--- stderr ---\n{result.stderr}"
        )
    return result


_COLLECTIVE_TIMEOUT_FLAG = "--xla_cpu_collective_call_terminate_timeout_seconds=600"


def cpu_mesh_env(num_devices: int = 8) -> dict:
    """Env for a child process running on the N-device virtual CPU mesh (the
    debug_launcher-adjacent single-process harness)."""
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    # The caller's num_devices must WIN over an inherited device-count flag
    # (pytest's conftest bakes 8 into XLA_FLAGS; a 4-device request would
    # otherwise be silently ignored).
    from ..utils.environment import set_host_device_count_flag

    env["XLA_FLAGS"] = set_host_device_count_flag(env.get("XLA_FLAGS", ""), num_devices)
    # De-flake, not mask: all virtual devices share one intra-op thread pool, so
    # on a loaded small host a collective can take minutes to assemble its
    # participants — that's starvation, not a hang (XLA:CPU's default ~40s
    # rendezvous deadline calls it a hang and kills the child). Real hangs still
    # die at the harness subprocess timeout. NOTE: a longer deadline cannot fix
    # the second flake mechanism — the async-dispatch deadlock, where partitions
    # of DIFFERENT in-flight steps hold the pool's threads waiting on different
    # rendezvous; FusedTrainStep closes that one by fencing per call on the CPU
    # platform. Shrinking the thread pool likewise DEADLOCKS the first
    # cross-module collective (participants must run concurrently).
    if "collective_call_terminate_timeout" not in env["XLA_FLAGS"]:
        env["XLA_FLAGS"] += f" {_COLLECTIVE_TIMEOUT_FLAG}"
    # Children must resolve the package even when it's driven from a source checkout.
    pkg_parent = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = pkg_parent + os.pathsep + env.get("PYTHONPATH", "")
    return env


def run_test_script(script_name: str, num_devices: int = 8, extra_args=()) -> subprocess.CompletedProcess:
    """Run one of the bundled `test_utils/scripts/` by name on the virtual CPU mesh."""
    from . import scripts

    script = os.path.join(os.path.dirname(scripts.__file__), script_name)
    return execute_subprocess([sys.executable, script, *extra_args], env=cpu_mesh_env(num_devices))
