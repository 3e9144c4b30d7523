"""Test harness: force an 8-device host-CPU platform (the debug_launcher equivalent —
SURVEY §4 implication (b)) and reset the Borg singletons around every test (parity:
reference test_utils/testing.py:427-438 AccelerateTestCase)."""

import os

# Must run before jax initializes its backends.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("ACCELERATE_TPU_TESTING", "1")

import jax  # noqa: E402

# Persistent compilation cache, so repeated suite runs skip recompiles: placed by
# the package's one resolver (JAX_COMPILATION_CACHE_DIR, else the in-checkout dir).
from accelerate_tpu.utils.environment import configure_compile_cache  # noqa: E402

configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import pytest  # noqa: E402


# Marker REGISTRATION lives in pytest.ini (the single registry, honored even for
# files collected without this conftest); this hook only wires the implications.
def pytest_collection_modifyitems(config, items):
    # slow_launch / serving_soak imply slow: `-m "not slow"` is THE fast-tier switch.
    for item in items:
        if (
            item.get_closest_marker("slow_launch") or item.get_closest_marker("serving_soak")
        ) and not item.get_closest_marker("slow"):
            item.add_marker(pytest.mark.slow)


# The analysis trace-guard fixture ships in test_utils (post-install parity);
# re-exporting it here makes `trace_guard` available to every test in tests/.
from accelerate_tpu.test_utils.analysis_fixtures import trace_guard  # noqa: E402, F401


@pytest.fixture(autouse=True)
def reset_singletons():
    yield
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
