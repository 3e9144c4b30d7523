"""`latent_read_roofline_pct` (PR 39): the latent read's own share of its memory
roofline, read from the page-walk kernel's device time in a capture. The
reader's arithmetic by hand on the tiny latent cell of
`test_chipbench_latent_moe.py` (its root and its configuration, as they are),
its silence on a program whose read is the XLA loop, and its entry in
`BENCHMARK.json`. No number here is a speed."""

import json
import os

import pytest
from test_chipbench_latent_moe import CELL, REPO, TINY_CELL, latent_root  # noqa: F401  (the fixture)

from chipbench import harness, shapes_latent_moe

NAME = "latent_read_roofline_pct"
CHUNKS = {"jit_decode_chunk": {"seconds": 0.8, "runs": 10}}


@pytest.mark.parametrize(
    "device_ops,modules,steps_inside,want",
    [
        # the kernel ran 0.004 s under 10 chunks of 4 steps, 120 live tokens a step on average
        ([["paged_attention", 0.004], ["fusion", 0.5]], CHUNKS, True, 0.004),
        ([["gmm", 0.2], ["paged_attention", 0.001]], dict(CHUNKS, jit_insert={"seconds": 0.1, "runs": 5}), True, 0.001),
        # the parent of PR 39, and any engine off the TPU: the XLA read's loop, no such operation
        ([["fusion", 0.5], ["bitcast_add_fusion", 0.1], ["gmm", 0.2]], CHUNKS, True, None),
        ([], CHUNKS, True, None),
        # a capture without a decode chunk, or without a step of the client loop inside it
        ([["paged_attention", 0.004]], {"jit_insert": {"seconds": 0.1, "runs": 5}}, True, None),
        ([["paged_attention", 0.004]], CHUNKS, False, None),
    ],
    ids=["kernel", "kernel_beside_inserts", "xla_read", "empty_capture", "no_chunk", "no_step_in_capture"],
)
def test_the_latent_reads_own_share_is_its_live_rows_over_the_kernels_time(latent_root, device_ops, modules,  # noqa: F811
                                                                            steps_inside, want):
    """By hand: the live tokens' published rows ([c 32 | k_pe 8] float32, 3
    layers) once a decode step of the captured chunks, over the chip's bytes/s
    and the kernel's device time; None where the capture holds no
    `paged_attention` — it never raises on the parent."""
    cell = harness.Cell(TINY_CELL, latent_root)
    peaks = harness.peaks_for("TPU v5 lite")
    now = 100.0
    inside = [(now, now + 0.1, 3, 1, 100, 4), (now + 0.1, now + 0.2, 3, 1, 140, 4)]
    context = {"cell": cell, "window": {"t0": now - 1.0, "t1": now + 1.0, "steps": inside + [(now + 5.0, now + 5.1, 3, 1, 999, 4)]},
               "trace_span": (now - 1.0, now + 1.0) if steps_inside else (now + 2.0, now + 3.0), "chunk_size": 4,
               "peaks": peaks, "trace": {"busy_s": 1.0, "window_s": 2.0, "device_ops": device_ops, "modules": modules}}
    value = harness.load_reader(NAME, latent_root).read(context)
    if want is None:
        assert value is None
        return
    assert shapes_latent_moe.latent_read_bytes(cell.config, "float32", 120) == 120 * 3 * 40 * 4
    assert value == pytest.approx(120 * 3 * 40 * 4 * 40 / peaks["hbm_bytes_per_s"] / want * 100)


def test_a_cell_of_another_family_reads_nothing(tiny_root):
    """A cell that names no `latent_decode` module (every K/V family's) is left out before the capture is looked at."""
    cell = harness.Cell("neox-tiny.tiny-backlog", tiny_root)
    context = {"cell": cell, "trace": {"device_ops": [["paged_attention", 0.004]], "modules": CHUNKS}}
    assert harness.load_reader(NAME, tiny_root).read(context) is None


def test_the_latent_reads_share_is_declared_for_the_latent_cell_alone():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "device_trace",
                     "layer": "attention read and decode matmuls", "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert sum(m["name"] == NAME for m in bench["per_layer"]) == 1
    assert NAME in {m["name"] for m in harness.Cell(CELL).per_layer}
    assert callable(harness.load_reader(NAME).read)
