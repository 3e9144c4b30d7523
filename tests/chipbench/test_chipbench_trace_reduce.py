"""`trace_reduce`: on events trimmed from a chip run (`data/`), and on hand-made
events where the answer is known exactly."""

import json
import os

import pytest

from chipbench import harness, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TPU, OPS, MODS = "/device:TPU:0", trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "serve_trace_events.json")) as f:
        return [tuple(e) for e in json.load(f)["events"]]


@pytest.fixture(scope="module")
def reduced(recorded):
    return trace_reduce.reduce_events(recorded, chips=1)


def test_recorded_busy_union_is_below_the_window_and_above_the_modules(reduced):
    modules = sum(m["seconds"] for m in reduced["modules"].values())
    assert reduced["window_s"] == pytest.approx(0.030, abs=1e-6)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # operations only run inside modules; the union of nested ops is never counted twice
    assert reduced["busy_s"] <= modules * 1.001
    assert sum(s for _, s in reduced["device_ops"]) == pytest.approx(reduced["busy_s"], rel=1e-3)


def test_recorded_modules_are_the_engines_two_programs(reduced):
    assert set(reduced["modules"]) == {"jit_insert", "jit_decode_chunk"}
    assert trace_reduce.module_seconds(reduced, "^jit_insert$") == (
        pytest.approx(reduced["modules"]["jit_insert"]["seconds"]), 1)
    assert trace_reduce.module_seconds(reduced, "^no_such$") == (0.0, 0)


def test_recorded_idle_share_and_gap_attribution(reduced):
    idle = reduced["window_s"] - reduced["busy_s"]
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) <= {"bench.step", "bench.submit", "(no bench span)"}
    assert sum(gaps.values()) <= idle + 1e-9
    assert sum(gaps.values()) >= 0.8 * idle  # the rest is launch latency under MIN_GAP_NS
    assert max(gaps, key=gaps.get) == "bench.step"


def test_recorded_operation_names_are_short_and_add_up(reduced):
    names = [name for name, _ in reduced["device_ops"]]
    assert all(len(n) <= 80 and " = " not in n for n in names)
    assert sum(s for _, s in reduced["device_ops"]) == pytest.approx(reduced["busy_s"], rel=1e-3)
    assert reduced["device_ops"] == sorted(reduced["device_ops"], key=lambda kv: -kv[1])


@pytest.mark.parametrize("intervals,merged", [
    ([(0, 10), (5, 12), (20, 30)], [[0, 12], [20, 30]]),
    ([(5, 6), (0, 10)], [[0, 10]]),
    ([], []),
])
def test_union(intervals, merged):
    assert trace_reduce.union(intervals) == merged


@pytest.mark.parametrize("raw,short", [
    ("%fusion.3344 = s32[128]{0:T(128)S(1)} fusion(s32[1,128] %x)", "fusion"),
    ("%while.5 = (s32[], bf16[2817,16,16,128]) while(%tuple)", "while"),
    ("%all-gather-start.1.2 = bf16[8] all-gather-start(%p)", "all-gather-start"),
    ("copy-done", "copy-done"),
])
def test_op_name(raw, short):
    assert trace_reduce.op_name(raw) == short


def _hand_made():
    return [
        ("/host:CPU", "host", "bench.train_call", 0, 100),
        ("/host:CPU", "host", "bench.next_batch", 100, 900),
        (TPU, MODS, "jit_fused(123)", 1000, 1000),
        (TPU, OPS, "%while.1 = while()", 1000, 600),            # self time 600 - 500 = 100
        (TPU, OPS, "%fusion.1 = fusion()", 1000, 300),
        (TPU, OPS, "%all-gather-done.2 = all-gather-done()", 1300, 200),
        (TPU, OPS, "%all-reduce.7 = all-reduce()", 1600, 400),
        ("/device:TPU:1", OPS, "%fusion.1 = fusion()", 1000, 1000),
        ("/device:TPU:1", MODS, "jit_fused(123)", 1000, 1000),
    ]


def test_hand_made_self_times_and_chip_means():
    own = dict((trace_reduce.op_name(n), ns) for n, ns in trace_reduce.self_times(
        [e for e in _hand_made() if e[0] == TPU and e[1] == OPS]))
    assert own == {"while": 100, "fusion": 300, "all-gather-done": 200, "all-reduce": 400}
    two = trace_reduce.reduce_events(_hand_made(), chips=2)
    assert two["chips"] == 2
    assert two["busy_s"] == pytest.approx((1000 + 1000) / 2 / 1e9)
    assert two["modules"]["jit_fused"] == {"seconds": pytest.approx(1e-6), "runs": 1}
    one = trace_reduce.reduce_events(_hand_made(), chips=1)
    assert one["chips"] == 1 and one["busy_s"] == pytest.approx(1000 / 1e9)
    assert dict(one["device_ops"])["all-reduce"] == pytest.approx(400 / 1e9)
    assert dict(two["device_ops"])["fusion"] == pytest.approx((300 + 1000) / 2 / 1e9)  # a chip's mean


def test_hand_made_gap_goes_to_the_span_that_covers_most_of_it():
    events = _hand_made() + [(TPU, OPS, "%x = x()", 100_000, 1000)]
    reduced = trace_reduce.reduce_events(events, chips=1)
    gaps = dict(reduced["idle_gaps"])
    # [2000, 100000) has no bench span at all; the 1000 ns before the first op are under MIN_GAP_NS
    assert gaps == {"(no bench span)": pytest.approx(98_000 / 1e9)}
    long_host = [("/host:CPU", "host", "bench.wait_due", 2000, 90_000)]
    gaps = dict(trace_reduce.reduce_events(events + long_host, chips=1)["idle_gaps"])
    assert gaps == {"bench.wait_due": pytest.approx(98_000 / 1e9)}


def test_a_capture_with_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce_events([("/host:CPU", "host", "bench.step", 0, 10)], chips=1)


@pytest.mark.parametrize("reader,context,expected", [
    # a quantity split by the end-to-end metric it moves keeps one reader, under the quantity's name
    ("device_idle_pct.serve", {"trace": {"busy_s": 3.0, "window_s": 4.0}}, 25.0),
    ("device_idle_pct.open", {"trace": {"busy_s": 1.0, "window_s": 4.0}}, 75.0),
    ("device_idle_pct.train", {"trace": {"busy_s": 4.0, "window_s": 4.0}}, 0.0),
    ("hbm_peak_gb.serve", {"peak_bytes": 11_809_342_464}, 11.809342464),
    ("hbm_peak_gb.train", {"peak_bytes": 5_000_000_000}, 5.0),
    ("recompiles_in_window", {"compiles_in_window": 0}, 0.0),
    ("chunk_wall_ms", {"window": {"t0": 1.0, "steps": [(0.5, 0.9, 0, 0, 0, 0), (1.0, 1.5, 0, 0, 0, 0), (1.5, 1.7, 0, 0, 0, 0), (1.7, 2.0, 0, 0, 0, 0)]}}, 300.0),
    ("slots_busy_pct", {"num_slots": 4, "window": {"t0": 1.0, "steps": [(1.0, 2.0, 4, 0, 0, 0), (2.0, 5.0, 2, 0, 0, 0)]}}, 62.5),
    ("pages_peak_pct", {"pages_total": 200, "window": {"t0": 1.0, "steps": [(1.0, 2.0, 4, 50, 0, 0), (2.0, 5.0, 2, 90, 0, 0)]}}, 45.0),
    ("train_dispatch_ms", {"window": {"dispatch": [0.001, 0.003, 0.002]}}, 2.0),
    ("data_wait_ms", {"window": {"data_wait": [0.004]}}, 4.0),
])
def test_host_side_readers(reader, context, expected):
    assert harness.load_reader(reader).read(context) == pytest.approx(expected)


def test_a_metric_with_no_reader_of_its_own_or_of_its_quantity_is_an_error():
    with pytest.raises(FileNotFoundError):
        harness.load_reader("no_such_quantity.serve")
    with pytest.raises(FileNotFoundError):
        harness.load_reader("no_such_metric")


class _Cell:
    def __init__(self, config, spec):
        self.config, self.spec, self.root = config, spec, harness.ROOT


def test_device_side_readers_on_the_recorded_trace(reduced):
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "chipbench", "configs", "pythia-1.4b.json")) as f:
        config = json.load(f)
    cell = _Cell(config, {"dtype": "bfloat16", "modules": {"decode": "^jit_decode_chunk$", "insert": "^jit_insert$"}})
    context = {"cell": cell, "trace": reduced, "chunk_size": 8, "kv_bytes_per_token": 196_608,
               "peaks": {"hbm_bytes_per_s": 819e9}, "trace_span": (0.0, 10.0),
               "window": {"steps": [(1.0, 2.0, 32, 0, 14_000, 256)]}}
    prefill = harness.load_module("readers", "prefill_device_pct").read(context)
    assert prefill == pytest.approx(reduced["modules"]["jit_insert"]["seconds"] / reduced["busy_s"] * 100)
    roofline = harness.load_module("readers", "decode_roofline_pct").read(context)
    need = (1_414_647_808 - 103_022_592) * 2 + 14_000 * 196_608
    per_step = reduced["modules"]["jit_decode_chunk"]["seconds"] / 8
    assert roofline == pytest.approx(need / 819e9 / per_step * 100)
    # nothing to read: no step inside the traced span, or no such module
    assert harness.load_module("readers", "decode_roofline_pct").read(dict(context, trace_span=(5.0, 6.0))) is None
    cell.spec["modules"]["insert"] = "^absent$"
    assert harness.load_module("readers", "prefill_device_pct").read(context) is None


def test_mfu_reader_on_hand_made_numbers():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "chipbench", "configs", "bert-base.json")) as f:
        config = json.load(f)
    cell = _Cell(config, {"modules": {"step": "^jit_fused$"}})
    trace = {"modules": {"jit_fused": {"seconds": 0.5, "runs": 20}}}
    context = {"cell": cell, "trace": trace, "batch": 32, "seq_len": 128, "chips": 1,
               "peaks": {"bf16_flops_per_s": 197e12}}
    flops = (6 * (109_483_778 - 23_837_184) + 12 * 12 * 768 * 128) * 4096
    assert harness.load_module("readers", "train_step_mfu_pct").read(context) == pytest.approx(
        flops / 0.025 / 197e12 * 100)
    # nothing to read: the step's module is not in the capture
    cell.spec["modules"]["step"] = "^absent$"
    assert harness.load_module("readers", "train_step_mfu_pct").read(context) is None
