"""The hybrid linear-attention configuration (`olmo-hybrid-7b`): its reference
against hand counts and against the program's model, its count functions, its
cell driven end to end on CPU at a tiny size through `drivers/serve.py`, and
its readers on the counters that run leaves. The tiny cell exists only as NEW
files in a copy of the benchmark (conftest.py's root plus this file's own). No
number here is a speed."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, shapes_hybrid

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "olmo-hybrid-7b.chat-saturated"
TINY_CELL = "hybrid-tiny.tiny-backlog"
NEW_METRICS = ("hybrid_decode_roofline_pct", "delta_step_roofline_pct", "state_and_kv_held_vs_full_pct")
PERIOD = ["linear_attention"] * 3 + ["full_attention"]


def _config():
    return harness.load_json(os.path.join(REPO, "chipbench", "configs", "olmo-hybrid-7b.json"))


def _tiny(**over):
    """Two periods of the layer pattern, small heads."""
    return dict(_config(), source="test", vocab_size=2048, hidden_size=128, intermediate_size=256,
                num_hidden_layers=8, layer_types=PERIOD * 2, num_attention_heads=4, num_key_value_heads=4,
                linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=16,
                linear_value_head_dim=32, max_position_embeddings=256, **over)


@pytest.fixture(scope="module")
def hybrid_root(tmp_path_factory):
    """conftest.py's throw-away root, plus this family's tiny configuration and
    cell as new files and new entries."""
    from conftest import SERVE_SPEC, build_tiny_root

    root = build_tiny_root(str(tmp_path_factory.mktemp("chipbench_hybrid_root")))
    for relative, payload in (
        ("chipbench/configs/hybrid-tiny.json", _tiny()),
        ("chipbench/workloads/" + TINY_CELL + ".json",
         dict(SERVE_SPEC, engine={"num_slots": 4, "max_length": 144, "chunk_size": 4},
              modules={"insert": "^jit_insert$", "hybrid_decode": "^jit_decode_chunk$"},
              correct={"sample": 48, "mean_gap_limit": 2e-5, "max_gap_limit": 5e-4})),
    ):
        path = os.path.join(root, relative)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(payload, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "hybrid-tiny", "source": "test", "file": "chipbench/configs/hybrid-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "hybrid-tiny", "traffic": "tiny-backlog",
                               "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] == "serve_tokens_per_s" or metric["name"] in NEW_METRICS:
            metric["workloads"] = metric["workloads"] + [TINY_CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


# ------------------------------------------------------------------ hand counts
def test_parameter_counts_match_hand_counts():
    reference = harness.load_module("reference", "olmo_hybrid")
    counts = reference.param_counts(_config())
    # W_q, W_k 3840 x 2880; W_v, W_g, W_o 3840 x 5760; W_a, W_b 3840 x 30; 4 taps x 11,520 channels;
    # A_log, dt_bias 30 each; one output-norm scale of 192
    assert counts["linear_mixer"] == (2 * 11_059_200 + 3 * 22_118_400 + 2 * 115_200 + 46_080 + 60 + 192
                                      ) == 88_750_332  # 88.75 M
    assert counts["mlp"] == 3 * 3840 * 11008 == 126_812_160  # 126.81 M
    assert counts["linear_layer"] == 88_750_332 + 126_812_160 + 2 * 3840 == 215_570_172  # 215.57 M
    assert counts["full_mixer"] == 4 * 3840 * 3840 + 2 * 3840 == 58_990_080  # 58.99 M
    assert counts["full_layer"] == 58_990_080 + 126_812_160 + 7_680 == 185_809_920  # 185.81 M
    assert counts["embedding"] == counts["head"] == 100352 * 3840 == 385_351_680  # 385.35 M
    assert (counts["linear_layers"], counts["full_layers"]) == (12, 4)
    # the cut: 12 linear and 4 full layers, final norm, embedding and head
    assert counts["total"] == 12 * 215_570_172 + 4 * 185_809_920 + 3840 + 2 * 385_351_680 == 4_100_788_944
    whole = reference.param_counts(dict(_config(), num_hidden_layers=32, layer_types=PERIOD * 8))
    assert whole["total"] == 24 * 215_570_172 + 8 * 185_809_920 + 3840 + 770_703_360 == 7_430_870_688
    assert round(whole["total"] / 1e9, 2) == 7.43  # as published: 14.9 GB in bfloat16
    assert round(whole["layers"] / 32 / 1e6, 1) == 208.1  # the catalog's "about 208M a layer"


def test_count_functions_match_hand_counts():
    cfg = _config()
    counts = harness.load_module("reference", "olmo_hybrid").param_counts(cfg)
    # S: 30 heads x 96 x 192 float32 = 2,211,840 B a layer, 12 layers: 26.5 MB a slot
    assert shapes_hybrid.recurrent_state_bytes_per_slot(cfg) == 12 * 30 * 96 * 192 * 4 == 26_542_080
    # the convolution's last 3 inputs of 11,520 channels, bfloat16: 69,120 B a layer
    assert shapes_hybrid.conv_state_bytes_per_slot(cfg, "bfloat16") == 12 * 3 * 11_520 * 2 == 829_440
    assert shapes_hybrid.state_bytes_per_slot(cfg, "bfloat16") == 27_371_520  # 27.4 MB whatever the length
    # pages: 4 full layers x K and V x 30 heads x 128, bfloat16
    assert shapes_hybrid.kv_bytes_per_token(cfg, "bfloat16") == 4 * 2 * 30 * 128 * 2 == 61_440
    assert shapes_hybrid.full_attention_kv_bytes_per_token(cfg, "bfloat16") == 16 * 2 * 3840 * 2 == 245_760
    # a step's weights: all but the embedding table, 3,715.4 M parameters = 7.43 GB
    weights = shapes_hybrid.decode_step_weight_bytes(counts, "bfloat16")
    assert weights == (4_100_788_944 - 385_351_680) * 2 == 7_430_874_528
    assert shapes_hybrid.delta_step_bytes(cfg, 48) == 2 * 48 * 26_542_080 == 2_548_039_680  # 2.55 GB a step
    # 48 slots x ~345 live tokens: 11.0 GB a step, 13.4 ms at 819 GB/s
    step = shapes_hybrid.decode_step_bytes(cfg, counts, "bfloat16", 48, 48 * 345)
    assert step == weights + 2_548_039_680 + 48 * 829_440 + 48 * 345 * 61_440 == 11_036_173_728
    assert round(step / 819e9 * 1e3, 1) == 13.5


def test_the_configuration_file_is_the_catalogs_but_for_the_depth():
    cfg = _config()
    published = {"model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
                 "num_attention_heads": 30, "num_key_value_heads": 30, "hidden_act": "silu",
                 "max_position_embeddings": 65536, "attention_bias": False, "rms_norm_eps": 1e-06,
                 "tie_word_embeddings": False, "linear_num_key_heads": 30, "linear_num_value_heads": 30,
                 "linear_key_head_dim": 96, "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
                 "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 16 and cfg["layer_types"] == PERIOD * 4
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["family"] == "olmo_hybrid" and "2-stage pipeline" in cfg["deployment"]
    for key in ("block", "qk_norm", "rope", "linear_layer", "init", "decay_init", "serve_dtype", "state_layout",
                "kv_heads_stored"):
        assert cfg["assumed"][key]
    entry = next(c for c in harness.load_json(os.path.join(REPO, "BENCHMARK.json"))["configs"]
                 if c["name"] == "olmo-hybrid-7b")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


# ------------------------------------------------------- reference and program
def test_reference_matches_the_programs_model_and_imports_nothing_of_it():
    reference = harness.load_module("reference", "olmo_hybrid")
    source = open(os.path.join(REPO, "chipbench", "reference", "olmo_hybrid.py")).read()
    body = source.split('"""', 2)[2]
    assert "accelerate_tpu" not in body
    assert "lax.scan(one_token" in body and 'default_matmul_precision("highest")' in body
    cfg = _tiny()
    params = reference.init_params(cfg, harness.seed_key(2**31 + 5), "float32")
    assert all(isinstance(x, np.ndarray) for x in jax.tree_util.tree_leaves(params))
    model = harness.load_module("adapters", "olmo_hybrid").build_model(cfg, params, "float32")
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], (2, 70)).astype(np.int32)  # over a chunk of 64
    want = reference.logits(params, cfg, jnp.asarray(ids))
    with jax.default_matmul_precision("highest"):
        got = model.module.apply(params, jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    mixer = params["params"]["layer_0"]["mixer"]
    assert mixer["A_log"].dtype == mixer["dt_bias"].dtype == np.float32
    alpha = np.exp(-np.exp(mixer["A_log"]) * np.log1p(np.exp(mixer["dt_bias"])))
    assert 0.15 < alpha.min() and alpha.max() < 1.0  # slow and fast heads, none dead


def test_the_recurrence_is_the_issues_equation_with_beta_over_one():
    """One head, three tokens, by hand: S_t = a S + b k (v - (a S)^T k)^T, o = S^T q."""
    reference = harness.load_module("reference", "olmo_hybrid")
    rng = np.random.default_rng(3)
    q, k = rng.normal(size=(2, 1, 3, 1, 4)).astype(np.float32)
    v = rng.normal(size=(1, 3, 1, 5)).astype(np.float32)
    alpha = np.asarray([0.9, 0.5, 0.99], np.float32).reshape(1, 3, 1)
    beta = np.asarray([1.7, 0.3, 1.99], np.float32).reshape(1, 3, 1)
    state, want = np.zeros((4, 5), np.float32), []
    for t in range(3):
        decayed = alpha[0, t, 0] * state
        state = decayed + beta[0, t, 0] * np.outer(k[0, t, 0], v[0, t, 0] - decayed.T @ k[0, t, 0])
        want.append(state.T @ q[0, t, 0])
    got = reference.delta_recurrence(*(jnp.asarray(x) for x in (q, k, v, alpha, beta)))
    np.testing.assert_allclose(np.asarray(got)[0, :, 0], np.stack(want), rtol=1e-5, atol=1e-6)


def test_served_token_gaps_are_zero_for_the_references_own_choice():
    reference = harness.load_module("reference", "olmo_hybrid")
    cfg = _tiny()
    params = reference.init_params(cfg, harness.seed_key(3), "float32")
    prompt = np.random.default_rng(1).integers(1, cfg["vocab_size"], 9).astype(np.int32)
    tokens = []
    for _ in range(5):
        ids = np.concatenate([prompt, np.asarray(tokens, np.int32)])[None, :]
        tokens.append(int(jnp.argmax(reference.logits(params, cfg, jnp.asarray(ids))[0, -1])))
    gaps = reference.served_token_gaps(params, cfg, [(prompt, tokens)], 32, 8)
    assert len(gaps) == 1 and gaps[0].shape == (5,) and float(gaps[0].max()) < 1e-5
    wrong = list(tokens)
    wrong[2] = (wrong[2] + 1) % cfg["vocab_size"]
    assert float(reference.served_token_gaps(params, cfg, [(prompt, wrong)], 32, 8)[0][2]) > 1e-4


# ------------------------------------------------------------ the driver on CPU
def test_the_new_cell_resolves_and_reports_the_right_metrics():
    cell = harness.Cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert not names & {"decode_roofline_pct", "prefill_device_pct.serve", "latent_moe_decode_roofline_pct",
                        "step_host_ms.serve", "kv_held_vs_full_pct"}
    assert {"slots_busy_pct", "pages_peak_pct", "recompiles_in_window", "hbm_peak_gb.serve",
            "device_idle_pct.serve"} <= names
    assert cell.spec["engine"] == {"num_slots": 48, "max_length": 1280}  # nothing else pinned
    assert cell.traffic["pool"] == 64 and cell.traffic["ramp_s"] == 12.0
    assert cell.traffic["arrivals"] == {"kind": "backlog"}
    for name in NEW_METRICS:
        assert callable(harness.load_reader(name).read)
    limits = cell.spec["correct"]
    assert set(limits["why"]) == {"mean_gap_limit", "max_gap_limit"} and limits["sample"] == 32


def test_the_tiny_cell_runs_through_the_serve_driver_and_its_counters_are_read(hybrid_root, ledger):
    from accelerate_tpu.telemetry import FlightRecorder, Tracer, set_default_tracer

    previous = set_default_tracer(Tracer(recorder=FlightRecorder()))
    try:
        cell = harness.Cell(TINY_CELL, hybrid_root)
        driver = harness.load_module("drivers", "serve", hybrid_root)
        out = driver.serve_once(cell, 2**31 + 17, 1.5, harness.TraceWindow(False, 0.0, 0.0), ledger,
                                time.perf_counter())
        assert out["correct"] is True and out["e2e"]["failed"] == 0 and out["e2e"]["attempted"] > 0
        window = out["context"]["window"]
        steps = [s for s in window["steps"] if s[0] >= window["t0"]]
        # as a traced run leaves it: the capture's module and kernel times, hand-made
        context = dict(out["context"], peaks=harness.peaks_for("TPU v5 lite"),
                       trace_span=(steps[0][0], steps[-1][1]),
                       trace={"busy_s": 1.0, "window_s": 2.0, "device_ops": [["delta_step", 0.2], ["fusion", 0.5]],
                              "modules": {"jit_decode_chunk": {"seconds": 0.8, "runs": 10},
                                          "jit_insert": {"seconds": 0.1, "runs": 5}}})
        values = harness.read_per_layer(cell, context)
    finally:
        set_default_tracer(previous)
    assert "decode_roofline_pct" not in values and "kv_held_vs_full_pct" not in values
    assert values["hybrid_decode_roofline_pct"] > 0 and values["delta_step_roofline_pct"] > 0
    # 4 slots hold 6 layers x (4 x 16 x 32 float32 + 3 x 256 float32) = 67,584 B each and pages of 2 full
    # layers (16 stored heads of 32: the tiny 4 heads in whole tiles) for tokens that full attention in
    # all 8 layers would hold in 8 x 2 x 128 x 4 = 8,192 B each: the state dominates at these lengths
    assert 100.0 < values["state_and_kv_held_vs_full_pct"] < 2000.0


def test_a_program_without_the_counters_reads_nothing(hybrid_root):
    """The parent commit's chunks carry no `state_slots`: the new readers
    return None and the line leaves their metrics out."""
    from accelerate_tpu.telemetry import FlightRecorder, Tracer, set_default_tracer

    mine = Tracer(recorder=FlightRecorder())
    previous = set_default_tracer(mine)
    try:
        now = time.perf_counter()
        with mine.span("serve.decode_chunk", category="serve", live_pages=3, window_pages=36, read_blocks=1,
                       kv_row_values=256):
            pass
        cell = harness.Cell(TINY_CELL, hybrid_root)
        context = {"cell": cell, "window": {"t0": now - 1.0, "t1": now + 1.0, "steps": [(now, now, 1, 1, 10, 4)]},
                   "trace_span": (now - 1.0, now + 1.0), "chunk_size": 4, "num_slots": 4,
                   "peaks": harness.peaks_for("TPU v5 lite"),
                   "trace": {"busy_s": 1.0, "window_s": 2.0, "device_ops": [["fusion", 0.5]],
                             "modules": {"jit_decode_chunk": {"seconds": 0.8, "runs": 10}}}}
        for name in NEW_METRICS:
            assert harness.load_reader(name, hybrid_root).read(context) is None
    finally:
        set_default_tracer(previous)


def test_the_readers_arithmetic_on_hand_made_counters(hybrid_root):
    """Two chunks of known counters and a known capture: each share by hand."""
    from accelerate_tpu.telemetry import FlightRecorder, Tracer, set_default_tracer

    mine = Tracer(recorder=FlightRecorder())
    previous = set_default_tracer(mine)
    try:
        now = time.perf_counter()
        for slots in (2, 4):
            with mine.span("serve.decode_chunk", category="serve", live_pages=10, window_pages=36, read_blocks=1,
                           kv_row_values=1024, state_slots=slots, state_bytes_per_slot=67_584, kv_page_bytes=65_536):
                pass
        cell = harness.Cell(TINY_CELL, hybrid_root)
        peaks = harness.peaks_for("TPU v5 lite")
        context = {"cell": cell, "window": {"t0": now - 1.0, "t1": now + 1.0,
                                            "steps": [(now, now + 0.1, 3, 1, 100, 4), (now + 0.1, now + 0.2, 3, 1, 140, 4)]},
                   "trace_span": (now - 1.0, now + 1.0), "chunk_size": 4, "num_slots": 4, "peaks": peaks,
                   "trace": {"busy_s": 1.0, "window_s": 2.0, "device_ops": [["delta_step", 0.004], ["fusion", 0.5]],
                             "modules": {"jit_decode_chunk": {"seconds": 0.8, "runs": 10}}}}
        read = {name: harness.load_reader(name, hybrid_root).read(context) for name in NEW_METRICS}
    finally:
        set_default_tracer(previous)
    cfg = cell.config
    counts = harness.load_module("reference", "olmo_hybrid", hybrid_root).param_counts(cfg)
    state = 6 * 4 * 16 * 32 * 4  # S a slot: 6 linear layers x 4 heads x 16 x 32 float32
    assert shapes_hybrid.recurrent_state_bytes_per_slot(cfg) == state
    # mean of 3 slots; 40 decode steps share 0.8 s; live tokens mean 120
    need = ((counts["total"] - counts["embedding"]) * 4 + 2 * 3 * state + 3 * 6 * 3 * 256 * 4
            + 120 * 2 * 2 * 128 * 4)
    assert read["hybrid_decode_roofline_pct"] == pytest.approx(need / peaks["hbm_bytes_per_s"] / (0.8 / 40) * 100)
    assert read["delta_step_roofline_pct"] == pytest.approx(2 * 3 * state * 40 / peaks["hbm_bytes_per_s"] / 0.004 * 100)
    held = (3 * 67_584 + 10 * 65_536)  # mean state of 2 and 4 slots, 10 live pages
    assert read["state_and_kv_held_vs_full_pct"] == pytest.approx(held / (120 * 8 * 2 * 128 * 4) * 100)
