"""The parallel state-space and attention configuration (`falcon-h1-34b`): its
reference against hand counts and against the program's model, its count
functions, its cell driven end to end on CPU at a tiny size through
`drivers/serve.py`, and its readers on the counters a layer that holds BOTH
kinds of leaf leaves. The tiny cell exists only as NEW files in a copy of the
benchmark (conftest.py's root plus this file's own). No number here is a speed."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, shapes_ssm_hybrid

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "falcon-h1-34b.chat-saturated"
TINY_CELL = "ssm-hybrid-tiny.tiny-backlog"
NEW_METRICS = ("ssm_hybrid_decode_roofline_pct", "ssm_step_roofline_pct", "state_held_vs_published_pct")


def _config():
    return harness.load_json(os.path.join(REPO, "chipbench", "configs", "falcon-h1-34b.json"))


def _tiny(**over):
    """Three blocks, small heads, 2 groups, 4 query heads over 2 KV heads; every multiplier as published."""
    return dict(_config(), source="test", vocab_size=2048, hidden_size=128, intermediate_size=256,
                num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2, head_dim=32, rope_theta=10000,
                mamba_d_ssm=128, mamba_n_heads=4, mamba_d_head=32, mamba_n_groups=2, mamba_d_state=16,
                mamba_chunk_size=16, max_position_embeddings=256, **over)


@pytest.fixture(scope="module")
def ssm_root(tmp_path_factory):
    """conftest.py's throw-away root, plus this family's tiny configuration and
    cell as new files and new entries."""
    from conftest import SERVE_SPEC, build_tiny_root

    root = build_tiny_root(str(tmp_path_factory.mktemp("chipbench_ssm_root")))
    for relative, payload in (
        ("chipbench/configs/ssm-hybrid-tiny.json", _tiny()),
        ("chipbench/workloads/" + TINY_CELL + ".json",
         dict(SERVE_SPEC, engine={"num_slots": 4, "max_length": 144, "chunk_size": 4},
              modules={"insert": "^jit_insert$", "ssm_hybrid_decode": "^jit_decode_chunk$"},
              correct={"sample": 48, "mean_gap_limit": 2e-5, "max_gap_limit": 5e-4})),
    ):
        path = os.path.join(root, relative)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(payload, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "ssm-hybrid-tiny", "source": "test",
                             "file": "chipbench/configs/ssm-hybrid-tiny.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "ssm-hybrid-tiny", "traffic": "tiny-backlog",
                               "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] == "serve_tokens_per_s" or metric["name"] in NEW_METRICS:
            metric["workloads"] = metric["workloads"] + [TINY_CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


# ------------------------------------------------------------------ hand counts
def test_parameter_counts_match_hand_counts():
    reference = harness.load_module("reference", "falcon_h1")
    counts = reference.param_counts(_config())
    # W_in 5120 x (4096 + 4096 + 512 + 512 + 32 = 9,248); 4 taps and a bias over 5,120 channels; dt_bias, A_log, D 32
    # each; the gated norm's 4,096 scales; W_out 4096 x 5120
    assert counts["w_in"] == 5120 * 9248 == 47_349_760
    assert counts["mixer"] == 47_349_760 + 25_600 + 96 + 4_096 + 20_971_520 == 68_351_072  # 68.35 M
    # W_q 5120 x 2560, W_k and W_v 5120 x 512, W_o 2560 x 5120
    assert counts["attention"] == 13_107_200 + 2 * 2_621_440 + 13_107_200 == 31_457_280  # 31.46 M
    assert counts["mlp"] == 3 * 5120 * 21504 == 330_301_440  # 330.30 M
    assert counts["layer"] == 68_351_072 + 31_457_280 + 330_301_440 + 2 * 5120 == 430_120_032  # "about 430M"
    assert counts["embedding"] == counts["head"] == 261120 * 5120 == 1_336_934_400  # 1.337 B each
    # the cut: 6 layers, the final norm, the embedding and the head
    assert counts["total"] == 6 * 430_120_032 + 2 * 1_336_934_400 + 5120 == 5_254_594_112  # 10.51 GB in bfloat16
    whole = reference.param_counts(dict(_config(), num_hidden_layers=72))
    assert whole["total"] == 72 * 430_120_032 + 2 * 1_336_934_400 + 5120 == 33_642_516_224  # 67.3 GB
    assert round(whole["total"] / 1e9, 1) == 33.6


def test_count_functions_match_hand_counts():
    cfg = _config()
    counts = harness.load_module("reference", "falcon_h1").param_counts(cfg)
    # H: 32 heads x 128 x 256 float32 = 4,194,304 B a layer, 6 layers
    assert shapes_ssm_hybrid.recurrent_state_bytes_per_slot(cfg) == 6 * 32 * 128 * 256 * 4 == 25_165_824
    # the convolution's last 3 inputs of 5,120 channels, bfloat16: 30,720 B a layer
    assert shapes_ssm_hybrid.conv_state_bytes_per_slot(cfg, "bfloat16") == 6 * 3 * 5120 * 2 == 184_320
    assert shapes_ssm_hybrid.state_bytes_per_slot(cfg, "bfloat16") == 25_350_144  # 25.35 MB whatever the length
    # pages: 6 layers x K and V x 4 KV heads x 128, bfloat16 — the KV heads, not the 20 query heads
    assert shapes_ssm_hybrid.kv_bytes_per_token(cfg, "bfloat16") == 6 * 2 * 4 * 128 * 2 == 12_288
    # a step's weights: all but the embedding table, 3,917.7 M parameters = 7.835 GB
    weights = shapes_ssm_hybrid.decode_step_weight_bytes(counts, "bfloat16")
    assert weights == (5_254_594_112 - 1_336_934_400) * 2 == 7_835_319_424
    assert round(weights / 1e9, 3) == 7.835
    assert shapes_ssm_hybrid.ssm_step_bytes(cfg, 80) == 2 * 80 * 25_165_824 == 4_026_531_840  # 4.03 GB a step
    # 80 slots x ~345 live tokens: 12.2 GB a step, 14.9 ms at 819 GB/s
    step = shapes_ssm_hybrid.decode_step_bytes(cfg, counts, "bfloat16", 80, 80 * 345)
    assert step == weights + 4_026_531_840 + 80 * 184_320 + 80 * 345 * 12_288 == 12_215_745_664
    assert round(step / 819e9 * 1e3, 1) == 14.9


def test_the_configuration_file_is_the_catalogs_but_for_the_depth():
    cfg = _config()
    published = {
        "model_type": "falcon_h1", "vocab_size": 261120, "hidden_size": 5120, "intermediate_size": 21504,
        "num_attention_heads": 20, "num_key_value_heads": 4, "head_dim": 128, "hidden_act": "silu",
        "max_position_embeddings": 262144, "attention_bias": False, "mlp_bias": False, "rms_norm_eps": 1e-05,
        "rope_theta": 100000000000, "rope_scaling": None, "tie_word_embeddings": False,
        "mamba_d_ssm": 4096, "mamba_n_heads": 32, "mamba_d_head": 128, "mamba_d_state": 256, "mamba_n_groups": 2,
        "mamba_d_conv": 4, "mamba_chunk_size": 128, "mamba_expand": 2, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "mamba_rms_norm": True, "mamba_norm_before_gate": False,
        "mlp_expansion_factor": 8, "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
        "embedding_multiplier": 5.656854249492381, "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "ssm_in_multiplier": 0.25, "ssm_out_multiplier": 0.08838834764831845,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    }
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 6 and cfg["published"]["num_hidden_layers"] == 72
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["family"] == "falcon_h1" and "12-stage pipeline of 6 layers" in cfg["deployment"]
    for key in ("init", "branch_shares", "decay_init", "serve_dtype", "state_layout", "gated_norm", "rope",
                "softmax_scale", "requests", "kv_pool_layout"):
        assert cfg["assumed"][key], key
    entry = next(c for c in harness.load_json(os.path.join(REPO, "BENCHMARK.json"))["configs"]
                 if c["name"] == "falcon-h1-34b")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


def test_the_seeded_scales_undo_the_multipliers_around_each_tensor():
    """`std = gain / sqrt(fan_in) / multipliers`: the numbers the configuration file states."""
    reference = harness.load_module("reference", "falcon_h1")
    cfg = _config()
    std = reference.init_stds(cfg)
    assert std["embedding"] == pytest.approx(1.0 / 5.656854249492381)
    assert std["wq"] == pytest.approx(1.4 / 5120 ** 0.5) == pytest.approx(0.019566, rel=1e-3)
    assert std["wk"] == pytest.approx(1.4 / 5120 ** 0.5 / 0.011048543456039804)  # 1.77: what key_multiplier undoes
    assert std["wo"] == pytest.approx(0.7 / 2560 ** 0.5 / 0.0375)
    assert std["w_in"] == pytest.approx(tuple(1.4 / 5120 ** 0.5 / 0.25 / m for m in cfg["ssm_multipliers"]))
    assert std["w_out"] == pytest.approx(0.5 / 4096 ** 0.5 / 0.08838834764831845)
    assert std["w_gate"] == pytest.approx(1.4 / 5120 ** 0.5 / 0.1767766952966369)
    assert std["w_down"] == pytest.approx(0.5 / 21504 ** 0.5 / 0.011160714285714284)
    assert std["lm_head"] == pytest.approx(1.4 / 5120 ** 0.5 * 128)
    assert std["conv"] == pytest.approx(0.5)


# ------------------------------------------------------- reference and program
def test_reference_matches_the_programs_model_and_imports_nothing_of_it():
    reference = harness.load_module("reference", "falcon_h1")
    source = open(os.path.join(REPO, "chipbench", "reference", "falcon_h1.py")).read()
    body = source.split('"""', 2)[2]
    assert "accelerate_tpu" not in body
    assert "lax.scan(one_token" in body and 'default_matmul_precision("highest")' in body
    cfg = _tiny()
    params = reference.init_params(cfg, harness.seed_key(2**31 + 5), "float32")
    assert all(isinstance(x, np.ndarray) for x in jax.tree_util.tree_leaves(params))
    model = harness.load_module("adapters", "falcon_h1").build_model(cfg, params, "float32")
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], (2, 70)).astype(np.int32)  # over four chunks of 16
    shares = []
    reference.hidden_states(params, cfg, jnp.asarray(ids), shares)
    assert len(shares) == 3 and all(0.1 < share < 1.0 for layer in shares for share in layer)  # every branch says something
    want = reference.logits(params, cfg, jnp.asarray(ids))
    with jax.default_matmul_precision("highest"):
        got = model.module.apply(params, jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    mixer = params["params"]["layer_0"]["mixer"]
    assert mixer["A_log"].dtype == mixer["dt_bias"].dtype == mixer["D"].dtype == np.float32
    assert np.all(mixer["D"] == 1.0) and 0.0 <= mixer["A_log"].min() and mixer["A_log"].max() <= np.log(16.0)
    dt = np.log1p(np.exp(mixer["dt_bias"]))
    assert 0.0009 < dt.min() and dt.max() < 0.11  # exp(U(log 0.001, log 0.1))


def test_the_recurrence_is_the_issues_equation():
    """One head, three tokens, by hand: H_t = exp(dt A) H + dt x B^T, y = H C."""
    reference = harness.load_module("reference", "falcon_h1")
    rng = np.random.default_rng(3)
    x = rng.normal(size=(1, 3, 1, 4)).astype(np.float32)
    b_in, c_in = rng.normal(size=(2, 1, 3, 1, 5)).astype(np.float32)
    dt = np.asarray([0.9, 0.05, 0.3], np.float32).reshape(1, 3, 1)
    a = np.asarray([-2.0], np.float32)
    state, want = np.zeros((4, 5), np.float32), []
    for t in range(3):
        state = np.exp(dt[0, t, 0] * a[0]) * state + dt[0, t, 0] * np.outer(x[0, t, 0], b_in[0, t, 0])
        want.append(state @ c_in[0, t, 0])
    got = reference.ssm_recurrence(*(jnp.asarray(v) for v in (x, dt, a, b_in, c_in)))
    np.testing.assert_allclose(np.asarray(got)[0, :, 0], np.stack(want), rtol=1e-5, atol=1e-6)


def test_served_token_gaps_are_zero_for_the_references_own_choice(monkeypatch):
    """Also with the head in several blocks of the vocabulary, as the published 261,120 columns need."""
    reference = harness.load_module("reference", "falcon_h1")
    cfg = _tiny()
    params = reference.init_params(cfg, harness.seed_key(3), "float32")
    prompt = np.random.default_rng(1).integers(1, cfg["vocab_size"], 9).astype(np.int32)
    tokens = []
    for _ in range(5):
        ids = np.concatenate([prompt, np.asarray(tokens, np.int32)])[None, :]
        tokens.append(int(jnp.argmax(reference.logits(params, cfg, jnp.asarray(ids))[0, -1])))
    wrong = list(tokens)
    wrong[2] = (wrong[2] + 1) % cfg["vocab_size"]
    assert reference._head_block(261120) == 32640  # 8 blocks at the published vocabulary
    for block in (reference.VOCAB_BLOCK, 512, 600):  # one block; four blocks of 512; 600 -> four of 512 again
        monkeypatch.setattr(reference, "VOCAB_BLOCK", block)
        assert reference._head_block(cfg["vocab_size"]) == (2048 if block > 2048 else 512)
        reference.head_gaps.clear_cache()  # the block is read as the head is traced
        gaps = reference.served_token_gaps(params, cfg, [(prompt, tokens)], 32, 8)
        assert len(gaps) == 1 and gaps[0].shape == (5,) and float(gaps[0].max()) < 1e-5
        assert float(reference.served_token_gaps(params, cfg, [(prompt, wrong)], 32, 8)[0][2]) > 1e-4


# ------------------------------------------------------------ the driver on CPU
def test_the_new_cell_resolves_and_reports_the_right_metrics():
    cell = harness.Cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert not names & {"decode_roofline_pct", "prefill_device_pct.serve", "hybrid_decode_roofline_pct",
                        "delta_step_roofline_pct", "step_host_ms.serve", "kv_held_vs_full_pct", "host_exposed_pct.serve"}
    assert {"slots_busy_pct", "pages_peak_pct", "recompiles_in_window", "hbm_peak_gb.serve",
            "device_idle_pct.serve"} <= names
    assert cell.spec["engine"] == {"num_slots": 80, "max_length": 1280}  # nothing else pinned: no attention read named
    assert cell.spec["controls"]["weights_int8"]["engine"] == {"weight_dtype": "int8"}
    # one mix under two hybrids: the traffic file is the sibling's, unedited
    sibling = harness.Cell("olmo-hybrid-7b.chat-saturated")
    assert cell.traffic == sibling.traffic and cell.traffic["pool"] == 64 and cell.traffic["ramp_s"] == 12.0
    for name in NEW_METRICS:
        assert callable(harness.load_reader(name).read)
    limits = cell.spec["correct"]
    assert set(limits["why"]) == {"mean_gap_limit", "max_gap_limit"} and limits["sample"] == 32


def test_the_tiny_cell_runs_through_the_serve_driver_and_its_counters_are_read(ssm_root, ledger):
    from accelerate_tpu.telemetry import FlightRecorder, Tracer, set_default_tracer

    previous = set_default_tracer(Tracer(recorder=FlightRecorder()))
    try:
        cell = harness.Cell(TINY_CELL, ssm_root)
        driver = harness.load_module("drivers", "serve", ssm_root)
        out = driver.serve_once(cell, 2**31 + 17, 1.5, harness.TraceWindow(False, 0.0, 0.0), ledger,
                                time.perf_counter())
        assert out["correct"] is True and out["e2e"]["failed"] == 0 and out["e2e"]["attempted"] > 0
        window = out["context"]["window"]
        steps = [s for s in window["steps"] if s[0] >= window["t0"]]
        # as a traced run leaves it: the capture's module and kernel times, hand-made
        context = dict(out["context"], peaks=harness.peaks_for("TPU v5 lite"),
                       trace_span=(steps[0][0], steps[-1][1]),
                       trace={"busy_s": 1.0, "window_s": 2.0, "device_ops": [["ssm_step", 0.2], ["fusion", 0.5]],
                              "modules": {"jit_decode_chunk": {"seconds": 0.8, "runs": 10},
                                          "jit_insert": {"seconds": 0.1, "runs": 5}}})
        values = harness.read_per_layer(cell, context)
    finally:
        set_default_tracer(previous)
    assert "decode_roofline_pct" not in values and "hybrid_decode_roofline_pct" not in values
    assert values["ssm_hybrid_decode_roofline_pct"] > 0 and values["ssm_step_roofline_pct"] > 0
    # a slot holds 3 layers x (4 x 32 x 16 float32 + 3 x 192 float32) = 31,488 B, which is what the configuration
    # publishes for float32 serving: nothing padded, nothing widened
    assert values["state_held_vs_published_pct"] == pytest.approx(100.0)


def test_a_program_without_the_counters_reads_nothing(ssm_root):
    """The parent commit's chunks carry no `state_slots` for this cell's readers
    to find (it cannot run the family at all): they return None and the line
    leaves their metrics out."""
    from accelerate_tpu.telemetry import FlightRecorder, Tracer, set_default_tracer

    mine = Tracer(recorder=FlightRecorder())
    previous = set_default_tracer(mine)
    try:
        now = time.perf_counter()
        with mine.span("serve.decode_chunk", category="serve", live_pages=3, window_pages=36, read_blocks=1,
                       kv_row_values=256):
            pass
        cell = harness.Cell(TINY_CELL, ssm_root)
        context = {"cell": cell, "window": {"t0": now - 1.0, "t1": now + 1.0, "steps": [(now, now, 1, 1, 10, 4)]},
                   "trace_span": (now - 1.0, now + 1.0), "chunk_size": 4, "num_slots": 4,
                   "peaks": harness.peaks_for("TPU v5 lite"),
                   "trace": {"busy_s": 1.0, "window_s": 2.0, "device_ops": [["fusion", 0.5]],
                             "modules": {"jit_decode_chunk": {"seconds": 0.8, "runs": 10}}}}
        for name in NEW_METRICS:
            assert harness.load_reader(name, ssm_root).read(context) is None
    finally:
        set_default_tracer(previous)


def test_the_readers_arithmetic_on_hand_made_counters(ssm_root):
    """Two chunks of known counters and a known capture: each share by hand."""
    from accelerate_tpu.telemetry import FlightRecorder, Tracer, set_default_tracer

    mine = Tracer(recorder=FlightRecorder())
    previous = set_default_tracer(mine)
    try:
        now = time.perf_counter()
        for slots in (2, 4):
            with mine.span("serve.decode_chunk", category="serve", live_pages=10, window_pages=36, read_blocks=1,
                           kv_row_values=128, state_slots=slots, state_bytes_per_slot=47_232, kv_page_bytes=24_576):
                pass
        cell = harness.Cell(TINY_CELL, ssm_root)
        peaks = harness.peaks_for("TPU v5 lite")
        context = {"cell": cell, "window": {"t0": now - 1.0, "t1": now + 1.0,
                                            "steps": [(now, now + 0.1, 3, 1, 100, 4), (now + 0.1, now + 0.2, 3, 1, 140, 4)]},
                   "trace_span": (now - 1.0, now + 1.0), "chunk_size": 4, "num_slots": 4, "peaks": peaks,
                   "trace": {"busy_s": 1.0, "window_s": 2.0, "device_ops": [["ssm_step", 0.004], ["fusion", 0.5]],
                             "modules": {"jit_decode_chunk": {"seconds": 0.8, "runs": 10}}}}
        read = {name: harness.load_reader(name, ssm_root).read(context) for name in NEW_METRICS}
    finally:
        set_default_tracer(previous)
    cfg = cell.config
    counts = harness.load_module("reference", "falcon_h1", ssm_root).param_counts(cfg)
    state = 3 * 4 * 32 * 16 * 4  # H a slot: 3 layers x 4 heads x 32 x 16 float32
    conv = 3 * 3 * 192 * 4  # the convolution's 3 inputs of 128 + 2 x 2 x 16 channels, float32 here
    assert shapes_ssm_hybrid.recurrent_state_bytes_per_slot(cfg) == state
    assert shapes_ssm_hybrid.state_bytes_per_slot(cfg, "float32") == state + conv == 31_488
    # mean of 3 slots; 40 decode steps share 0.8 s; live tokens mean 120; 3 layers x K and V x 2 KV heads of 32
    need = (counts["total"] - counts["embedding"]) * 4 + 2 * 3 * state + 3 * conv + 120 * 3 * 2 * 2 * 32 * 4
    assert read["ssm_hybrid_decode_roofline_pct"] == pytest.approx(need / peaks["hbm_bytes_per_s"] / (0.8 / 40) * 100)
    assert read["ssm_step_roofline_pct"] == pytest.approx(2 * 3 * state * 40 / peaks["hbm_bytes_per_s"] / 0.004 * 100)
    # a state stored half as large again as published reads 150
    assert read["state_held_vs_published_pct"] == pytest.approx(47_232 / 31_488 * 100) == pytest.approx(150.0)
