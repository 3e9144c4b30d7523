"""The latent-attention, sparse-expert configuration (`kimi-vl-a3b`): its
reference against hand counts and against the program's model, its count
functions, its cell driven end to end on CPU at a tiny size through
`drivers/serve.py`, and its readers on the counters that run leaves. The tiny
cell exists only as NEW files in a copy of the benchmark (conftest.py's root
plus this file's own). No number here is a speed."""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, shapes_latent_moe

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "kimi-vl-a3b.decode-heavy-saturated"
TINY_CELL = "latent-tiny.tiny-backlog"
NEW_METRICS = ("latent_moe_decode_roofline_pct", "expert_ffn_roofline_pct",
               "prefill_device_pct.serve", "expert_load_max_over_mean", "kv_held_vs_full_pct")


def _config():
    return harness.load_json(os.path.join(REPO, "chipbench", "configs", "kimi-vl-a3b.json"))


def _tiny(**over):
    over.setdefault("check", {})  # every served token is held, unless a test gives a router margin
    return dict(_config(), source="test", vocab_size=2048, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4, n_routed_experts=8,
                num_experts_per_tok=3, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, max_position_embeddings=256, **over)


@pytest.fixture(scope="module")
def latent_root(tmp_path_factory):
    """conftest.py's throw-away root, plus this family's tiny configuration and
    cell as new files and new entries."""
    from conftest import SERVE_SPEC, TINY_REQUESTS, build_tiny_root

    root = build_tiny_root(str(tmp_path_factory.mktemp("chipbench_latent_root")))
    for relative, payload in (
        ("chipbench/configs/latent-tiny.json", _tiny()),
        ("chipbench/workloads/" + TINY_CELL + ".json",
         dict(SERVE_SPEC, engine={"num_slots": 4, "max_length": 144, "chunk_size": 4},
              modules={"insert": "^jit_insert$", "latent_decode": "^jit_decode_chunk$"},
              correct={"sample": 48, "mean_gap_limit": 2e-6, "max_gap_limit": 1e-4})),
    ):
        path = os.path.join(root, relative)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(payload, f)
    assert TINY_REQUESTS["kind"] == "requests"
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "latent-tiny", "source": "test", "file": "chipbench/configs/latent-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "latent-tiny", "traffic": "tiny-backlog",
                               "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] == "serve_tokens_per_s" or metric["name"] in NEW_METRICS:
            metric["workloads"] = metric["workloads"] + [TINY_CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


# ------------------------------------------------------------------ hand counts
def test_parameter_counts_match_hand_counts():
    counts = harness.load_module("reference", "latent_moe").param_counts(_config())
    # W_q 2048*16*192, W_kva 2048*576, W_kvb 512*16*256, W_o 2048*2048
    assert counts["attention"] == 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304 == 13_762_560
    assert counts["shared_expert"] == 3 * 2048 * 2816 == 17_301_504
    assert counts["router"] == 2048 * 64 + 64
    # outside the routed experts: attention, shared expert, router, three norms (2048 + 2048 + 512): 31.2 M
    assert counts["outside_routed_experts"] == 13_762_560 + 17_301_504 + 131_136 + 4_608 == 31_199_808
    assert counts["routed_expert"] == 3 * 2048 * 1408 == 8_650_752  # 8.65 M
    assert counts["dense_layer"] == 13_762_560 + 4_608 + 3 * 2048 * 11264 == 82_973_184  # 83.0 M
    assert counts["embedding"] + counts["head"] == 2 * 163840 * 2048 == 671_088_640  # 671 M
    # the cut: the dense layer and 8 expert layers of 31.2 M + 64 x 8.65 M, final norm, embedding and head
    assert counts["total"] == 82_973_184 + 8 * (31_199_808 + 64 * 8_650_752) + 2048 + 671_088_640 == 5_432_847_360
    whole = harness.load_module("reference", "latent_moe").param_counts(dict(_config(), num_hidden_layers=27))
    assert round(whole["total"] / 1e9, 2) == 15.96  # as published: does not fit one chip in bfloat16


def test_count_functions_match_hand_counts():
    cfg = _config()
    counts = harness.load_module("reference", "latent_moe").param_counts(cfg)
    assert shapes_latent_moe.kv_row_values(cfg) == 512 + 64 == 576
    assert shapes_latent_moe.full_head_kv_values(cfg) == 16 * (192 + 128) == 5120
    assert shapes_latent_moe.kv_bytes_per_token(cfg, "bfloat16") == 9 * 576 * 2 == 10_368
    # a full step: everything but the embedding table, 5,097.3 M parameters = 10.19 GB
    weights = shapes_latent_moe.decode_step_weight_bytes(cfg, counts, "bfloat16", 64)
    assert weights == (5_432_847_360 - 335_544_320) * 2 == 10_194_606_080
    assert shapes_latent_moe.expert_ffn_bytes(cfg, counts, "bfloat16", 64) == 8 * 64 * 8_650_752 * 2 == 8_858_370_048
    # half the experts touched: half the routed bytes go
    assert shapes_latent_moe.decode_step_weight_bytes(cfg, counts, "bfloat16", 32) == weights - 8_858_370_048 // 2
    assert shapes_latent_moe.latent_read_bytes(cfg, "bfloat16", 96_000) == 96_000 * 10_368
    assert shapes_latent_moe.decode_step_bytes(cfg, counts, "bfloat16", 64, 96_000) == weights + 995_328_000


def test_the_configuration_file_is_the_catalogs_but_for_the_depth():
    cfg = _config()
    published = {"vocab_size": 163840, "max_position_embeddings": 131072, "hidden_size": 2048,
                 "intermediate_size": 11264, "moe_intermediate_size": 1408, "num_attention_heads": 16,
                 "n_shared_experts": 2, "n_routed_experts": 64, "ep_size": 1, "routed_scaling_factor": 2.446,
                 "kv_lora_rank": 512, "q_lora_rank": None, "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "qk_nope_head_dim": 128, "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
                 "num_experts_per_tok": 6, "moe_layer_freq": 1, "first_k_dense_replace": 1,
                 "norm_topk_prob": True, "scoring_func": "sigmoid", "seq_aux": True, "num_key_value_heads": 16,
                 "hidden_act": "silu", "rms_norm_eps": 1e-05, "rope_theta": 800000, "rope_scaling": None,
                 "attention_bias": False, "tie_word_embeddings": False}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 9 and cfg["published"] == {"num_hidden_layers": 27}
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert "text requests only" in cfg["assumed"]["inputs"] and "3-stage pipeline" in cfg["deployment"]


# ------------------------------------------------------- reference and program
def test_reference_matches_the_programs_model_and_imports_nothing_of_it():
    reference = harness.load_module("reference", "latent_moe")
    source = open(os.path.join(REPO, "chipbench", "reference", "latent_moe.py")).read()
    assert "accelerate_tpu" not in source.split('"""', 2)[2]
    cfg = _tiny()
    params = reference.init_params(cfg, harness.seed_key(2**31 + 5), "float32")
    model = harness.load_module("adapters", "latent_moe").build_model(cfg, params, "float32")
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], (2, 40)).astype(np.int32)
    want = reference.logits(params, cfg, jnp.asarray(ids))
    with jax.default_matmul_precision("highest"):
        got = model.module.apply(params, jnp.asarray(ids))
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6
    bias = params["params"]["layer_1"]["moe"]["router_bias"]
    assert bias.dtype == jnp.float32 and float(jnp.abs(bias).max()) > 0  # the bias path is exercised


def test_served_token_gaps_are_zero_for_the_references_own_choice(capsys):
    reference = harness.load_module("reference", "latent_moe")
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
    near, tried = (json.loads(x) for x in capsys.readouterr().out.strip().splitlines()[-2:])
    assert near["routed_positions"] == 2 * 13 and near["margin_under_0.01"] >= near["margin_under_0.001"] >= 0
    assert tried["router_margin"] == 0.0 and tried["0"]["tokens"] == 5 and tried["0"]["max_gap"] > 1e-4


@pytest.mark.parametrize("margin", [0.0, 0.004, 0.02, 10.0])
def test_served_token_gaps_hold_only_tokens_whose_router_margins_are_wide(margin, capsys):
    """`check.router_margin`: a generated token is held only where every expert
    layer's margin (sixth against seventh biased score, the reference's own) at
    its position is at least that — counted here from `route`'s margins by hand."""
    reference = harness.load_module("reference", "latent_moe")
    cfg = _tiny(check={"router_margin": margin})
    params = reference.init_params(cfg, harness.seed_key(4), "float32")
    rng = np.random.default_rng(2)
    served = [(rng.integers(1, cfg["vocab_size"], n).astype(np.int32), list(rng.integers(1, cfg["vocab_size"], m)))
              for n, m in ((9, 8), (5, 6), (12, 3))]
    gaps = reference.served_token_gaps(params, cfg, served, 32, 8)
    every = reference.served_token_gaps(params, dict(cfg, check={}), served, 32, 8)
    capsys.readouterr()
    for (prompt, tokens), held, whole in zip(served, gaps, every):
        ids = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])[None, :]
        margins: list = []
        reference.hidden_states(params, cfg, jnp.asarray(ids), margins)
        least = np.min(np.stack([np.asarray(m) for m in margins]), axis=0)[0, len(prompt) - 1:]
        assert whole.shape == (len(tokens),) and least.shape == (len(tokens),)
        np.testing.assert_allclose(held, whole[least >= margin], rtol=0, atol=1e-5)
    held_tokens = sum(g.size for g in gaps)
    assert held_tokens == 17 if margin == 0.0 else held_tokens == 0 if margin == 10.0 else 0 < held_tokens < 17


# ------------------------------------------------------------ the driver on CPU
def test_the_benchmarks_weights_stay_on_the_host_and_the_int8_control_runs_beside_them(latent_root, ledger, monkeypatch):
    """The driver keeps the seeded weights for the check while the engine runs:
    they are host arrays (`init_params`), the engine places its own copy — and
    a control whose engine holds them as int8 never holds the floating tree on
    the device, which is what lets it run at the real cell's size."""
    cell = harness.Cell(TINY_CELL, latent_root)
    driver = harness.load_module("drivers", "serve", latent_root)
    reference = harness.load_module("reference", "latent_moe", latent_root)
    made = reference.init_params(cell.config, harness.seed_key(5), "float32")
    assert all(isinstance(x, np.ndarray) for x in jax.tree_util.tree_leaves(made))
    engines = []
    build = driver.build_router

    def recording(*args, **kwargs):
        router = build(*args, **kwargs)
        engines.append([type(x) for x in jax.tree_util.tree_leaves(driver.router_engine(router).params)])
        return router

    monkeypatch.setattr(driver, "build_router", recording)
    sound = driver.control(cell, 2**31 + 23, 1.0, None, ledger)
    int8 = driver.control(cell, 2**31 + 23, 1.0, cell.spec["controls"]["weights_int8"], ledger)
    assert len(engines) == 2 and all(issubclass(t, jax.Array) for leaves in engines for t in leaves)
    assert sound["wrong_length"] == int8["wrong_length"] == 0.0
    assert sound["mean_gap"] <= 2e-6 < int8["mean_gap"]  # float32 against the float32 reference; int8 weights are told


def test_the_new_cell_resolves_and_reports_the_right_metrics():
    cell = harness.Cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names and "decode_roofline_pct" not in names
    assert {"slots_busy_pct", "pages_peak_pct", "recompiles_in_window", "hbm_peak_gb.serve",
            "device_idle_pct.serve"} <= names
    assert cell.spec["engine"] == {"num_slots": 128, "max_length": 2048}
    assert cell.traffic["pool"] == 64 and cell.traffic["ramp_s"] == 12.0
    for name in NEW_METRICS:
        assert callable(harness.load_reader(name).read)


def test_the_tiny_cell_runs_through_the_serve_driver_and_its_counters_are_read(latent_root, ledger):
    from accelerate_tpu.telemetry import FlightRecorder, Tracer, set_default_tracer

    previous = set_default_tracer(Tracer(recorder=FlightRecorder()))
    try:
        cell = harness.Cell(TINY_CELL, latent_root)
        driver = harness.load_module("drivers", "serve", latent_root)
        out = driver.serve_once(cell, 2**31 + 17, 1.5, harness.TraceWindow(False, 0.0, 0.0), ledger,
                                time.perf_counter())
        assert out["correct"] is True and out["e2e"]["failed"] == 0 and out["e2e"]["attempted"] > 0
        window = out["context"]["window"]
        steps = [s for s in window["steps"] if s[0] >= window["t0"]]
        # as a traced run leaves it: the capture's module times, hand-made
        context = dict(out["context"], peaks=harness.peaks_for("TPU v5 lite"),
                       trace_span=(steps[0][0], steps[-1][1]),
                       trace={"busy_s": 1.0, "window_s": 2.0, "device_ops": [["gmm", 0.2], ["fusion", 0.5]],
                              "modules": {"jit_decode_chunk": {"seconds": 0.8, "runs": 10},
                                          "jit_insert": {"seconds": 0.1, "runs": 5}}})
        values = harness.read_per_layer(cell, context)
    finally:
        set_default_tracer(previous)
    assert "decode_roofline_pct" not in values
    # 40 of 128 values a token: the tiny row [c 32 | k_pe 8] in its whole tile, over 4 heads of 24 + 16
    assert values["kv_held_vs_full_pct"] == pytest.approx(128 / 160 * 100)
    assert 1.0 <= values["expert_load_max_over_mean"] <= 8.0
    assert values["prefill_device_pct.serve"] == pytest.approx(10.0)
    assert values["latent_moe_decode_roofline_pct"] > 0 and values["expert_ffn_roofline_pct"] > 0


def test_a_program_without_the_counters_reads_nothing(latent_root):
    """The parent commit's chunks carry no `kv_row_values` and no expert counts:
    the new readers return None and the line leaves their metrics out."""
    from accelerate_tpu.telemetry import FlightRecorder, Tracer, set_default_tracer

    mine = Tracer(recorder=FlightRecorder())
    previous = set_default_tracer(mine)
    try:
        now = time.perf_counter()
        with mine.span("serve.decode_chunk", category="serve", live_pages=3, window_pages=36, read_blocks=1):
            pass
        cell = harness.Cell(TINY_CELL, latent_root)
        context = {"cell": cell, "window": {"t0": now - 1.0, "t1": now + 1.0, "steps": [(now, now, 1, 1, 10, 4)]},
                   "trace_span": (now - 1.0, now + 1.0), "chunk_size": 4, "peaks": harness.peaks_for("TPU v5 lite"),
                   "trace": {"busy_s": 1.0, "window_s": 2.0, "device_ops": [],
                             "modules": {"jit_decode_chunk": {"seconds": 0.8, "runs": 10}}}}
        for name in ("latent_moe_decode_roofline_pct", "expert_ffn_roofline_pct", "expert_load_max_over_mean",
                     "kv_held_vs_full_pct"):
            assert harness.load_reader(name, latent_root).read(context) is None
    finally:
        set_default_tracer(previous)
