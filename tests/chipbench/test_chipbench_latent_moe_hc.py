"""The four-stream latent-attention configuration (`xing4-29b-a4b`): its
reference against hand counts and against itself (YaRN's ramp, the head in
blocks, the check's held tokens), its count functions, its configuration file
against the catalog's keys, its cell driven end to end on CPU at a tiny size
through `drivers/serve.py`, and its four readers on a hand-made reduced trace.
The tiny cell exists only as NEW files in a copy of the benchmark. No number
here is a speed."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, shapes_latent_moe_hc

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "xing4-29b-a4b.prompt-heavy-saturated"
TINY_CELL = "xing-tiny.tiny-backlog"
NEW_METRICS = ("latent_hc_prefill_mfu_pct", "latent_hc_decode_roofline_pct", "hc_mix_roofline_pct",
               "hc_mix_device_pct")


def _config():
    return harness.load_json(os.path.join(REPO, "chipbench", "configs", "xing4-29b-a4b.json"))


def _tiny(**over):
    over.setdefault("check", {})  # every served token is held, unless a test gives a router margin
    scaling = dict(_config()["rope_scaling"], factor=8, beta_fast=4, original_max_position_embeddings=32)
    return dict(_config(), source="test", vocab_size=2048, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=4, num_attention_heads=4, n_routed_experts=8,
                num_experts_per_tok=3, kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, max_position_embeddings=256, rope_scaling=scaling, **over)


@pytest.fixture(scope="module")
def hc_root(tmp_path_factory):
    """conftest.py's throw-away root, plus this family's tiny configuration and
    cell as new files and new entries."""
    from conftest import SERVE_SPEC, build_tiny_root

    root = build_tiny_root(str(tmp_path_factory.mktemp("chipbench_hc_root")))
    for relative, payload in (
        ("chipbench/configs/xing-tiny.json", _tiny()),
        ("chipbench/workloads/" + TINY_CELL + ".json",
         dict(SERVE_SPEC, engine={"num_slots": 4, "max_length": 144, "chunk_size": 4},
              modules={"insert": "^jit_insert$", "latent_hc_decode": "^jit_decode_chunk$",
                       "latent_decode": "^jit_decode_chunk$"},
              correct={"sample": 48, "mean_gap_limit": 5e-6, "max_gap_limit": 2e-4})),
    ):
        path = os.path.join(root, relative)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(payload, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "xing-tiny", "source": "test", "file": "chipbench/configs/xing-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "xing-tiny", "traffic": "tiny-backlog",
                               "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] == "serve_tokens_per_s" or metric["name"] in NEW_METRICS:
            metric["workloads"] = metric["workloads"] + [TINY_CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


# ------------------------------------------------------------------ hand counts
def test_parameter_counts_match_hand_counts():
    reference = harness.load_module("reference", "latent_moe_hc")
    cfg = _config()
    counts = reference.param_counts(cfg)
    # W_qa 3584 x 768, its norm 768, W_qb 768 x 32 x 192, W_kva 3584 x 576, the latent's norm 512,
    # W_kvb 512 x 32 x 256, W_o 4096 x 3584
    assert counts["attention"] == 2_752_512 + 768 + 4_718_592 + 2_064_384 + 512 + 4_194_304 + 14_680_064 == 28_411_136
    assert counts["maps"] == 14_336 * 24 + 3 + 24 == 344_091  # Phi, the alphas, the biases
    assert counts["routed_expert"] == 3 * 3584 * 1024 == 11_010_048 == counts["shared_expert"]
    assert counts["router"] == 3584 * 64 + 64 == 229_440
    assert counts["dense_layer"] == 28_411_136 + 2 * 344_091 + 2 * 3584 + 3 * 3584 * 9216 == 128_196_918
    assert counts["expert_layer"] == 744_989_046 and counts["outside_routed_experts"] == 40_345_974
    assert counts["expert_layer"] - 64 * counts["routed_expert"] == counts["outside_routed_experts"]
    assert counts["embedding"] == counts["head"] == 131_072 * 3584 == 469_762_048
    assert (counts["dense_layers"], counts["expert_layers"]) == (2, 6)
    assert counts["total"] == 2 * 128_196_918 + 6 * 744_989_046 + 2 * 469_762_048 + 3584 == 5_665_855_792
    whole = reference.param_counts(dict(cfg, num_hidden_layers=cfg["published"]["num_hidden_layers"]))
    assert (whole["dense_layers"], whole["expert_layers"], whole["total"]) == (2, 38, 29_505_505_264)
    # and the seeded weights ARE that many, at a size a test can hold
    tiny = _tiny()
    made = reference.init_params(tiny, harness.seed_key(1), "float32")
    assert sum(x.size for x in jax.tree_util.tree_leaves(made)) == reference.param_counts(tiny)["total"]


def test_count_functions_match_hand_counts():
    reference = harness.load_module("reference", "latent_moe_hc")
    cfg = _config()
    counts = reference.param_counts(cfg)
    shapes = shapes_latent_moe_hc
    assert (shapes.kv_row_values(cfg), shapes.stored_row_values(cfg)) == (576, 640)
    assert shapes.kv_bytes_per_token(cfg, "bfloat16") == 8 * 640 * 2 == 10_240
    assert shapes.latent_read_bytes(cfg, "bfloat16", 60_000) == 60_000 * 8 * 576 * 2
    assert shapes.mix_bytes_per_row(cfg, "bfloat16") == (3 * 4 + 2) * 3584 * 2 == 100_352
    assert shapes.sublayers(cfg) == 16
    assert shapes.float32_params(cfg, counts) == 16 * 344_091 + 6 * 64 == 5_505_840
    # everything outside the routed experts, the maps and the routers' biases at four bytes, then 6 x 64 experts
    outside = 2 * 128_196_918 + 6 * 40_345_974 + 3584 + 469_762_048
    assert outside == 968_235_312
    weights = (outside - 5_505_840) * 2 + 5_505_840 * 4 + 6 * 64 * 11_010_048 * 2
    assert shapes.decode_step_weight_bytes(cfg, counts, "bfloat16", 64) == weights == 10_403_199_168
    assert shapes.decode_step_weight_bytes(cfg, counts, "bfloat16", 32) == weights - 6 * 32 * 11_010_048 * 2
    assert shapes.decode_step_bytes(cfg, counts, "bfloat16", 64, 60_000) == weights + 60_000 * 9_216
    # a row of an insert: every attention matrix and both Phi of 8 layers, two dense SwiGLUs, and in six
    # layers the router, the shared expert and four routed experts
    per_row = 8 * (28_411_136 - 768 - 512 + 2 * 14_336 * 24) + 2 * 99_090_432 + 6 * (229_376 + 5 * 11_010_048)
    assert shapes.matmul_params_per_row(cfg, counts) == per_row == 762_642_432
    # 1,000 real rows: two FLOPs a parameter a row, 32 heads x (192 + 128) x 2 a key over 1000 * 1001 / 2
    # pairs a layer, and the head once
    assert shapes.insert_flops(cfg, counts, 1000) == 2 * per_row * 1000 + 8 * 20_480 * 500_500 + 2 * 469_762_048
    assert shapes.insert_flops(cfg, counts, 1000) == 1_608_226_308_096


def test_yarn_is_deepseek_v3s_with_the_published_numbers():
    reference = harness.load_module("reference", "latent_moe_hc")
    cfg = _config()
    assert reference.yarn_range(cfg["rope_scaling"], 64, 10000.0) == (10, 23)
    assert reference.softmax_scale(cfg) * np.sqrt(192) == pytest.approx((0.1 * np.log(64) + 1) ** 2) == pytest.approx(
        2.0047, abs=5e-5)
    sizes = reference._Sizes.of(cfg)
    assert sizes.yarn[:4] == (64.0, 10, 23, 1.0)  # factor, low, high, the rope's own mscale
    # the frequencies: pairs under 10 keep theirs, pairs from 23 on are divided by 64, a ramp between
    x = jnp.zeros((1, 2, 1, 64)).at[..., :32].set(1.0)
    rotated = reference.rotary(x, jnp.asarray([[0, 1]]), sizes)[0, 1, 0]  # position 1: cos and sin of inv_freq
    inv_freq = np.arctan2(np.asarray(rotated[32:]), np.asarray(rotated[:32]))
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv_freq[:11], plain[:11], rtol=1e-5)
    np.testing.assert_allclose(inv_freq[23:], plain[23:] / 64, rtol=1e-4)
    keep = 1 - (16 - 10) / 13
    np.testing.assert_allclose(inv_freq[16], plain[16] * (keep + (1 - keep) / 64), rtol=1e-5)
    # the program computes the same frequencies and the same scale
    from accelerate_tpu.models.latent_moe import xing4_29b_a4b, yarn_inv_freq

    np.testing.assert_allclose(yarn_inv_freq(cfg["rope_scaling"], 64, 10000.0), inv_freq, rtol=2e-5)
    assert xing4_29b_a4b().softmax_scale == pytest.approx(reference.softmax_scale(cfg), rel=1e-12)


def test_the_configuration_file_is_the_catalogs_but_for_the_depth():
    cfg = _config()
    published = {"attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu",
                 "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
                 "max_position_embeddings": 262144, "model_type": "xing4_0", "moe_intermediate_size": 1024,
                 "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
                 "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
                 "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
                 "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
                 "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                                  "original_max_position_embeddings": 4096, "type": "yarn"},
                 "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False,
                 "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 8 and cfg["published"] == {"num_hidden_layers": 40}
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["family"] == "latent_moe_hc"
    assert "5-stage pipeline of 8 layers" in cfg["deployment"] and "all 64 routed experts" in cfg["deployment"]
    assumed = cfg["assumed"]
    assert "NOT built" in assumed["next_token_module"] and "text requests only" in assumed["inputs"]
    for key in ("init", "streams", "maps", "map_dtype", "rope_pairs", "yarn", "cache_row", "check"):
        assert assumed[key]
    # the initialisation's measured numbers are in the file, and inside what was asked of them
    measured = cfg["init"]["measured"]
    assert measured["h_pre_entry_std_over_tokens_min"][0] >= 0.05 <= measured["h_post_entry_std_over_tokens_min"][0]
    assert min(measured["h_res_mean_distance_from_identity"][0], measured["h_res_mean_distance_from_uniform"][0]) > 0.05
    assert 0.1 <= measured["h_post_y_rms_over_streams_rms"][0] <= measured["h_post_y_rms_over_streams_rms"][1] <= 1.0
    assert measured["res_logit_abs_max"][1] < 30
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "xing4-29b-a4b")
    assert entry["source"] == cfg["source"] and entry["reduced"] == ["num_hidden_layers"]


# ------------------------------------------------------------- the reference
def test_the_reference_imports_nothing_of_the_program_and_its_statistics_read_the_maps():
    source = open(os.path.join(REPO, "chipbench", "reference", "latent_moe_hc.py")).read()
    assert "accelerate_tpu" not in source.split('"""', 2)[2]
    reference = harness.load_module("reference", "latent_moe_hc")
    cfg = _tiny()
    params = reference.init_params(cfg, harness.seed_key(2**31 + 5), "float32")
    assert all(isinstance(x, np.ndarray) for x in jax.tree_util.tree_leaves(params))
    maps = params["params"]["layer_3"]["hc_ffn"]
    assert maps["phi"].shape == (4 * 64, 24) and all(v.dtype == np.float32 for v in maps.values())
    assert float(np.diag(maps["b_res"]).mean()) > 1.0 > float(np.abs(maps["b_res"] - np.diag(np.diag(maps["b_res"]))).mean())
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], (1, 48)).astype(np.int32)
    rows = reference.init_statistics(params, cfg, ids)
    assert len(rows) == 8  # 4 layers: attention and feed-forward each
    for row in rows:
        assert row["h_pre_std_min"] > 0.05 and row["h_post_std_min"] > 0.05 and row["res_logit_abs_max"] < 30
        assert row["h_res_row_sum_err"] < 1e-5 and row["h_res_col_sum_err"] < 0.05
        assert min(row["h_res_from_identity"], row["h_res_from_uniform"]) > 0.05


def test_served_token_gaps_are_zero_for_the_references_own_choice_and_hold_by_margin(capsys, monkeypatch):
    reference = harness.load_module("reference", "latent_moe_hc")
    monkeypatch.setattr(reference, "HEAD_BLOCK", 512)  # four blocks of the tiny vocabulary
    cfg = _tiny()
    params = reference.init_params(cfg, harness.seed_key(3), "float32")
    prompt = np.random.default_rng(1).integers(1, cfg["vocab_size"], 9).astype(np.int32)
    tokens = []
    for _ in range(5):
        ids = np.concatenate([prompt, np.asarray(tokens, np.int32)])[None, :]
        tokens.append(int(jnp.argmax(reference.logits(params, cfg, ids)[0, -1])))
    gaps = reference.served_token_gaps(params, cfg, [(prompt, tokens)], 32, 8)
    assert len(gaps) == 1 and gaps[0].shape == (5,) and float(gaps[0].max()) < 1e-5
    wrong = list(tokens)
    wrong[2] = (wrong[2] + 700) % cfg["vocab_size"]  # in another block of the head
    assert float(reference.served_token_gaps(params, cfg, [(prompt, wrong)], 32, 8)[0][2]) > 1e-4
    near, tried = (json.loads(x) for x in capsys.readouterr().out.strip().splitlines()[-2:])
    assert near["routed_positions"] == 2 * 13  # two expert layers, 9 + 4 real positions
    assert tried["router_margin"] == 0.0 and tried["0"]["tokens"] == 5 and tried["0"]["max_gap"] > 1e-4
    # a margin no position reaches holds nothing; one every position reaches holds all
    none = reference.served_token_gaps(params, dict(cfg, check={"router_margin": 10.0}), [(prompt, tokens)], 32, 8)
    assert none[0].size == 0
    with pytest.raises(ValueError, match="longer than the reference was sized for"):
        reference.served_token_gaps(params, cfg, [(prompt, tokens)], 12, 8)


# ------------------------------------------------------------------- the cell
def test_the_new_cell_resolves_and_reports_the_right_metrics():
    cell = harness.Cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names == set(NEW_METRICS) | {"slots_busy_pct", "pages_peak_pct", "recompiles_in_window",
                                        "hbm_peak_gb.serve", "device_idle_pct.serve"}
    assert cell.spec["engine"] == {"num_slots": 64, "max_length": 2176} and cell.chips == 1
    assert "attention_impl" not in json.dumps(cell.spec)
    traffic = cell.traffic
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 768, "sigma": 0.5, "min": 256, "max": 2048}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 48, "sigma": 0.5, "min": 16, "max": 128}
    assert traffic["pool"] == 64 and traffic["ramp_s"] == 12.0 and traffic["arrivals"] == {"kind": "backlog"}
    from chipbench import traffic_gen

    stream = traffic_gen.RequestStream(traffic, cell.config["vocab_size"], 2**31 + 3)
    prompts = [stream.sizes(i)[1] for i in range(64)]
    assert min(prompts) == 256 and max(prompts) == 2048 and 840 < np.mean(prompts) < 900
    assert {1 << (p - 1).bit_length() for p in prompts} == {256, 512, 1024, 2048}  # the insert buckets
    assert stream.max_output_len <= 128 and 50 < stream.mean_output_len < 58
    for name in NEW_METRICS:
        assert callable(harness.load_reader(name).read)
    # each new entry once, under its name, for this cell alone; the accepted latent read's entry is what it was
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert len(by_name) == len(bench["per_layer"])
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "serve_tokens_per_s"
    assert by_name["latent_read_roofline_pct"] == {
        "name": "latent_read_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "attention read and decode matmuls", "moves": "serve_tokens_per_s",
        "workloads": ["kimi-vl-a3b.decode-heavy-saturated"]}
    assert CELL in {m["name"]: m for m in bench["end_to_end"]}["serve_tokens_per_s"]["workloads"]
    # the accepted latent cell lost nothing and gained none of these
    kimi = {m["name"] for m in harness.Cell("kimi-vl-a3b.decode-heavy-saturated").per_layer}
    assert not kimi & set(NEW_METRICS) and "latent_moe_decode_roofline_pct" in kimi


def test_the_adapter_maps_the_published_keys_and_refuses_what_is_not_built():
    adapter = harness.load_module("adapters", "latent_moe_hc")
    cfg = _config()
    program = adapter.program_config(cfg, "bfloat16")
    assert (program.q_lora_rank, program.hc_mult, program.hc_sinkhorn_iters, program.num_hidden_layers) == (768, 4, 20, 8)
    assert program.rope_scaling["factor"] == 64 and program.mhc_h_res_clamp_max == 30
    for key, value in (("n_group", 2), ("scoring_func", "softmax"), ("ep_size", 8), ("attention_bias", True)):
        with pytest.raises(ValueError, match=f"{key}=.*the program's latent_moe family has"):
            adapter.program_config(dict(cfg, **{key: value}), "bfloat16")
    maps = {"phi": np.arange(8 * 24, dtype=np.float32).reshape(8, 24), "alpha": np.ones(3, np.float32),
            "b_pre": np.full(4, 1.0, np.float32), "b_post": np.full(4, 2.0, np.float32),
            "b_res": np.arange(16, dtype=np.float32).reshape(4, 4)}
    stored = adapter.program_maps(maps)
    assert stored["phi_t"].shape == (24, 8) and stored["phi_t"][5, 3] == maps["phi"][3, 5]
    np.testing.assert_array_equal(stored["bias"], np.concatenate([np.full(4, 1.0), np.full(4, 2.0), np.arange(16)]))


def _hand_made_trace(**ops):
    return {"busy_s": 1.0, "window_s": 1.0,
            "device_ops": [[name, seconds] for name, seconds in ops.items()] + [["fusion", 0.5]],
            "modules": {"jit_decode_chunk": {"seconds": 0.25, "runs": 5}, "jit_insert": {"seconds": 0.7, "runs": 10}}}


def test_the_tiny_cell_runs_through_the_serve_driver_and_its_readers_read_it(hc_root, ledger):
    from accelerate_tpu.telemetry import FlightRecorder, Tracer, set_default_tracer

    previous = set_default_tracer(Tracer(recorder=FlightRecorder()))
    try:
        cell = harness.Cell(TINY_CELL, hc_root)
        driver = harness.load_module("drivers", "serve", hc_root)
        out = driver.serve_once(cell, 2**31 + 17, 1.5, harness.TraceWindow(False, 0.0, 0.0), ledger,
                                time.perf_counter())
        assert out["correct"] is True and out["e2e"]["failed"] == 0 and out["e2e"]["attempted"] > 0
        # as a traced run leaves it: a capture from the window's first step on, its module times hand-made
        window = out["context"]["window"]
        steps = [s for s in window["steps"] if s[0] >= window["t0"]]
        context = dict(out["context"], peaks=harness.peaks_for("TPU v5 lite"),
                       trace_span=(steps[0][0], steps[-1][1]),
                       trace=_hand_made_trace(**{"hc_pre": 0.04, "hc_post": 0.06}))
        values = harness.read_per_layer(cell, context)
        # the accepted readers of the experts' matmuls and of the latent read find this cell's chunk under their
        # own module key, so a `benchmark` PR that lists the cell for them has only lists to extend
        theirs = dict(context, trace=_hand_made_trace(**{"gmm": 0.2, "paged_attention": 0.004}))
        others = {name: harness.load_reader(name, hc_root).read(theirs)
                  for name in ("expert_ffn_roofline_pct", "latent_read_roofline_pct")}
        # the readers' arithmetic, from the spans the run left
        from chipbench import captured_spans

        placed = captured_spans.place(captured_spans.captured(context))
        inserts = [r["attrs"] for r in captured_spans.spans("serve.insert", placed)]
        chunks = [r["attrs"] for r in captured_spans.spans("serve.decode_chunk", placed)]
    finally:
        set_default_tracer(previous)
    assert inserts and chunks and all(a["hc_streams"] == 4 for a in inserts + chunks)
    assert values["hc_mix_device_pct"] == pytest.approx(10.0)
    counts = harness.load_module("reference", "latent_moe_hc", hc_root).param_counts(cell.config)
    flops = np.mean([shapes_latent_moe_hc.insert_flops(cell.config, counts, a["suffix_tokens"]) for a in inserts])
    assert values["latent_hc_prefill_mfu_pct"] == pytest.approx(flops * 10 / 0.7 / 197e12 * 100)
    rows = np.mean([a["suffix_tokens"] for a in inserts]) * 8 * 10 + np.mean([a["hc_rows"] for a in chunks]) * 5
    per_row = (3 * 4 + 2) * 64 * 4  # float32 at the tiny size
    assert values["hc_mix_roofline_pct"] == pytest.approx(rows * per_row / 819e9 / 0.1 * 100)
    assert all(a["hc_rows"] == a["active_slots"] * 4 * 8 for a in chunks)
    assert values["latent_hc_decode_roofline_pct"] > 0
    assert cell.spec["modules"]["latent_decode"] == harness.Cell(CELL).spec["modules"]["latent_decode"]
    assert others["expert_ffn_roofline_pct"] > 0 and others["latent_read_roofline_pct"] > 0


def test_a_program_or_a_capture_without_the_kernels_reads_nothing(hc_root):
    """The parent commit's spans carry no `hc_rows` and its captures hold no
    `hc_pre` / `hc_post`: the new readers return None and the line leaves their
    metrics out; so does a capture in which the kernels did not run."""
    from accelerate_tpu.telemetry import FlightRecorder, Tracer, set_default_tracer

    mine = Tracer(recorder=FlightRecorder())
    previous = set_default_tracer(mine)
    try:
        now = time.perf_counter()
        with mine.span("serve.insert", category="serve", bucket=64, suffix_tokens=50, routed_pairs=384):
            pass
        with mine.span("serve.decode_chunk", category="serve", live_pages=3, window_pages=36, active_slots=2):
            pass
        cell = harness.Cell(TINY_CELL, hc_root)
        context = {"cell": cell, "window": {"t0": now - 1.0, "t1": now + 1.0, "steps": [(now, now, 1, 1, 10, 4)]},
                   "trace_span": (None, None), "chunk_size": 4, "peaks": harness.peaks_for("TPU v5 lite"),
                   "trace": _hand_made_trace(**{"hc_pre": 0.04, "hc_post": 0.06})}
        read = lambda name, ctx: harness.load_reader(name, hc_root).read(ctx)  # noqa: E731
        for name in ("latent_hc_prefill_mfu_pct", "hc_mix_roofline_pct", "latent_hc_decode_roofline_pct"):
            assert read(name, context) is None, name
        assert read("hc_mix_device_pct", context) == pytest.approx(10.0)  # the capture's own: no counter needed
        # with the counters but no kernel in the capture: the two shares of the kernels read nothing
        with mine.span("serve.insert", category="serve", bucket=64, suffix_tokens=50, hc_rows=512, hc_streams=4):
            pass
        bare = dict(context, trace=_hand_made_trace())
        assert read("hc_mix_roofline_pct", bare) is None and read("hc_mix_device_pct", bare) is None
    finally:
        set_default_tracer(previous)
