"""Fixtures of the benchmark's own tests: a throw-away root that holds a copy of
`chipbench/` plus tiny configurations, traffic mixes and cells added as NEW
FILES ONLY (no file of the copy is edited; `BENCHMARK.json` gains entries) — so
every test that drives a tiny cell is also the proof that a later PR can add a
configuration, a cell and a metric without editing what is there."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

NEOX_TINY = {
    "source": "test", "family": "gpt_neox", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4, "vocab_size": 8192,
    "max_position_embeddings": 256, "rotary_pct": 0.25, "rotary_emb_base": 10000,
    "layer_norm_eps": 1e-5, "use_parallel_residual": True, "reduced": [],
    "init": {"std": 0.02},
}
BERT_TINY = {
    "source": "test", "family": "bert", "hidden_size": 128, "intermediate_size": 512,
    "num_hidden_layers": 4, "num_attention_heads": 4, "vocab_size": 1024,
    "max_position_embeddings": 128, "type_vocab_size": 2, "layer_norm_eps": 1e-12,
    "num_labels": 2, "reduced": [], "init": {"std": 0.02},
}
TINY_REQUESTS = {
    "kind": "requests",
    "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 4, "max": 100},
    "output_len": {"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 2, "max": 40},
    "pool": 32, "arrivals": {"kind": "backlog"}, "ramp_s": 0.3,
}
SERVE_SPEC = {
    "driver": "serve", "dtype": "float32",
    "engine": {"num_slots": 4, "max_length": 144, "chunk_size": 4, "paged": True, "page_size": 16},
    "modules": {"decode": "^jit_decode_chunk$", "insert": "^jit_insert$"},
    "correct": {"sample": 48, "mean_gap_limit": 1e-7, "max_gap_limit": 1e-5},
    "controls": {"weights_int8": {"engine": {"weight_dtype": "int8"}}},
}
TRAIN_SPEC = {
    "driver": "train", "param_dtype": "float32", "accelerator": {"mixed_precision": "no"},
    "batch": 8, "learning_rate": 1e-3, "warm_steps": 1, "max_in_flight": 2,
    "modules": {"step": "^jit_fused$"},
    "correct": {"loss_gap_limit": 1e-4, "first_grad_norm_gap_limit": 3e-3, "change_norm_gap_limit": 3e-3,
                "first_grad_cosine_gap_limit": 1e-6,
                "nonfinite_losses_limit": 0.0},
    "controls": {"float8_e4m3fn": {"precision": "float8_e4m3fn"},
                 "program_fp8": {"accelerator": {"mixed_precision": "fp8"}}},
}


def _write(root, relative, payload):
    path = os.path.join(root, relative)
    assert not os.path.exists(path), f"{relative} would EDIT a file of the benchmark"
    with open(path, "w") as f:
        json.dump(payload, f)


def build_tiny_root(root: str) -> str:
    shutil.copytree(os.path.join(REPO, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    _write(root, "chipbench/configs/neox-tiny.json", NEOX_TINY)
    _write(root, "chipbench/configs/bert-tiny.json", BERT_TINY)
    _write(root, "chipbench/traffic/tiny-backlog.json", TINY_REQUESTS)
    _write(root, "chipbench/traffic/tiny-open.json",
           dict(TINY_REQUESTS, arrivals={"kind": "exponential_quantiles", "rate_per_s": 20.0}))
    _write(root, "chipbench/traffic/tiny-mrpc.json",
           {"kind": "batches", "shape": "mrpc_pairs", "seq_len": 32, "examples": 256})
    _write(root, "chipbench/workloads/neox-tiny.tiny-backlog.json", SERVE_SPEC)
    _write(root, "chipbench/workloads/neox-tiny.tiny-open.json", SERVE_SPEC)
    _write(root, "chipbench/workloads/bert-tiny.tiny-mrpc.json", TRAIN_SPEC)
    # a per-layer metric added as a new reader file and a new entry
    with open(os.path.join(root, "chipbench/readers/steps_in_window.py"), "x") as f:
        f.write("def read(context):\n    return float(len(context['window']['steps']))\n")
    bench["configs"] += [
        {"name": "neox-tiny", "source": "test", "file": "chipbench/configs/neox-tiny.json", "reduced": [], "why": "test"},
        {"name": "bert-tiny", "source": "test", "file": "chipbench/configs/bert-tiny.json", "reduced": [], "why": "test"},
    ]
    bench["workloads"] += [
        {"name": "neox-tiny.tiny-backlog", "config": "neox-tiny", "traffic": "tiny-backlog", "chips": 1, "why": "test"},
        {"name": "neox-tiny.tiny-open", "config": "neox-tiny", "traffic": "tiny-open", "chips": 1, "why": "test"},
        {"name": "bert-tiny.tiny-mrpc", "config": "bert-tiny", "traffic": "tiny-mrpc", "chips": 1, "why": "test"},
    ]
    bench["end_to_end"] = [m for m in bench["end_to_end"]
                           if m["name"] not in ("serve_tokens_per_s", "train_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms")] + [
        {"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.03,
         "source": "host_clock", "workloads": ["neox-tiny.tiny-backlog"]},
        {"name": "ttft_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1, "source": "host_clock",
         "workloads": ["neox-tiny.tiny-open"]},
        {"name": "tpot_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1, "source": "host_clock",
         "workloads": ["neox-tiny.tiny-open"]},
        {"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher", "bound": 0.03,
         "source": "host_clock", "workloads": ["bert-tiny.tiny-mrpc"]},
    ]
    bench["per_layer"].append({"name": "steps_in_window", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "engine host plane",
                               "moves": "serve_tokens_per_s"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return build_tiny_root(str(tmp_path_factory.mktemp("chipbench_root")))


@pytest.fixture(scope="session")
def ledger():
    from chipbench import harness

    return harness.CompileLedger()
