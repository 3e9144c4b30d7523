"""`BENCHMARK.json` against its contract, and every name in it against the files
it has to resolve to."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(relative):
    with open(os.path.join(REPO, relative)) as f:
        return json.load(f)


BENCH = _load("BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["command"]) <= 32 and all(_line(w) for w in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["configs"]) <= 24 and 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16 and 1 <= len(BENCH["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    # the full check with 24 cells has to fit: 2 + 14 x cells runs, each run_seconds + 60 s
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_the_command_and_every_path_stay_inside_the_benchmark():
    for path in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path) and not path.startswith("/") and ".." not in path
        assert os.path.isdir(os.path.join(REPO, path))
    for word in BENCH["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(REPO, word)):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])
    for path in BENCH["paths"]:
        for folder, _dirs, files in os.walk(os.path.join(REPO, path)):
            if "__pycache__" in folder:
                continue
            for name in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), os.path.join(folder, name)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_configuration_entry_and_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and _line(entry["source"]) and _line(entry["why"])
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    assert len(entry["reduced"]) <= 16 and all(NAME.match(k) for k in entry["reduced"])
    config = _load(entry["file"])
    assert config["source"] == entry["source"] and config["reduced"] == entry["reduced"]
    assert "assumed" in config and "deployment" in config and "family" in config
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])  # used by some cell
    for kind in ("reference", "adapters"):
        assert os.path.exists(os.path.join(REPO, "chipbench", kind, config["family"] + ".py"))
    forbidden = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head")
    assert not [k for k in entry["reduced"] if forbidden.search(k)], "a width may never be reduced"


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda e: e["name"])
def test_cell_entry_resolves_to_its_files(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(entry[k]) for k in ("name", "config", "traffic")) and _line(entry["why"])
    assert entry["chips"] in (1, 4)
    assert entry["config"] in {c["name"] for c in BENCH["configs"]}
    traffic = _load(f"chipbench/traffic/{entry['traffic']}.json")
    spec = _load(f"chipbench/workloads/{entry['name']}.json")
    assert traffic["kind"] in ("requests", "batches") and "why" in traffic
    assert os.path.exists(os.path.join(REPO, "chipbench", "drivers", spec["driver"] + ".py"))
    assert {"driver", "correct", "controls", "why"} <= set(spec)
    assert (spec["driver"] == "serve") == (traffic["kind"] == "requests")


def test_names_are_unique_and_pairs_appear_once():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len({c["file"] for c in BENCH["configs"]}) == len(BENCH["configs"])


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("metric", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


def test_setup_s_is_reported_everywhere_and_each_cell_has_another_metric():
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1
    for cell in CELLS:
        others = [m for m in BENCH["end_to_end"] if m["name"] != "setup_s" and cell in m.get("workloads", CELLS)]
        assert others, f"{cell} reports no end-to-end metric besides setup_s"


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_a_metric_its_cells_report_and_has_a_reader(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"]) and _line(metric["layer"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    moved = next((m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]), None)
    assert moved is not None and metric["moves"] != "setup_s"
    reporting = set(moved.get("workloads", CELLS))
    assert set(metric.get("workloads", reporting)) <= reporting
    from chipbench import harness

    assert callable(harness.load_reader(metric["name"]).read)


def test_every_cell_reports_a_per_layer_metric_and_layers_are_perf_mds():
    sys.path.insert(0, REPO)
    from chipbench import harness

    perf = open(os.path.join(REPO, "PERF.md")).read()
    for cell in CELLS:
        assert harness.Cell(cell).per_layer, f"{cell} reports no per-layer metric"
    for layer in {m["layer"] for m in BENCH["per_layer"]}:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_peaks_name_their_source_and_reject_an_unknown_device():
    from chipbench import harness

    table = _load("chipbench/peaks.json")
    assert table["TPU v5 lite"]["bf16_flops_per_s"] == 197e12 and table["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all("source" in entry for entry in table.values())
    with pytest.raises(SystemExit, match="not in chipbench/peaks.json"):
        harness.peaks_for("cpu")


@pytest.mark.parametrize("where", ["repo", "benchmark_only"])
def test_the_command_has_no_cpu_mode(where, tmp_path):
    """Without a TPU the command exits non-zero and prints no result line: in the
    repo (JAX finds only the CPU), and in a directory that holds only
    `BENCHMARK.json` and `paths` (the system under test is missing)."""
    import shutil

    cwd = REPO
    if where == "benchmark_only":
        cwd = str(tmp_path)
        shutil.copy(os.path.join(REPO, "BENCHMARK.json"), cwd)
        for path in BENCH["paths"]:
            shutil.copytree(os.path.join(REPO, path), os.path.join(cwd, path),
                            ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable] + BENCH["command"][1:] + ["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)
    assert done.returncode != 0
    assert not [line for line in done.stdout.splitlines() if line.startswith('{"correct"')]
