"""What the engine shows of itself on the host, by family: its instruments
(name, kind, help, labels, buckets), the keys of `stats`, the attribute names of
its recorded spans and of a request's `admitted` / `handed_back` events, and the
counts a family's config puts on a span. Held to `serving_host_surface.json`.

A change that renames, adds or drops any of them on purpose regenerates the file:

    JAX_PLATFORMS=cpu python tests/test_serving_host_surface.py --write
"""

from __future__ import annotations

import json
import os
import sys

if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest

from accelerate_tpu import models
from accelerate_tpu.serving import ContinuousBatcher, Request
from accelerate_tpu.telemetry.flight_recorder import FlightRecorder
from accelerate_tpu.telemetry.tracing import Tracer

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "serving_host_surface.json")

#: case -> (the `models` factory, its tiny preset, engine keywords)
CASES = {
    "gpt_neox": ("create_gpt_neox_model", "gpt_neox_tiny", {}),
    "llama": ("create_llama_model", "llama_tiny", {}),
    "llama_speculative": ("create_llama_model", "llama_tiny", {"speculative": True}),
    "latent_moe": ("create_latent_moe_model", "latent_moe_tiny", {}),
    "latent_moe_hc": ("create_latent_moe_model", "latent_moe_hc_tiny", {}),
    "olmo_hybrid": ("create_olmo_hybrid_model", "olmo_hybrid_tiny", {}),
    "falcon_h1": ("create_falcon_h1_model", "falcon_h1_tiny", {}),
}

#: Span attributes that are counts (of the family, of the traffic): compared by value.
COUNTED = {
    "serve.insert": ("bucket", "suffix_tokens", "prefix_hit_pages", "head_rows", "routed_pairs", "hc_streams",
                     "hc_rows", "scan_chunks", "attn_key_blocks", "attn_key_blocks_window"),
    "serve.decode_chunk": ("chunk_size", "active_slots", "ahead", "live_pages", "window_pages", "read_blocks",
                           "kv_row_values", "hc_streams", "hc_rows", "state_bytes_per_slot", "state_slots",
                           "kv_page_bytes", "tokens_streamed"),
}
COUNTED_STATS = ("kv_bytes_per_token", "state_bytes_per_slot", "residual_streams", "pages_total", "inserts",
                 "chunks", "decode_steps", "attention_impl", "weight_dtype", "kv_cache_dtype", "tp")
COUNTED_GAUGES = ("serving_residual_streams", "serving_kv_bytes_per_token", "serving_state_bytes_per_slot",
                  "serving_pages_total")


def _keys(value):
    """The nested key structure of a `stats` view."""
    if isinstance(value, dict):
        return {k: _keys(v) for k, v in sorted(value.items())}
    return None


def surface(case: str) -> dict:
    create, tiny, engine = CASES[case]
    model = getattr(models, create)(getattr(models, tiny)(), seq_len=32)
    recorder = FlightRecorder()
    batcher = ContinuousBatcher(model, num_slots=2, max_length=64, chunk_size=4,
                                tracer=Tracer(recorder=recorder), **engine)
    rng = np.random.default_rng(0)
    vocab = model.module.config.vocab_size
    # Three requests over two slots: the third waits, so a step runs ahead.
    batcher.run([Request(i, rng.integers(1, vocab, size=n), max_new_tokens=6)
                 for i, n in enumerate((5, 12, 20))])
    spans = [r for r in recorder.records() if r.get("kind") == "span"]
    instruments = []
    for inst in batcher.metrics.instruments():
        entry = {"name": inst.name, "kind": inst.kind, "labels": inst.label_dict, "help": inst.help}
        if inst.kind == "histogram":
            entry["buckets"] = [float(b) for b in inst.bucket_bounds]
        instruments.append(entry)
    out = {
        "instruments": instruments,
        "stats_keys": _keys(batcher.stats),
        "stats_counts": {k: batcher.stats[k] for k in COUNTED_STATS if k in batcher.stats},
        "gauges": {name: batcher.metrics.value(name) for name in COUNTED_GAUGES},
        "span_attrs": {}, "event_attrs": {}, "span_counts": {},
    }
    for name in ("serve.step", "serve.insert", "serve.decode_chunk", "serve.request"):
        out["span_attrs"][name] = sorted({k for r in spans if r["name"] == name for k in r["attrs"]})
    for name in ("submitted", "admitted", "first_token", "handed_back"):
        out["event_attrs"][name] = sorted({k for r in spans if r["name"] == "serve.request"
                                           for e in r.get("events", ()) if e["name"] == name for k in e["attrs"]})
    for name, counted in COUNTED.items():
        out["span_counts"][name] = [{k: r["attrs"][k] for k in counted if k in r["attrs"]}
                                    for r in spans if r["name"] == name]
    return json.loads(json.dumps(out))  # tuples and numpy scalars as the file holds them


@pytest.mark.parametrize("case", list(CASES))
def test_the_engines_host_surface_is_the_recorded_one(case):
    with open(GOLDEN) as f:
        table = json.load(f)
    golden = dict(table["cases"][case])
    golden["instruments"] = table["instruments"][golden.pop("instruments_of")]
    got = surface(case)
    for part, expected in golden.items():
        assert got[part] == expected, (
            f"{case}: `{part}` of the engine's host surface moved; where that is meant, regenerate with "
            "`JAX_PLATFORMS=cpu python tests/test_serving_host_surface.py --write`")


if __name__ == "__main__":
    # The instruments are the same table for every engine of one kind: kept once.
    table = {"instruments": {}, "cases": {case: surface(case) for case in CASES}}
    for case, entry in table["cases"].items():
        kind = "speculative" if CASES[case][2].get("speculative") else "plain"
        assert table["instruments"].setdefault(kind, entry["instruments"]) == entry.pop("instruments"), case
        entry["instruments_of"] = kind
    if "--write" in sys.argv:
        with open(GOLDEN, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {GOLDEN}")
    else:
        print(json.dumps(table, indent=1, sort_keys=True))
