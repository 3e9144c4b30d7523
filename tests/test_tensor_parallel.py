"""Tensor-parallel decode: one `ContinuousBatcher` spanning a forced
multi-device CPU mesh (`tests/conftest.py` exports
``--xla_force_host_platform_device_count=8``, the same harness the
`parallel/mesh.py` tests use).

The acceptance pins:

  - **token parity** — greedy decode is token-IDENTICAL tp==N vs tp==1
    across {llama, gpt_neox} x {speculative on/off} x
    {bf16, int8, fp8 KV}: GSPMD partitioning is a layout change, never a numerics
    change (and the Pallas page-walk kernels, shard_mapped over the KV-head
    grid, hold the same identity);
  - **compiled-once discipline** — the ONE decode executable survives mixed
    admissions with sharded operands, and a warm engine's steady state is 0
    recompiles / 0 guarded host transfers under TraceGuard;
  - **sharding audit** — every rule-matched weight leaf and every KV pool
    leaf carries the "model" axis in its LIVE sharding (no silent full
    replication — TPU118's runtime complement), scalars/page-tables stay
    replicated, and per-chip weight+pool bytes drop ~1/N;
  - **composition** — `router.Router` treats a mesh-spanning engine as one
    replica: disjoint TP device groups per replica, rolling `swap_weights`
    re-sharding at the engine's params setter.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from accelerate_tpu.models.gpt_neox import GPTNeoXConfig, create_gpt_neox_model
from accelerate_tpu.models.llama import LlamaConfig, create_llama_model
from accelerate_tpu.serving import ContinuousBatcher, Request

pytestmark = pytest.mark.tp

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs a >= 4-device mesh (forced CPU devices)"
)


def tiny_llama():
    return create_llama_model(
        LlamaConfig(
            vocab_size=512, hidden_size=128, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rope_theta=10000.0,
        ),
        seq_len=32,
    )


def tiny_neox():
    return create_gpt_neox_model(
        GPTNeoXConfig(
            vocab_size=512, hidden_size=128, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=64,
        ),
        seq_len=32,
    )


_MODELS = {"llama": tiny_llama, "gpt_neox": tiny_neox}
_MODEL_CACHE = {}


def get_model(family):
    if family not in _MODEL_CACHE:
        _MODEL_CACHE[family] = _MODELS[family]()
    return _MODEL_CACHE[family]


def make_requests(n=4, max_new=8):
    return [
        Request(i, list(range(3 + i, 10 + i)) + [2, 5, 2, 5], max_new_tokens=max_new)
        for i in range(n)
    ]


def run_engine(model, tp, **kwargs):
    engine = ContinuousBatcher(model, num_slots=2, chunk_size=4, tp=tp, **kwargs)
    out = engine.run(make_requests())
    return engine, out


def assert_parity(a, b, tag=""):
    assert set(a) == set(b)
    for rid in a:
        assert np.array_equal(a[rid], b[rid]), (tag, rid, a[rid], b[rid])


# --------------------------------------------------------------------- parity
@needs_mesh
@pytest.mark.parametrize("family", ["llama", "gpt_neox"])
@pytest.mark.parametrize(
    "variant",
    [
        {"page_size": 4},
        {"page_size": 4, "speculative": True, "draft_tokens": 3},
        {"page_size": 4, "kv_cache_dtype": "int8"},
        {"page_size": 4, "kv_cache_dtype": "int8", "speculative": True, "draft_tokens": 3},
        {"page_size": 4, "kv_cache_dtype": "fp8_e4m3"},
        {"page_size": 4, "kv_cache_dtype": "fp8_e4m3", "speculative": True, "draft_tokens": 3},
    ],
    ids=["paged", "paged-spec", "int8kv", "int8kv-spec", "fp8kv", "fp8kv-spec"],
)
def test_tp_token_parity(family, variant):
    """Greedy decode tp==2 vs tp==1: token-identical across the whole
    {family} x {speculative} x {kv dtype} matrix."""
    model = get_model(family)
    _, base = run_engine(model, tp=1, **variant)
    _, spanned = run_engine(model, tp=2, **variant)
    assert_parity(base, spanned, tag=(family, variant))


@needs_mesh
def test_tp4_parity_across_families():
    """tp=4 (one KV head... per shard for gpt_neox; llama's 2 KV heads split
    further constraints, so llama runs tp=2 and neox the full tp=4): deeper
    submeshes hold the same identity."""
    neox = get_model("gpt_neox")
    _, base = run_engine(neox, tp=1, page_size=4)
    _, spanned = run_engine(neox, tp=4, page_size=4)
    assert_parity(base, spanned, tag="neox-tp4")


@needs_mesh
def test_tp_parity_pallas_kernels():
    """The fused page-walk kernels under shard_map over the KV-head grid
    (interpret mode on CPU) match the tp=1 kernel path token for token —
    and so does the speculative verify kernel."""
    model = get_model("llama")
    for variant in (
        {"page_size": 4, "attention_impl": "pallas_paged"},
        {"page_size": 4, "attention_impl": "pallas_paged", "speculative": True, "draft_tokens": 3},
        {"page_size": 4, "attention_impl": "pallas_paged", "kv_cache_dtype": "int8"},
    ):
        _, base = run_engine(model, tp=1, **variant)
        _, spanned = run_engine(model, tp=2, **variant)
        assert_parity(base, spanned, tag=("pallas", variant))


@needs_mesh
def test_tp_int8_weights_parity():
    """int8 weight-only quantization composes: the quantized {"q", "scale"}
    entries shard by their kernel's Megatron rule and decode stays
    token-identical to the single-device int8 engine."""
    model = get_model("llama")
    _, base = run_engine(model, tp=1, page_size=4, weight_dtype="int8")
    _, spanned = run_engine(model, tp=2, page_size=4, weight_dtype="int8")
    assert_parity(base, spanned, tag="int8-weights")


# ----------------------------------------------------------------- discipline
@needs_mesh
def test_tp_decode_compiled_once_and_zero_recompiles():
    """The compiled-once pin with sharded operands: one decode executable
    across mixed admissions, and a warm engine's steady state is 0
    recompiles / 0 guarded host transfers under an armed TraceGuard."""
    from accelerate_tpu.analysis import TraceGuard

    model = get_model("llama")
    engine = ContinuousBatcher(model, num_slots=2, chunk_size=4, page_size=4, tp=2)
    engine.warm_inserts()
    engine.run(make_requests())
    assert engine.trace_counts["decode_chunk"] == 1, engine.trace_counts
    inserts_before = engine.trace_counts["insert"]
    with TraceGuard(name="tp-steady") as guard:
        engine.run(
            [Request(100 + i, list(range(2 + i, 12 + i)), max_new_tokens=6) for i in range(4)]
        )
    assert guard.total_recompiles == 0 and guard.host_transfers == 0, guard.report().summary()
    assert engine.trace_counts["decode_chunk"] == 1
    assert engine.trace_counts["insert"] == inserts_before  # warm ladder held


# -------------------------------------------------------------- sharding audit
@needs_mesh
def test_tp_sharding_audit_no_unintended_replication():
    """Per-leaf audit off the LIVE arrays: every rule-matched kernel leaf and
    every KV pool leaf carries the "model" axis, scalars replicate, and the
    per-chip weight+pool footprint drops ~1/2 at tp=2."""
    model = get_model("llama")
    base = ContinuousBatcher(model, num_slots=2, chunk_size=4, page_size=4, tp=1)
    engine = ContinuousBatcher(model, num_slots=2, chunk_size=4, page_size=4, tp=2)
    report = engine.tp_sharding_report()

    sharded_kernels = [
        path for path, spec in report["params"].items()
        if "kernel" in path or "embedding" in path
    ]
    assert sharded_kernels, "no weight leaves found"
    for path in sharded_kernels:
        assert "model" in report["params"][path], (path, report["params"][path])
    # Norm scales replicate (no rule matches them).
    norm_leaves = [p for p in report["params"] if "norm" in p]
    assert norm_leaves
    for path in norm_leaves:
        assert "model" not in report["params"][path], (path, report["params"][path])

    for path, spec in report["cache"].items():
        leaf = path.rsplit("/", 1)[-1]
        if leaf in ("cached_key", "cached_value", "key_scale", "value_scale"):
            assert "model" in spec, (path, spec)
        else:
            assert "model" not in spec, (path, spec)

    ratio = (base.per_device_weight_nbytes + base.per_device_kv_cache_nbytes) / (
        engine.per_device_weight_nbytes + engine.per_device_kv_cache_nbytes
    )
    assert ratio >= 1.6, f"per-chip footprint only dropped {ratio:.2f}x at tp=2"


@needs_mesh
def test_tp_quantized_scale_leaves_follow_kernel_rule():
    """Quantized {"q", "scale"} entries: `q` shards exactly like the kernel it
    replaced; the per-output-channel `scale` vector follows the kernel's
    OUTPUT dim — sharded for column-parallel (wq/w_gate), replicated for
    row-parallel (wo/w_down)."""
    model = get_model("llama")
    engine = ContinuousBatcher(
        model, num_slots=2, chunk_size=4, page_size=4, tp=2, weight_dtype="int8"
    )
    params = engine.tp_sharding_report()["params"]
    col = [p for p in params if p.endswith("wq/kernel/scale")]
    row = [p for p in params if p.endswith("wo/kernel/scale")]
    assert col and row
    for path in col:
        assert "model" in params[path], (path, params[path])
    for path in row:
        assert "model" not in params[path], (path, params[path])
    for path in [p for p in params if p.endswith("kernel/q")]:
        assert "model" in params[path], (path, params[path])


@needs_mesh
def test_tp_blast_radius_rebuilds_sharded_pools():
    """The donated-cache rebuild (`_abort_in_flight`) must reconstruct the
    pools SHARDED on the submesh — a replicated rebuild would keep serving
    correct tokens at N x the per-chip HBM."""
    model = get_model("llama")
    engine = ContinuousBatcher(model, num_slots=2, chunk_size=4, page_size=4, tp=2)
    engine.run(make_requests(n=2))
    engine._abort_in_flight(RuntimeError("synthetic blast radius"))
    for path, spec in engine.tp_sharding_report()["cache"].items():
        if path.rsplit("/", 1)[-1] in ("cached_key", "cached_value"):
            assert "model" in spec, (path, spec)
    # ...and the rebuilt engine still serves, token-identically.
    probes = [
        Request(200 + i, list(range(3 + i, 10 + i)) + [2, 5, 2, 5], max_new_tokens=8)
        for i in range(2)
    ]
    out = engine.run(probes)
    _, base = run_engine(model, tp=1, page_size=4)
    for i in range(2):
        assert np.array_equal(out[200 + i], base[i])


# ----------------------------------------------------------------- validation
@needs_mesh
def test_tp_validation_errors():
    model = get_model("llama")
    with pytest.raises(ValueError, match="KV head"):
        ContinuousBatcher(model, num_slots=2, tp=4, page_size=4)  # 2 KV heads % 4
    with pytest.raises(ValueError):
        ContinuousBatcher(model, num_slots=2, tp=0, page_size=4)
    import dataclasses

    bare = dataclasses.replace(model, sharding_rules=None)
    with pytest.raises(ValueError, match="sharding_rules"):
        ContinuousBatcher(bare, num_slots=2, tp=2, page_size=4)


@needs_mesh
def test_tp_swap_weights_reshards_at_setter():
    """The one-seam params setter: assigning raw params to a TP engine lands
    them sharded (the rolling-deploy path), and decode continues
    token-identically after the swap."""
    model = get_model("llama")
    engine = ContinuousBatcher(model, num_slots=2, chunk_size=4, page_size=4, tp=2)
    before = engine.run(make_requests(n=2))
    engine.params = model.params  # raw tree, as swap_weights hands it over
    for path, spec in engine.tp_sharding_report()["params"].items():
        if path.endswith("wq/kernel"):
            assert "model" in spec, (path, spec)
    after = engine.run(
        [Request(50 + i, list(range(3 + i, 10 + i)) + [2, 5, 2, 5], max_new_tokens=8) for i in range(2)]
    )
    for i in range(2):
        assert np.array_equal(before[i], after[50 + i])


# ----------------------------------------------------------------- composition
@needs_mesh
@pytest.mark.router
def test_router_over_tp_engines_smoke():
    """A mesh-spanning engine is ONE replica: the fleet assigns disjoint TP
    device groups per replica, serves and drains normally, and the rolling
    `swap_weights` re-shards at each engine's params setter."""
    from accelerate_tpu.router import Router

    model = get_model("llama")
    router = Router(
        model, replicas=2, max_queue=8, default_deadline_s=60.0,
        num_slots=2, chunk_size=4, page_size=4, tp=2,
    )
    try:
        groups = [
            tuple(d.id for d in replica.engine.mesh.devices.flat)
            for replica in router.replica_set.replicas
        ]
        assert len(set(groups)) == len(groups), f"TP groups overlap: {groups}"
        for i in range(6):
            router.submit(Request(i, list(range(3 + i, 10 + i)), max_new_tokens=6))
        while router.pending:
            router.step()
        assert all(
            r.finished and r.finish_reason in ("eos", "length")
            for r in router.results.values()
        )
        router.swap_weights(model.params)
        assert all(not rep.dead for rep in router.replica_set.replicas)
    finally:
        router.close()
