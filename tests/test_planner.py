"""The sharding-strategy planner (parallel/planner.py): the cost-model search
that replaces the hand-written partition tables as the SOURCE of sharding
decisions (`sharding_rules="auto"`), with the family tables demoted to parity
oracles.

The acceptance pins:

  - **legality** — every candidate spec the enumerator returns passes the
    same `_check_tp_divisible` gate placement enforces (a planner choice can
    never hit the indivisible-rule hard error);
  - **cost-model sanity** — per-chip bytes never exceed the replicated
    footprint, and modeled cost is non-increasing in mesh size for nets whose
    dims shard cleanly;
  - **planner-vs-hand parity** — on llama + gpt_neox at tp in {2, 4} the auto
    plan matches or beats the hand tables on modeled cost, and the auto
    ENGINE reproduces hand-rule greedy tokens exactly at 0 recompiles /
    0 host transfers with decode compiled once;
  - **round-trip** — the emitted rules table feeds
    `derive_tp_param_shardings` unchanged, and predicted per-chip bytes match
    the live `tree_device_nbytes` within 10% on the forced CPU mesh;
  - **measure-and-refine** — `refine_plans` returns the measured-best of the
    top-k candidates (cost model proposes, hardware disposes).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from accelerate_tpu.models.gpt_neox import (
    GPT_NEOX_SHARDING_RULES,
    GPTNeoXConfig,
    create_gpt_neox_model,
)
from accelerate_tpu.models.llama import LLAMA_SHARDING_RULES, LlamaConfig, create_llama_model
from accelerate_tpu.parallel.planner import (
    Workload,
    candidate_specs,
    emit_rules,
    measure_forward_step,
    plan_serving_sharding,
    plan_sharding,
    refine_plans,
    resolve_sharding_rules,
    score_rules,
)
from accelerate_tpu.parallel.sharding import (
    _check_tp_divisible,
    derive_tp_param_shardings,
    serving_tp_mesh,
    tree_device_nbytes,
    tree_paths_and_leaves,
)
from accelerate_tpu.serving import ContinuousBatcher, Request

pytestmark = pytest.mark.planner

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


_MODELS = {"llama": (tiny_llama, LLAMA_SHARDING_RULES), "gpt_neox": (tiny_neox, GPT_NEOX_SHARDING_RULES)}
_CACHE = {}


def get_model(family):
    if family not in _CACHE:
        _CACHE[family] = _MODELS[family][0]()
    return _CACHE[family]


def make_requests(n=4, max_new=8):
    return [
        Request(i, list(range(3 + i, 10 + i)) + [2, 5, 2, 5], max_new_tokens=max_new)
        for i in range(n)
    ]


def wide_net(hidden=256, vocab=4096, inter=1024, layers=2):
    """A cleanly-shardable transformer-shaped params tree (plain numpy — the
    planner only reads shapes/dtypes), wide enough that weight bytes dominate
    activation collectives at every mesh size under test."""
    z = lambda *shape: np.zeros(shape, np.float32)
    params = {"embed_tokens": {"embedding": z(vocab, hidden)}}
    for i in range(layers):
        params[f"layer_{i}"] = {
            "attention": {
                "wq": {"kernel": z(hidden, hidden)},
                "wk": {"kernel": z(hidden, hidden)},
                "wv": {"kernel": z(hidden, hidden)},
                "wo": {"kernel": z(hidden, hidden)},
            },
            "mlp": {
                "w_up": {"kernel": z(hidden, inter)},
                "w_down": {"kernel": z(inter, hidden)},
            },
            "norm": {"scale": z(hidden)},
        }
    params["lm_head"] = {"kernel": z(hidden, vocab)}
    return {"params": params}


# ------------------------------------------------------------------ legality
@needs_mesh
def test_candidate_specs_divisibility_property():
    """Property sweep: every candidate the enumerator returns passes the
    placement-time divisibility gate; every divisible single-axis placement
    IS enumerated; 1-D leaves only replicate."""
    rng = np.random.default_rng(0)
    mesh = serving_tp_mesh(4)
    dims = [1, 2, 3, 4, 6, 8, 12, 16, 31, 64, 96]
    for _ in range(200):
        ndim = int(rng.integers(1, 4))
        shape = tuple(int(rng.choice(dims)) for _ in range(ndim))
        cands = candidate_specs("params/x/kernel", shape, mesh, axes=("model",))
        assert () in cands  # replicate is always legal
        for spec in cands:
            _check_tp_divisible("params/x/kernel", shape, spec, mesh)  # must not raise
        if ndim == 1:
            assert cands == [()]
            continue
        for dim, d in enumerate(shape):
            # Full-rank specs, trailing Nones kept: (model, None) not
            # (model,) — the quantized-scale derivation reads the LAST entry
            # as the kernel's output axis.
            expect = [None] * ndim
            expect[dim] = "model"
            if d % 4 == 0 and d >= 4:
                assert tuple(expect) in cands, (shape, dim)
            else:
                assert tuple(expect) not in cands, (shape, dim)


def test_emit_rules_suffix_grouping_and_conflicts():
    """Same-suffix leaves that agree collapse into one (^|/)suffix(/|$) rule;
    a conflicting suffix falls back to full-path rules emitted FIRST so
    first-match-wins keeps them authoritative; replicated leaves get no rule."""
    assignment = {
        "params/layer_0/attention/wq/kernel": (None, "model"),
        "params/layer_1/attention/wq/kernel": (None, "model"),
        "params/layer_0/norm/scale": (),
        "params/a/odd/kernel": ("model",),
        "params/b/odd/kernel": (),
    }
    rules = emit_rules(assignment)
    patterns = [p for p, _ in rules]
    assert "(^|/)wq/kernel(/|$)" in patterns
    assert not any("norm" in p for p in patterns)
    # the conflicting "odd/kernel" suffix: exact rule for the sharded leaf
    # only, and it precedes the grouped rules.
    assert patterns[0].startswith("^params/a/odd/kernel")
    assert not any(p == "(^|/)odd/kernel(/|$)" for p in patterns)
    # the emitted shapes feed re.search-based matching: the quantized
    # {"q","scale"} children of a kernel keep matching their kernel's rule.
    import re

    assert re.search("(^|/)wq/kernel(/|$)", "params/layer_0/attention/wq/kernel/q")


def test_resolve_sharding_rules_seam():
    mesh = {"model": 2}
    params = wide_net(hidden=32, vocab=64, inter=64, layers=1)
    rules, plan = resolve_sharding_rules("auto", params, mesh)
    assert plan is not None and rules == plan.rules and rules
    explicit = [("wq/kernel", (None, "model"))]
    assert resolve_sharding_rules(explicit, params, mesh) == (explicit, None)
    assert resolve_sharding_rules(None, params, mesh) == (None, None)
    assert resolve_sharding_rules("rules", params, mesh) == (None, None)
    with pytest.raises(ValueError, match="auto"):
        resolve_sharding_rules("magic", params, mesh)


# ---------------------------------------------------------------- cost model
def test_cost_model_bytes_and_mesh_monotonicity():
    """Per-chip bytes never exceed the replicated footprint (and land within
    [total/N, total]); modeled cost is non-increasing in mesh size for a
    cleanly-shardable net — more chips never price WORSE, because
    replicate-everything is always in the candidate set."""
    params = wide_net()
    total = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(np.asarray, params)
        )
    )
    costs = []
    for n in (1, 2, 4, 8):
        plan = plan_sharding(params, {"model": n}, workload=Workload(batch=4, seq=1))
        assert plan.cost.per_chip_param_bytes <= total * (1 + 1e-9)
        assert plan.cost.per_chip_param_bytes >= total / n * (1 - 1e-9)
        costs.append(plan.cost.total)
    for prev, nxt in zip(costs, costs[1:]):
        assert nxt <= prev * (1 + 1e-9), costs


def test_cost_model_prices_optimizer_state_and_kv_pool():
    params = wide_net(hidden=64, vocab=256, inter=128, layers=1)
    lean = plan_sharding(params, {"model": 2}, workload=Workload(batch=2))
    heavy = plan_sharding(
        params, {"model": 2},
        workload=Workload(batch=2, kv_pool_bytes=1 << 20, opt_bytes_per_param=8.0),
    )
    assert heavy.cost.per_chip_kv_bytes == (1 << 20) / 2
    assert heavy.cost.per_chip_opt_bytes > 0 == lean.cost.per_chip_opt_bytes
    assert heavy.cost.per_chip_total_bytes > lean.cost.per_chip_total_bytes


# ------------------------------------------------------- planner vs the hand
@pytest.mark.parametrize("family", ["llama", "gpt_neox"])
@pytest.mark.parametrize("tp", [2, 4])
def test_auto_plan_matches_or_beats_hand_rules_on_modeled_cost(family, tp):
    """The headline: on llama + gpt_neox at tp in {2,4}, the auto plan's
    modeled cost never exceeds the hand table's under the same cost model —
    and it shards at least as many leaves (no silent replication the hand
    rules would have caught). Abstract mesh: no devices needed."""
    model = get_model(family)
    hand_rules = _MODELS[family][1]
    cfg = model.module.config
    mesh = {"model": tp}
    plan = plan_serving_sharding(
        model.params, mesh, cfg,
        num_slots=2, page_size=4, num_pages=33,
    )
    hand = score_rules(model.params, mesh, hand_rules, workload=plan.workload)
    assert plan.cost.total <= hand.cost.total * (1 + 1e-9), (
        family, tp, plan.cost.total, hand.cost.total
    )
    auto_sharded = sum(1 for l in plan.leaves if l.spec)
    hand_sharded = sum(1 for l in hand.leaves if l.spec)
    assert auto_sharded >= hand_sharded, (auto_sharded, hand_sharded)


@needs_mesh
@pytest.mark.parametrize("family,tp", [("llama", 2), ("gpt_neox", 2), ("gpt_neox", 4)])
def test_auto_engine_token_parity_and_discipline(family, tp):
    """sharding_rules="auto" end to end: greedy tokens IDENTICAL to the
    hand-ruled engine (tp divides each family's KV heads in this matrix: the
    llama tiny config has 2, gpt_neox 4), ONE decode executable across mixed
    admissions, and a warm engine's steady state at 0 recompiles / 0 guarded
    host transfers."""
    from accelerate_tpu.analysis import TraceGuard

    model = get_model(family)
    hand = ContinuousBatcher(model, num_slots=2, chunk_size=4, page_size=4, tp=tp)
    base = hand.run(make_requests())
    auto = ContinuousBatcher(
        model, num_slots=2, chunk_size=4, page_size=4, tp=tp, sharding_rules="auto"
    )
    auto.warm_inserts()
    out = auto.run(make_requests())
    assert set(out) == set(base)
    for rid in base:
        assert np.array_equal(base[rid], out[rid]), (family, tp, rid)
    assert auto.trace_counts["decode_chunk"] == 1, auto.trace_counts
    with TraceGuard(name=f"planner-steady-{family}-tp{tp}") as guard:
        auto.run(
            [Request(100 + i, list(range(2 + i, 12 + i)), max_new_tokens=6) for i in range(4)]
        )
    assert guard.total_recompiles == 0 and guard.host_transfers == 0, guard.report().summary()
    assert auto.trace_counts["decode_chunk"] == 1


@needs_mesh
@pytest.mark.parametrize("weight_dtype", ["bf16", "int8"])
def test_round_trip_rules_and_predicted_bytes(weight_dtype):
    """The plan round-trip: the emitted table feeds
    `derive_tp_param_shardings` UNCHANGED and reproduces the engine's live
    placements leaf for leaf; predicted per-chip param bytes match the live
    `tree_device_nbytes` within 10% (exactly, in practice, on the CPU mesh —
    including the int8 engines, whose quantized {"q","scale"} entries the
    cost model prices explicitly)."""
    model = get_model("llama")
    engine = ContinuousBatcher(
        model, num_slots=2, chunk_size=4, page_size=4, tp=2,
        sharding_rules="auto", weight_dtype=weight_dtype,
    )
    plan = engine.sharding_plan
    assert plan is not None and plan.rules

    # emitted rules -> derive_tp_param_shardings, byte-compatible with the
    # engine's own placement (same seam, same table).
    shardings = derive_tp_param_shardings(engine.params, engine.mesh, plan.rules)
    flat_live, _ = tree_paths_and_leaves(engine.params)
    flat_derived, _ = tree_paths_and_leaves(shardings)
    for (path, leaf), (dpath, derived) in zip(flat_live, flat_derived):
        assert path == dpath
        assert leaf.sharding.spec == derived.spec, (path, leaf.sharding.spec, derived.spec)

    if weight_dtype == "int8":
        # The quantized-entry contract (PR 13/14): `q` shards like its
        # kernel; the per-output-channel `scale` follows the kernel's OUTPUT
        # dim — so the planner's row-parallel rules MUST keep their trailing
        # None ((model, None), not (model,)) or wo/w_down scales would shard.
        report = engine.tp_sharding_report()["params"]
        col = [p for p in report if p.endswith("wq/kernel/scale")]
        row = [p for p in report if p.endswith(("wo/kernel/scale", "w_down/kernel/scale"))]
        assert col and row
        for path in col:
            assert "model" in report[path], (path, report[path])
        for path in row:
            assert "model" not in report[path], (path, report[path])

    device = engine.mesh.devices.flat[0]
    live = tree_device_nbytes(engine.params, device)
    predicted = plan.cost.per_chip_param_bytes
    assert abs(predicted - live) / live <= 0.10, (predicted, live)

    # the 60%-of-ideal footprint floor the bench asserts, pinned here too.
    replicated = sum(
        int(np.prod(np.shape(l))) * np.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(engine.params)
    )
    assert replicated / live >= 1.0 + 0.6 * (2 - 1)


# ------------------------------------------------------------------ CLI seam
def test_plan_cli_text_and_json(capsys):
    """`accelerate-tpu plan` end to end (device-free eval_shape path): the
    text report carries the rules table and predictions, the --json payload
    round-trips with the auto-vs-hand comparison."""
    import json

    from accelerate_tpu.commands.accelerate_cli import get_command_parser

    parser = get_command_parser()
    args = parser.parse_args(["plan", "llama-tiny", "--tp", "2"])
    args.func(args)
    out = capsys.readouterr().out
    assert "emitted rules table" in out and "predicted per-chip HBM" in out
    assert "matches or beats" in out

    args = parser.parse_args(["plan", "gpt-neox-tiny", "--tp", "4", "--json"])
    args.func(args)
    payload = json.loads(capsys.readouterr().out)
    assert payload["auto_beats_hand"] is True
    assert payload["plan"]["rules"] and payload["plan"]["predicted"]["per_chip_param_bytes"] > 0
    assert payload["plan"]["mesh_axes"] == {"model": 4}


@needs_mesh
def test_plan_cli_refine_measures(capsys):
    """--refine-top-k on the live mesh: measurements are reported and the
    chosen plan carries a measured step time (K=1 still measures)."""
    import json

    from accelerate_tpu.commands.accelerate_cli import get_command_parser

    parser = get_command_parser()
    args = parser.parse_args(["plan", "llama-tiny", "--tp", "2", "--refine-top-k", "2", "--json"])
    args.func(args)
    payload = json.loads(capsys.readouterr().out)
    measured = payload["refine_measurements_s"]
    assert len(measured) >= 1 and all(s > 0 for s in measured)
    assert payload["plan"]["measured_step_s"] == min(measured)


@needs_mesh
def test_engine_refine_kwarg_measures_and_holds_parity():
    """ContinuousBatcher(sharding_rules="auto", sharding_refine_top_k=K):
    the engine's plan is the measured-best candidate (measured_step_s
    stamped) and decode stays token-identical to the hand-ruled engine."""
    model = get_model("llama")
    engine = ContinuousBatcher(
        model, num_slots=2, chunk_size=4, page_size=4, tp=2,
        sharding_rules="auto", sharding_refine_top_k=2,
    )
    assert engine.sharding_plan is not None
    assert engine.sharding_plan.measured_step_s is not None
    base = ContinuousBatcher(model, num_slots=2, chunk_size=4, page_size=4, tp=1).run(
        make_requests()
    )
    out = engine.run(make_requests())
    for rid in base:
        assert np.array_equal(base[rid], out[rid]), rid


# --------------------------------------------------------- measure-and-refine
def test_refine_picks_measured_best_mechanics():
    """Selection is by MEASURED time, not modeled cost: with a measure_fn
    that inverts the model's ranking, refine returns the model's worst."""
    params = wide_net(hidden=64, vocab=256, inter=128, layers=1)
    plans = plan_sharding(params, {"model": 2}, workload=Workload(batch=2), top_k=3)
    assert len(plans) >= 2
    modeled_order = sorted(range(len(plans)), key=lambda i: plans[i].cost.total)
    times = {id(p): float(len(plans) - rank) for rank, i in enumerate(modeled_order) for p in [plans[i]]}
    best, measured = refine_plans(plans, lambda p: times[id(p)])
    assert len(measured) == len(plans)
    assert best is plans[modeled_order[-1]]  # the modeled-worst measured fastest
    assert best.measured_step_s == min(t for _, t in measured)


@needs_mesh
def test_refine_measures_real_forwards_on_cpu_mesh():
    """measure-and-refine against the real backend: each top-k candidate's
    params are placed by its rules on the forced 8-device CPU mesh, a
    one-token forward compiles and times, and the returned best is the
    measured argmin."""
    model = get_model("llama")
    cfg = model.module.config
    mesh = serving_tp_mesh(2)
    plans = plan_serving_sharding(
        model.params, mesh, cfg,
        num_slots=2, page_size=4, num_pages=33,
        top_k=3,
    )
    assert len(plans) >= 2
    best, measured = refine_plans(
        plans,
        lambda p: measure_forward_step(model.apply_fn, model.params, mesh, p.rules, batch=1),
    )
    assert all(seconds > 0 for _, seconds in measured)
    assert best.measured_step_s == min(seconds for _, seconds in measured)
    assert best in plans
