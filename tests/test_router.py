"""Replicated serving fleet tests (router.Router / ReplicaSet).

Pins the front-end's load-bearing contracts:

  1. greedy outputs through the fleet are token-identical to the static
     `Generator` path (routing adds scheduling, never different math);
  2. cancel() and per-request deadlines PROPAGATE to the owning replica and
     produce the same terminal finish_reason as the single-engine path;
  3. a replica failure re-dispatches only never-streamed requests — a request
     that already emitted tokens surfaces `finish_reason="replica_lost"`,
     never a duplicated stream;
  4. the health machine ejects a dead replica, never routes to it while
     ejected, and rejoins it through cooldown + probation;
  5. `swap_weights` rolls the fleet one replica at a time (capacity >= N-1
     throughout) and post-swap outputs match the NEW weights exactly.
"""

import numpy as np
import pytest

from accelerate_tpu.generation import generate
from accelerate_tpu.models.llama import LlamaConfig, create_llama_model
from accelerate_tpu.router import ROUTER_FINISH_REASONS, ReplicaSet, Router
from accelerate_tpu.serving import FINISH_REASONS, QueueFull, Request

pytestmark = pytest.mark.router


def _model(seed: int = 0):
    import jax

    cfg = LlamaConfig(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=64,
        rope_theta=10000.0,
    )
    return create_llama_model(cfg, rng=jax.random.key(seed), seq_len=32)


def _static_reference(model, prompt, max_new, **kwargs):
    out = np.asarray(generate(model, prompt[None, :], max_new_tokens=max_new, **kwargs))
    return out[0, prompt.size:]


def _router(model, **overrides):
    kwargs = dict(
        replicas=2, num_slots=2, max_length=64, chunk_size=4, max_queue=16,
        default_deadline_s=60.0, rejoin_cooldown_s=0.01, probation_steps=1,
        stall_degrade_s=None,
    )
    kwargs.update(overrides)
    return Router(model, **kwargs)


class _ReplicaDeath(BaseException):
    """Stand-in for a worker death escaping the engine (chaos uses InjectedKill)."""


def _kill_replica(router, index):
    """Make replica `index`'s next engine step die like a SIGKILLed worker."""
    engine = router.replica_set.replicas[index].engine

    def dead_step():
        raise _ReplicaDeath(f"replica {index} killed")

    engine.step = dead_step


def test_finish_reason_vocabulary():
    assert set(ROUTER_FINISH_REASONS) == set(FINISH_REASONS) | {"replica_lost"}


def test_greedy_parity_and_least_loaded_spread():
    """Mixed workload over 2 replicas: every output token-identical to the
    static path, and least-loaded routing actually used the whole fleet."""
    model = _model()
    rng = np.random.default_rng(0)
    router = _router(model)
    lengths = [3, 5, 9, 12, 6, 4]
    budgets = [6, 4, 8, 3, 5, 7]
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in lengths]
    outputs = router.run(
        [Request(i, p, max_new_tokens=m) for i, (p, m) in enumerate(zip(prompts, budgets))]
    )
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        np.testing.assert_array_equal(outputs[i], _static_reference(model, p, m))
    assert {entry["replica"] for entry in router.routing_log} == {0, 1}
    reasons = router.stats["finish_reasons"]
    assert reasons["length"] + reasons["eos"] == len(prompts)


def test_cancel_propagates_to_owning_replica():
    """cancel() reaches the replica that owns the request — queued or
    in-flight — and yields the single-engine terminal reason `cancelled`
    (partial tokens kept); the slot is serviceable again afterwards."""
    model = _model()
    rng = np.random.default_rng(1)
    router = _router(model, replicas=2, num_slots=1)
    prompt = rng.integers(1, 128, (4,)).astype(np.int32)
    for i in range(3):  # 2 in flight (one per replica), 1 queued
        router.submit(Request(i, prompt, max_new_tokens=24))
    router.step()
    inflight = next(i for i in range(2) if router.results[i].tokens)
    assert router.cancel(inflight) is True
    assert router.results[inflight].finish_reason == "cancelled"
    assert router.results[inflight].tokens, "partial tokens must be kept"
    assert router.cancel(2) is True  # cancelled while queued: no tokens
    assert router.results[2].finish_reason == "cancelled"
    assert router.results[2].tokens == []
    assert router.cancel(inflight) is False  # already finished
    with pytest.raises(KeyError):
        router.cancel(99)
    # the engine-side attempts are gone: slots free up and new work serves
    router.run()
    outputs = router.run([Request(10, prompt, max_new_tokens=4)])
    np.testing.assert_array_equal(outputs[10], _static_reference(model, prompt, 4))


def test_deadline_propagates_same_reason_as_single_engine():
    """Deadlines ride down to the owning replica's engine (queued requests
    expire without a slot; in-flight ones keep partial tokens) and surface the
    SAME terminal reason as the single-engine path: `timeout`."""
    model = _model()
    rng = np.random.default_rng(2)
    router = _router(model, replicas=2, num_slots=1)
    prompt = rng.integers(1, 128, (4,)).astype(np.int32)
    router.submit(Request(0, prompt, max_new_tokens=4, deadline_s=0.0))  # already expired
    router.submit(Request(1, prompt, max_new_tokens=24))
    router.step()
    # Force the in-flight request's ENGINE-side deadline into the past: the
    # propagation under test is engine-enforced, not router-side bookkeeping.
    tracked = router._tracked[1]
    attempt = next(a for a in tracked["attempts"] if not a["done"])
    engine = router.replica_set.replicas[attempt["replica"]].engine
    assert attempt["engine_id"] in engine._deadlines, "deadline did not reach the replica"
    partial = len(router.results[1].tokens)
    engine._deadlines[attempt["engine_id"]] = 0.0
    router.run()
    assert router.results[0].finish_reason == "timeout"
    assert router.results[0].tokens == []
    assert router.results[1].finish_reason == "timeout"
    assert len(router.results[1].tokens) >= partial  # partials kept
    # default_deadline_s applies when the request carries none
    assert router._tracked and router.default_deadline_s == 60.0


def test_replica_death_redispatches_only_never_streamed():
    """The safe re-dispatch rule: when a replica dies, its streamed request
    surfaces `replica_lost` (tokens kept, not duplicated), its queued/
    never-streamed requests complete on the surviving replica with exact
    greedy parity, and `router_retries_total` counts them."""
    model = _model()
    rng = np.random.default_rng(3)
    router = _router(model, replicas=2, num_slots=1, max_retries=2)
    prompts = [rng.integers(1, 128, (4 + i,)).astype(np.int32) for i in range(4)]
    for i, p in enumerate(prompts):
        router.submit(Request(i, p, max_new_tokens=10))
    router.step()  # 0 and 1 in flight (one per replica); 2, 3 queued
    router.step()  # with a queue behind them the engines run a chunk ahead: tokens from the second step
    victim_rid = 0 if router.results[0].tokens else 1
    victim_replica = next(
        a["replica"] for a in router._tracked[victim_rid]["attempts"]
    )
    queued_on_victim = [
        rid for rid in range(2, 4)
        if router._tracked[rid]["attempts"]
        and router._tracked[rid]["attempts"][0]["replica"] == victim_replica
        and not router.results[rid].tokens
    ]
    _kill_replica(router, victim_replica)
    outputs = router.run()
    assert router.results[victim_rid].finish_reason == "replica_lost"
    assert router.results[victim_rid].tokens, "streamed tokens must be kept"
    for rid in queued_on_victim:
        assert router.results[rid].finish_reason == "length"
        np.testing.assert_array_equal(
            outputs[rid], _static_reference(model, prompts[rid], 10)
        )
    assert router.stats["retries"] >= len(queued_on_victim)
    assert router.stats["ejected"] == 1


def test_never_routes_to_ejected_then_rejoins():
    """An ejected replica takes no traffic; after cooldown + probation it is
    live again and serves with exact parity."""
    import time

    model = _model()
    rng = np.random.default_rng(4)
    router = _router(model, replicas=2, rejoin_cooldown_s=0.05, probation_steps=1)
    prompt = rng.integers(1, 128, (5,)).astype(np.int32)
    router.run([Request(0, prompt, max_new_tokens=3)])
    _kill_replica(router, 0)
    router.submit(Request(1, prompt, max_new_tokens=3))
    router.step()  # the dead replica is discovered the first time it steps
    router.run()
    mark = len(router.routing_log)
    assert router.replica_states[0] == "ejected"
    # traffic while ejected lands on replica 1 only
    outputs = router.run([Request(i, prompt, max_new_tokens=3) for i in range(2, 5)])
    for entry in list(router.routing_log)[mark:]:
        assert entry["replica"] == 1
    for i in range(2, 5):
        np.testing.assert_array_equal(outputs[i], _static_reference(model, prompt, 3))
    # cooldown elapses -> rejoining (engine rebuilt) -> probation -> live
    time.sleep(0.06)
    router.step()
    assert router.replica_states[0] in ("rejoining", "live")
    router.step()
    router.step()
    assert router.replica_states[0] == "live"
    outputs = router.run([Request(10, prompt, max_new_tokens=3)])
    np.testing.assert_array_equal(outputs[10], _static_reference(model, prompt, 3))


def test_hedge_duplicates_queued_request_without_duplicate_stream():
    """TTFT hedging: a request stuck queued behind a long request is
    duplicated onto the other replica; exactly one copy's tokens are ever
    forwarded and the result matches the static path."""
    model = _model()
    rng = np.random.default_rng(5)
    router = _router(model, replicas=2, num_slots=1, hedge_after_s=0.0)
    long_prompt = rng.integers(1, 128, (4,)).astype(np.int32)
    short_prompt = rng.integers(1, 128, (5,)).astype(np.int32)
    # Fill BOTH replicas' slots, then queue one more: it can't admit anywhere,
    # so the hedge sweep fires for it on the next step.
    router.submit(Request(0, long_prompt, max_new_tokens=24))
    router.submit(Request(1, long_prompt, max_new_tokens=24))
    router.step()
    router.submit(Request(2, short_prompt, max_new_tokens=4))
    outputs = router.run()
    assert router.stats["hedges"] >= 1
    np.testing.assert_array_equal(outputs[2], _static_reference(model, short_prompt, 4))
    assert router.results[2].finish_reason == "length"
    # both engine-side copies are gone (no orphaned slots/results)
    for replica in router.replica_set.replicas:
        assert not replica.engine.pending


def test_swap_weights_rolls_fleet_without_capacity_collapse():
    """Rolling weight swap: during the swap at most ONE replica is unroutable
    at a time (capacity >= N-1), in-flight work finishes, and post-swap
    outputs are token-identical to the static path on the NEW params."""
    model_a = _model(seed=0)
    model_b = _model(seed=7)
    rng = np.random.default_rng(6)
    router = _router(model_a, replicas=3)
    prompt = rng.integers(1, 128, (6,)).astype(np.int32)
    ref_a = _static_reference(model_a, prompt, 4)
    ref_b = _static_reference(model_b, prompt, 4)
    assert not np.array_equal(ref_a, ref_b), "seeds must differ for the swap pin"
    router.submit(Request(0, prompt, max_new_tokens=4))
    router.swap_weights(model_b)
    assert not router.swap_in_progress
    # in-flight work finished (on old or new weights — never dropped)
    assert router.results[0].finished
    # every replica drained exactly once, one at a time
    drains = [e for e in router.replica_set.state_log if e["to"] == "draining"]
    assert len(drains) == 3
    unroutable = 0
    for entry in router.replica_set.state_log:
        if entry["to"] in ("draining", "ejected"):
            unroutable += 1
            assert unroutable <= 1, "fleet fell below N-1 capacity during the swap"
        elif entry["from"] in ("draining", "ejected"):
            unroutable -= 1
    outputs = router.run([Request(1, prompt, max_new_tokens=4)])
    np.testing.assert_array_equal(outputs[1], ref_b)


def test_queue_full_across_fleet_and_duplicate_ids():
    model = _model()
    rng = np.random.default_rng(7)
    router = _router(model, replicas=2, num_slots=1, max_queue=1)
    prompt = rng.integers(1, 128, (4,)).astype(np.int32)
    router.submit(Request(0, prompt, max_new_tokens=4))
    router.submit(Request(1, prompt, max_new_tokens=4))
    router.step()  # both admitted into slots; queues are empty again
    router.submit(Request(2, prompt, max_new_tokens=4))  # r0 queue full
    router.submit(Request(3, prompt, max_new_tokens=4))  # r1 queue full
    with pytest.raises(QueueFull):
        router.submit(Request(9, prompt, max_new_tokens=4))
    with pytest.raises(ValueError, match="duplicate"):
        router.submit(Request(0, prompt, max_new_tokens=4))
    with pytest.raises(ValueError, match="slot capacity"):
        router.submit(Request(10, rng.integers(1, 128, (70,)).astype(np.int32),
                              max_new_tokens=8))
    router.run()
    assert all(router.results[i].finish_reason == "length" for i in range(4))
    # release frees the id for reuse, like the engine
    first = router.release(0)
    assert first.finished and 0 not in router.results
    outputs = router.run([Request(0, prompt, max_new_tokens=4)])
    np.testing.assert_array_equal(outputs[0], np.asarray(first.tokens, np.int32))


def test_drain_and_close_lifecycle():
    from accelerate_tpu.serving import EngineClosed

    model = _model()
    rng = np.random.default_rng(8)
    router = _router(model)
    prompt = rng.integers(1, 128, (4,)).astype(np.int32)
    router.submit(Request(0, prompt, max_new_tokens=4))
    results = router.drain()
    assert results[0].finished and not router.pending
    router.submit(Request(1, prompt, max_new_tokens=24))
    router.step()
    results = router.close()
    assert results[1].finish_reason == "cancelled" and results[1].tokens
    assert router.closed
    with pytest.raises(EngineClosed):
        router.submit(Request(2, prompt, max_new_tokens=4))
    assert router.step() == []
    assert router.close() is results or router.close() == results  # idempotent


def test_replica_set_validation_and_env_default(monkeypatch):
    from accelerate_tpu.router import SERVE_REPLICAS_ENV, default_replicas

    model = _model()
    with pytest.raises(ValueError, match="at least one replica"):
        ReplicaSet(model, replicas=0)
    monkeypatch.delenv(SERVE_REPLICAS_ENV, raising=False)
    assert default_replicas() == 2
    monkeypatch.setenv(SERVE_REPLICAS_ENV, "5")
    assert default_replicas() == 5
    monkeypatch.setenv(SERVE_REPLICAS_ENV, "bogus")
    assert default_replicas() == 2


def test_serve_cli_round_trip(capsys):
    """`accelerate-tpu serve` end to end: JSON result lines on stdout, exit 0,
    replica fleet sized by the flag."""
    from accelerate_tpu.commands.accelerate_cli import get_command_parser

    parser = get_command_parser()
    args = parser.parse_args([
        "serve", "--model", "llama-tiny", "--replicas", "2", "--requests", "3",
        "--max-new", "4", "--num-slots", "2", "--prompt-max", "8",
    ])
    with pytest.raises(SystemExit) as exit_info:
        args.func(args)
    assert exit_info.value.code == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    import json

    records = [json.loads(l) for l in lines]
    assert len(records) == 3
    assert all(r["finish_reason"] == "length" and len(r["tokens"]) == 4 for r in records)
