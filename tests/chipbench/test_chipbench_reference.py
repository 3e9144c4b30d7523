"""Each plain reference against the repo's own model, in float32 at tiny size:
two implementations that share no code agree on seeded weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.traffic_gen import training_rows

from conftest import BERT_TINY, NEOX_TINY  # tests/chipbench/conftest.py: pytest puts this directory first on sys.path


@pytest.mark.parametrize("use_parallel_residual", [True])
def test_gpt_neox_reference_matches_the_programs_model(use_parallel_residual):
    reference = harness.load_module("reference", "gpt_neox")
    adapter = harness.load_module("adapters", "gpt_neox")
    config = dict(NEOX_TINY, use_parallel_residual=use_parallel_residual)
    params = reference.init_params(config, harness.seed_key(2**31 + 3), "float32")
    ids = jnp.asarray(np.random.default_rng(0).integers(1, config["vocab_size"], (2, 48)), jnp.int32)
    model = adapter.build_model(config, params, "float32")
    with jax.default_matmul_precision("highest"):
        theirs = model.apply_fn(params, ids)
    ours = reference.logits(params, config, ids)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), atol=2e-5, rtol=1e-4)


def test_gpt_neox_weights_are_seeded_and_plainly_normal():
    reference = harness.load_module("reference", "gpt_neox")
    a = reference.init_params(NEOX_TINY, harness.seed_key(7), "float32")
    b = reference.init_params(NEOX_TINY, harness.seed_key(7), "float32")
    c = reference.init_params(NEOX_TINY, harness.seed_key(8), "float32")
    flat = lambda t: np.concatenate([np.asarray(x).ravel() for x in jax.tree_util.tree_leaves(t)])
    assert np.array_equal(flat(a), flat(b)) and not np.array_equal(flat(a), flat(c))
    for name in ("wq", "wk", "wv", "wo"):  # no column of any projection stands out
        kernel = np.asarray(a["params"]["layer_0"]["attention"][name]["kernel"])
        assert abs(kernel.std() / NEOX_TINY["init"]["std"] - 1) < 0.05
        assert kernel.std(axis=0).max() < 2 * np.median(kernel.std(axis=0))


def test_served_token_gaps_are_zero_for_the_references_own_choice_and_positive_otherwise():
    reference = harness.load_module("reference", "gpt_neox")
    params = reference.init_params(NEOX_TINY, harness.seed_key(1), "float32")
    prompt = np.arange(1, 11, dtype=np.int32)
    generated = []
    for _ in range(5):  # greedy decoding by the reference's full forward pass
        ids = jnp.asarray(np.concatenate([prompt, np.asarray(generated, np.int32)])[None], jnp.int32)
        generated.append(int(np.asarray(reference.logits(params, NEOX_TINY, ids))[0, -1].argmax()))
    gaps = reference.served_token_gaps(params, NEOX_TINY, [(prompt, generated)], pad_to=32, rows=8)[0]
    assert gaps.shape == (5,) and np.all(gaps <= 1e-6)
    wrong = list(generated)
    wrong[2] = (wrong[2] + 1) % NEOX_TINY["vocab_size"]
    gaps = reference.served_token_gaps(params, NEOX_TINY, [(prompt, wrong)], pad_to=32, rows=8)[0]
    assert gaps[2] > 1e-4 and np.all(gaps[:2] <= 1e-6)


def _bert_batch(seed):
    rows = training_rows({"kind": "batches", "shape": "mrpc_pairs", "examples": 8, "seq_len": 32},
                         BERT_TINY["vocab_size"] - 1, seed)
    return {k: jnp.asarray(v) for k, v in rows.items()}


def test_bert_reference_loss_and_gradients_match_the_programs_model():
    reference = harness.load_module("reference", "bert")
    adapter = harness.load_module("adapters", "bert")
    params = reference.init_params(BERT_TINY, harness.seed_key(5), "float32")
    batch = _bert_batch(1)
    model = adapter.build_model(BERT_TINY, params, "float32")
    with jax.default_matmul_precision("highest"):
        theirs, their_grads = jax.value_and_grad(lambda p: model.loss_fn(p, batch, model.apply_fn))(params)
    ours, our_grads = jax.value_and_grad(reference.loss)(params, BERT_TINY, batch)
    # the program's GELU is the tanh approximation, the published one is erf: ~1e-4 apart
    assert float(ours) == pytest.approx(float(theirs), abs=2e-5)
    ours_n, theirs_n = reference.leaf_norms(our_grads), reference.leaf_norms(their_grads)
    for key, value in ours_n.items():
        assert float(value) == pytest.approx(float(theirs_n[key]), rel=2e-3, abs=1e-7), key


def test_bert_reference_adamw_matches_optax():
    import optax

    reference = harness.load_module("reference", "bert")
    params = reference.init_params(BERT_TINY, harness.seed_key(6), "float32")
    batches = [_bert_batch(s) for s in (1, 2, 3)]
    got = reference.train_steps(params, BERT_TINY, batches, 1e-3)
    tx = optax.adamw(1e-3)
    state, p = tx.init(params), params
    losses = []
    for batch in batches:
        loss, grads = jax.value_and_grad(reference.loss)(p, BERT_TINY, batch)
        updates, state = tx.update(grads, state, p)
        p = optax.apply_updates(p, updates)
        losses.append(float(loss))
    np.testing.assert_allclose(got["losses"], losses, rtol=1e-5)
    change = reference.leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, p, params))
    for key, value in got["change_norms"].items():
        assert float(value) == pytest.approx(float(change[key]), rel=1e-3), key


@pytest.mark.parametrize("precision,low,high", [("bfloat16", 1e-5, 2e-2), ("float8_e4m3fn", 1e-3, 0.2)])
def test_bert_reference_lower_precisions_move_the_gradient_norms(precision, low, high):
    from chipbench.drivers import train

    reference = harness.load_module("reference", "bert")
    params = reference.init_params(BERT_TINY, harness.seed_key(6), "float32")
    batches = [_bert_batch(s) for s in (1, 2, 3)]
    exact = reference.train_steps(params, BERT_TINY, batches, 1e-3)
    rounded = reference.train_steps(params, BERT_TINY, batches, 1e-3, precision)
    gap = train.worst_leaf_gap(rounded["first_grad_norms"], exact["first_grad_norms"])
    assert low < gap < high


def test_worst_leaf_gap_floors_small_leaves_at_the_median():
    from chipbench.drivers import train

    reference = {"a": 1.0, "b": 2.0, "c": 1e-9}
    assert train.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 1e-9}, reference) == pytest.approx(0.1)
    assert train.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 1e-3}, reference) == pytest.approx(1e-3, rel=1e-3)
