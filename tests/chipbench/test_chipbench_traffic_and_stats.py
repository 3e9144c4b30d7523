"""The traffic generator, the host-clock statistics and the FLOP/byte functions:
everything a CPU can check exactly."""

import json
import os

import numpy as np
import pytest

from chipbench import harness, shapes, traffic_gen
from chipbench.drivers import serve

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _traffic(name):
    with open(os.path.join(REPO, "chipbench", "traffic", name + ".json")) as f:
        return json.load(f)


REQUEST_MIXES = [n[:-5] for n in sorted(os.listdir(os.path.join(REPO, "chipbench", "traffic")))
                 if _traffic(n[:-5]).get("kind") == "requests"]
BATCH_MIXES = [n[:-5] for n in sorted(os.listdir(os.path.join(REPO, "chipbench", "traffic")))
               if _traffic(n[:-5]).get("kind") == "batches"]


@pytest.mark.parametrize("mix", REQUEST_MIXES)
def test_request_mix_is_deterministic_in_the_seed(mix):
    a = traffic_gen.RequestStream(_traffic(mix), 50304, 2**31 + 17)
    b = traffic_gen.RequestStream(_traffic(mix), 50304, 2**31 + 17)
    c = traffic_gen.RequestStream(_traffic(mix), 50304, 2**31 + 18)
    for i in (0, 5, 300):
        assert a.sizes(i) == b.sizes(i)
        np.testing.assert_array_equal(a.prompt(i), b.prompt(i))
    assert any(a.sizes(i) != c.sizes(i) for i in range(20))
    assert not np.array_equal(a.prompt(0)[:16], c.prompt(0)[:16])


@pytest.mark.parametrize("mix", REQUEST_MIXES)
def test_request_mix_has_the_stated_medians_and_clips(mix):
    traffic = _traffic(mix)
    stream = traffic_gen.RequestStream(traffic, 50304, 3)
    sizes = [stream.sizes(i) for i in range(stream.pool)]
    for column, dist in ((1, traffic["prompt_len"]), (2, traffic["output_len"])):
        values = np.array([s[column] for s in sizes])
        assert values.min() >= dist["min"] and values.max() <= dist["max"]
        assert abs(np.median(values) - dist["median"]) <= 0.03 * dist["median"]
    assert all(1 <= t < 50304 for t in stream.prompt(0))
    assert len(stream.prompt(7)) == stream.sizes(7)[1]


@pytest.mark.parametrize("mix", REQUEST_MIXES)
def test_every_seed_gets_the_same_sizes_in_another_order(mix):
    pools = []
    for seed in (1, 2, 2**32 + 5):
        stream = traffic_gen.RequestStream(_traffic(mix), 1000, seed)
        sizes = [stream.sizes(i) for i in range(stream.pool)]
        pools.append((sorted(s[1] for s in sizes), sorted(s[2] for s in sizes),
                      [s[1] for s in sizes]))
    assert pools[0][0] == pools[1][0] == pools[2][0]
    assert pools[0][1] == pools[1][1] == pools[2][1]
    assert pools[0][2] != pools[1][2]


@pytest.mark.parametrize("rate", [4.0, 1.76])
def test_open_loop_arrivals_keep_the_mean_rate_exactly_a_pool(rate):
    traffic = dict(_traffic("batch-saturated"), pool=128,
                   arrivals={"kind": "exponential_quantiles", "rate_per_s": rate})
    stream = traffic_gen.RequestStream(traffic, 1000, 9)
    due = [stream.sizes(i)[0] for i in range(4 * 128)]
    assert not stream.backlog and due == sorted(due) and due[0] > 0
    assert len(due) / due[-1] == pytest.approx(rate, rel=1e-9)  # every pool's gaps sum to pool / rate
    gaps = np.diff([0.0] + due[:128])
    other = traffic_gen.RequestStream(traffic, 1000, 10)
    other_gaps = np.diff([0.0] + [other.sizes(i)[0] for i in range(128)])
    np.testing.assert_allclose(np.sort(gaps), np.sort(other_gaps))  # the same gaps for every seed
    assert not np.allclose(gaps, other_gaps)                        # in another order
    assert 0.9 < np.std(gaps) * rate < 1.1                          # spread like an exponential's


def test_backlog_is_due_at_once():
    stream = traffic_gen.RequestStream(_traffic("batch-saturated"), 1000, 4)
    assert stream.backlog and all(stream.sizes(i)[0] == 0.0 for i in range(10))


@pytest.mark.parametrize("broken", [
    {"arrivals": {"kind": "no_such_arrivals"}},
    {"output_len": {"dist": "no_such_dist"}},
])
def test_a_kind_a_mix_names_is_a_file_found_by_name(broken):
    with pytest.raises(FileNotFoundError):
        traffic_gen.RequestStream(dict(_traffic("batch-saturated"), **broken), 1000, 4)
    with pytest.raises(FileNotFoundError):
        traffic_gen.training_rows({"kind": "batches", "shape": "no_such_shape"}, 1000, 4)


@pytest.mark.parametrize("mix", BATCH_MIXES)
def test_training_rows_are_seeded_and_shaped(mix):
    traffic = _traffic(mix)
    a = traffic_gen.training_rows(traffic, 30522, 2**31 + 1)
    b = traffic_gen.training_rows(traffic, 30522, 2**31 + 1)
    c = traffic_gen.training_rows(traffic, 30522, 2**31 + 2)
    for key in a:
        np.testing.assert_array_equal(a[key], b[key])
    assert not np.array_equal(a["input_ids"], c["input_ids"])
    assert a["input_ids"].shape == (traffic["examples"], traffic["seq_len"])
    assert a["input_ids"].min() >= 0 and a["input_ids"].max() < 30522


def test_mrpc_pairs_are_paraphrases_or_not():
    rows = traffic_gen.training_rows({"kind": "batches", "shape": "mrpc_pairs", "examples": 64, "seq_len": 32}, 1000, 5)
    for ids, label in zip(rows["input_ids"], rows["labels"]):
        same = sorted(ids[:16]) == sorted(ids[16:])
        assert same == bool(label)
    assert set(rows["token_type_ids"][0]) == {0, 1}


# ------------------------------------------------------------------- statistics
@pytest.mark.parametrize("values,q,expected", [
    ([1.0], 95, 1.0), ([1, 2, 3, 4, 5], 50, 3.0), (list(range(101)), 95, 95.0),
    ([10, 20], 95, 19.5), ([3, 1, 2], 0, 1.0), ([3, 1, 2], 100, 3.0),
])
def test_percentile_is_numpys(values, q, expected):
    assert harness.percentile(values, q) == pytest.approx(expected)
    assert harness.percentile(values, q) == pytest.approx(float(np.percentile(values, q)))


def test_time_weighted_mean_weights_by_duration():
    assert harness.time_weighted_mean([(1.0, 100.0), (3.0, 0.0)]) == pytest.approx(25.0)


def _served(rid, due, first, last, n, out_len, reason):
    r = serve.Served(rid, due, 10, out_len)
    r.first, r.last, r.tokens, r.reason = first, last, [1] * n, reason
    return r


def test_open_loop_window_counts_requests_due_in_it_from_when_they_were_due():
    served = {
        0: _served(0, due=99.0, first=99.5, last=100.5, n=5, out_len=5, reason="length"),    # due before the window
        1: _served(1, due=100.0, first=100.2, last=101.2, n=11, out_len=11, reason="length"),
        2: _served(2, due=109.5, first=111.0, last=113.0, n=5, out_len=5, reason="length"),  # finished after it closed
        3: _served(3, due=105.0, first=None, last=None, n=0, out_len=5, reason="error"),     # failed
        4: _served(4, due=110.0, first=110.1, last=110.2, n=2, out_len=2, reason="length"),  # due after it closed
    }
    window = {"served": served, "t0": 100.0, "t1": 110.0, "window_tokens": 420, "backlog": False}
    out = serve.end_to_end(window, seconds=10.0)
    assert (out["attempted"], out["failed"]) == (3, 1)
    assert out["values"]["serve_tokens_per_s"] == pytest.approx(42.0)
    assert out["values"]["ttft_p95_ms"] == pytest.approx(harness.percentile([200.0, 1500.0], 95))
    assert out["values"]["tpot_p95_ms"] == pytest.approx(harness.percentile([100.0, 500.0], 95))


def test_backlog_window_counts_what_finished_in_it():
    served = {
        0: _served(0, 0.0, 99.0, 100.5, 9, 9, "length"),
        1: _served(1, 0.0, 101.0, 111.0, 9, 9, "length"),   # finished after the window
        2: _served(2, 0.0, 101.0, 105.0, 3, 9, "error"),
    }
    window = {"served": served, "t0": 100.0, "t1": 110.5, "window_tokens": 2100, "backlog": True}
    out = serve.end_to_end(window, seconds=10.0)
    assert (out["attempted"], out["failed"]) == (2, 1)
    assert out["values"] == {"serve_tokens_per_s": pytest.approx(200.0)}


# ---------------------------------------------------------------- FLOPs and bytes
def _config(name):
    with open(os.path.join(REPO, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,family,total,embedding", [
    # pythia-1.4b: 50304*2048 in, 24 * (4*(2048^2+2048) + 2048*8192+8192 + 8192*2048+2048 + 4*2048), 2*2048, 2048*50304 out
    ("pythia-1.4b", "gpt_neox", 1_414_647_808, 103_022_592),
    # bert-base: (30522+512+2)*768 + 2*768; 12 * 7,087,872; pooler 590,592 + classifier 1,538
    ("bert-base", "bert", 109_483_778, 23_837_184),
])
def test_parameter_counts_match_hand_counts(name, family, total, embedding):
    counts = harness.load_module("reference", family).param_counts(_config(name))
    assert (counts["total"], counts["embedding"]) == (total, embedding)


def test_kv_and_decode_bytes_match_hand_counts():
    cfg = _config("pythia-1.4b")
    assert shapes.kv_bytes_per_token(cfg, "bfloat16") == 2 * 24 * 2048 * 2 == 196_608
    weights = (1_414_647_808 - 103_022_592) * 2
    assert shapes.decode_step_bytes(weights, 10_000, 196_608) == weights + 1_966_080_000


def test_train_step_flops_match_hand_counts():
    per_token = 6 * (109_483_778 - 23_837_184) + 12 * 12 * 768 * 128
    bert = harness.load_module("reference", "bert").param_counts(_config("bert-base"))
    assert shapes.train_step_flops(_config("bert-base"), bert, 32, 128) == pytest.approx(per_token * 4096)
    per_token = 6 * (1_414_647_808 - 103_022_592) + 12 * 24 * 2048 * 2048
    neox = harness.load_module("reference", "gpt_neox").param_counts(_config("pythia-1.4b"))
    assert shapes.train_step_flops(_config("pythia-1.4b"), neox, 16, 2048) == pytest.approx(per_token * 32768)
