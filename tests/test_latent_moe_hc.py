"""What Xing4.0 adds to the latent-attention, sparse-expert family
(`models/latent_moe.py`: low-rank queries, YaRN, a residual path of four
streams mixed by `ops/hyper_connection.py`), at a tiny size with seeded
weights, held against the LOGITS of the benchmark's plain float32 reference
(`chipbench/reference/latent_moe_hc.py`: streams `[t, n, C]`, a Python loop of
Sinkhorn divisions, decompressed attention, every expert on every token):

  (a) the full forward;
  (b) prefill then the absorbed decode through `ContinuousBatcher`'s own
      programs and pool — position by position, across a page boundary, behind
      an insert bucket's padding and in a reused slot — and the engine end to
      end, with what its spans say of the streams;
  (c) H_res's rows and columns sum to 1 as far as 20 turns bring them, in the
      op and in the reference, and a logit on the clamp stays finite;
  (d) each piece matters: left out, it moves the logits;
  (e) the two Pallas kernels (interpreted) equal the `jax.numpy` form at row
      counts that are and are not whole blocks;
  (f) `hc_mult` 1 is the plain residual: Kimi's tree and programs;
  (g) int8 weights leave the maps float32, and what a latent cache refuses it
      still refuses by name.
"""

import dataclasses
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from accelerate_tpu.generation import generate  # noqa: E402
from accelerate_tpu.models import latent_moe  # noqa: E402
from accelerate_tpu.ops import hyper_connection as hc  # noqa: E402
from accelerate_tpu.serving import ContinuousBatcher, Request  # noqa: E402
from chipbench import harness  # noqa: E402
from test_latent_moe import PAGE, Pool  # noqa: E402  (the engine's own programs, driven by hand)

TINY = {
    "family": "latent_moe_hc", "vocab_size": 512, "max_position_embeddings": 256, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": 4, "num_attention_heads": 4,
    "n_shared_experts": 1, "n_routed_experts": 8, "num_experts_per_tok": 3, "routed_scaling_factor": 2.0,
    "norm_topk_prob": True, "first_k_dense_replace": 2, "kv_lora_rank": 32, "q_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rope_scaling": {"type": "yarn", "factor": 8, "beta_fast": 4, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32},
    "init": {"embedding_std": 1.0, "norm_scale_std": 0.02, "router_bias_std": 0.1,
             "gain": {"wq_a": 1, "wq_b": 1, "wkv_a": 1, "wkv_b": 1, "wo": 1, "w_gate": 1, "w_up": 1, "w_down": 1,
                      "expert_w_down": 0.5, "router": 1, "lm_head": 1},
             "hc": {"phi_gain": 1.0, "alpha": [1.0, 1.0, 1.0], "b_std": 0.5, "b_res_diag": 2.0}},
}
ATOL = 5e-5  # float32 against float32: the program's largest distance from the reference's logits (to 4.2) is 4e-6


@pytest.fixture(scope="module")
def reference():
    return harness.load_module("reference", "latent_moe_hc")


@pytest.fixture(scope="module")
def adapter():
    return harness.load_module("adapters", "latent_moe_hc")


@pytest.fixture(scope="module")
def weights(reference):
    return reference.init_params(TINY, jax.random.key(11), "float32")


@pytest.fixture(scope="module")
def model(adapter, weights):
    return adapter.build_model(TINY, weights, "float32")


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _want(reference, weights, ids):
    return np.asarray(reference.logits(weights, TINY, np.asarray(ids, np.int32)[None, :])[0])


def _ids(seed, n):
    return np.random.default_rng(seed).integers(1, 512, n).astype(np.int32)


# ------------------------------------------------------------------ (a) forward
def test_the_full_forward_equals_the_references(reference, weights, model):
    ids = _ids(0, 2 * 40).reshape(2, 40)
    want = np.asarray(reference.logits(weights, TINY, ids))
    got = np.asarray(model.module.apply(model.params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    assert model.module.config.hc_mult == 4 and model.module.config.q_lora_rank == 24
    # the tiny preset of the registry is this shape
    preset = latent_moe.latent_moe_hc_tiny()
    assert (preset.hc_mult, preset.q_lora_rank, preset.first_k_dense_replace) == (4, 24, 2)
    assert preset.rope_scaling["type"] == "yarn"


# ------------------------------------------ (b) the engine's programs and pool
def test_prefill_then_absorbed_decode_equals_the_references_forward(reference, weights, model):
    pool = Pool(model)
    prompt = _ids(1, 21)  # a bucket of 32: eleven padded rows behind the prompt; two pages and 5 rows of a third
    forced = _ids(2, 12)
    first = pool.insert(0, prompt, pages=[1, 2, 3, 4, 5])
    want = _want(reference, weights, np.concatenate([prompt, forced]))
    assert first == int(want[20].argmax())
    for j in range(8):  # positions 21..28: the page boundary at 24 is crossed
        np.testing.assert_allclose(pool.decode(0, forced[j], 21 + j), want[21 + j], atol=ATOL, rtol=0)
    # the slot is reused: another prompt into other pages, the first request's rows still in the pool
    again = _ids(3, 9)
    first = pool.insert(0, again, pages=[6, 7, 8])
    want = _want(reference, weights, np.concatenate([again, forced]))
    assert first == int(want[8].argmax())
    for j in range(10):  # 9..18, over the boundary at 16
        np.testing.assert_allclose(pool.decode(0, forced[j], 9 + j), want[9 + j], atol=ATOL, rtol=0)


def test_the_engine_serves_it_on_mixed_admissions_and_its_spans_count_the_streams(model):
    from accelerate_tpu.telemetry import FlightRecorder, Tracer

    tracer = Tracer(recorder=FlightRecorder())
    engine = ContinuousBatcher(model, num_slots=2, max_length=64, chunk_size=4, page_size=PAGE, tracer=tracer)
    prompts = [_ids(10 + i, n) for i, n in enumerate((5, 17, 30, 5, 17))]  # more than the slots: reuse
    budgets = (6, 9, 12, 6, 9)
    out = engine.run([Request(i, p, max_new_tokens=m) for i, (p, m) in enumerate(zip(prompts, budgets))])
    assert engine.trace_counts["decode_chunk"] == 1
    for i, (prompt, budget) in enumerate(zip(prompts, budgets)):
        dense = np.asarray(generate(model, prompt[None, :], max_new_tokens=budget))[0]
        np.testing.assert_array_equal(out[i], dense[len(prompt): len(prompt) + budget])
    assert engine.stats["residual_streams"] == 4 and engine.stats["waits_per_step"] == 1.0
    assert engine._m_residual_streams.value == 4
    records = tracer.recorder.records()
    chunks = [r["attrs"] for r in records if r.get("kind") == "span" and r["name"] == "serve.decode_chunk"]
    inserts = [r["attrs"] for r in records if r.get("kind") == "span" and r["name"] == "serve.insert"]
    assert len(inserts) == 5 and chunks
    # 4 layers: 8 sub-layers mix every row of a bucket, and every busy slot of a chunk's 4 steps
    assert all(i["hc_streams"] == 4 and i["hc_rows"] == i["bucket"] * 8 for i in inserts)
    assert all(c["hc_streams"] == 4 and c["hc_rows"] == c["active_slots"] * 4 * 8 for c in chunks)
    assert all(i["routed_pairs"] == i["bucket"] * 3 * 2 for i in inserts) and all(c["kv_row_values"] == 128 for c in chunks)


# ------------------------------------------------------------------ (c) Sinkhorn
def _maps_inputs(tokens=50, n=4, width=64, res_bias=0.0):
    keys = jax.random.split(jax.random.key(3), 4)
    x = jax.random.normal(keys[0], (tokens, n * width), jnp.float32)
    phi = jax.random.normal(keys[1], (n * width, hc.map_count(n)), jnp.float32) / np.sqrt(n * width)
    maps = {"phi": phi, "alpha": jnp.asarray([1.0, 0.7, 1.3]), "b_pre": 0.5 * jax.random.normal(keys[2], (n,)),
            "b_post": jnp.zeros((n,)), "b_res": 2.0 * jnp.eye(n) + 0.5 * jax.random.normal(keys[3], (n, n)) + res_bias}
    return x, maps


def test_twenty_turns_make_h_res_doubly_stochastic_in_the_op_and_in_the_reference(reference, adapter):
    x, maps = _maps_inputs()
    sizes = reference._Sizes.of(TINY)
    want_pre, want_post, want_res, _peak = reference.hc_maps(maps, x.reshape(1, 50, 4, 64), sizes)
    program = adapter.program_maps(jax.device_get(maps))
    packed = hc.hc_maps(x, program["phi_t"], program["alpha"], program["bias"], n=4, iters=20, eps=1e-6)
    pre, post, res = hc.unpack_maps(packed, 4)
    for got, want in ((pre, want_pre[0]), (post, want_post[0]), (res, want_res[0])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6, rtol=0)
    assert np.all(np.asarray(packed[:, hc.map_count(4):]) == 0.0)
    for matrix in (np.asarray(res), np.asarray(want_res[0])):
        # the last turn divides the rows: they sum to 1; the columns as near as 20 turns bring them
        np.testing.assert_allclose(matrix.sum(-1), 1.0, atol=1e-5)
        np.testing.assert_allclose(matrix.sum(-2), 1.0, atol=0.05)
        assert matrix.min() > 0.0
    # one turn is not twenty
    once, _, res_once = hc.unpack_maps(hc.hc_maps(x, program["phi_t"], program["alpha"], program["bias"],
                                                  n=4, iters=1, eps=1e-6), 4)
    assert np.abs(np.asarray(res_once).sum(-2) - 1.0).max() > 10 * np.abs(np.asarray(res).sum(-2) - 1.0).max()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_logit_on_the_clamp_stays_finite(adapter, impl):
    x, maps = _maps_inputs(tokens=16, res_bias=jnp.asarray([[0, -100.0, 0, 0], [100.0, 0, 0, 0], [0] * 4, [0] * 4]))
    program = adapter.program_maps(jax.device_get(maps))
    x = x.astype(jnp.bfloat16) if impl == "pallas" else x  # the kernels take the served type
    u, packed = hc.hc_pre(x, program["phi_t"], program["alpha"], program["bias"], n=4, iters=20, eps=1e-6, impl=impl)
    _, _, res = hc.unpack_maps(packed, 4)
    assert np.isfinite(np.asarray(packed)).all() and np.isfinite(np.asarray(u, np.float32)).all()
    np.testing.assert_allclose(np.asarray(res).sum(-1), 1.0, atol=1e-5)
    # e^30 takes its row and its column, e^-30 is nothing beside its neighbours: no overflow, no NaN
    assert np.asarray(res)[:, 1, 0].min() > 0.9 and np.asarray(res)[:, 0, 1].max() < 1e-9


# ------------------------------------------------------- (d) each piece matters
def _without_q_norm(monkeypatch):
    class NoQueryNorm(latent_moe.RMSNorm):
        @nn.compact
        def __call__(self, x):
            return x if self.name == "q_norm" else super().__call__(x)

    monkeypatch.setattr(latent_moe, "RMSNorm", NoQueryNorm)


def _post_without_its_factor(monkeypatch):
    post = latent_moe.hc_post

    def halved(x, y, maps, *, n, impl=None):
        return post(x, y, maps.at[..., n:2 * n].multiply(0.5), n=n, impl=impl)

    monkeypatch.setattr(latent_moe, "hc_post", halved)


def _scaling(**over):
    return {"rope_scaling": dict(TINY["rope_scaling"], **over)}


@pytest.mark.parametrize("name,config,params,patch", [
    ("one_sinkhorn_turn", {"hc_sinkhorn_iters": 1}, None, None),
    ("alpha_zero", {}, lambda path, leaf: jnp.zeros_like(leaf) if path.endswith("alpha") else leaf, None),
    ("h_post_without_its_factor_2", {}, None, _post_without_its_factor),
    ("yarn_scale_left_out", _scaling(mscale=0, mscale_all_dim=0), None, None),
    ("yarn_frequencies_left_out", _scaling(original_max_position_embeddings=10**9), None, None),
    ("q_norm_left_out", {}, None, _without_q_norm),
], ids=lambda v: v if isinstance(v, str) else "")
def test_each_piece_left_out_moves_the_logits(reference, weights, model, monkeypatch, name, config, params, patch):
    ids = _ids(4, 48)
    want = _want(reference, weights, ids)
    if patch is not None:
        patch(monkeypatch)
    module = latent_moe.LatentMoEForCausalLM(dataclasses.replace(model.module.config, **config))
    tree = model.params
    if params is not None:
        tree = jax.tree_util.tree_map_with_path(lambda p, leaf: params(jax.tree_util.keystr(p).strip("[']"), leaf), tree)
    got = np.asarray(module.apply(tree, jnp.asarray(ids[None, :]))[0])
    assert np.abs(got - want).max() > 100 * ATOL, name
    if name == "yarn_frequencies_left_out":  # the scale stayed: only the rotation changed
        assert module.config.softmax_scale == model.module.config.softmax_scale
        np.testing.assert_allclose(latent_moe.yarn_inv_freq(module.config.rope_scaling, 8, 10000.0),
                                   10000.0 ** (-np.arange(4) / 4), rtol=1e-6)


# ------------------------------------------------------------ (e) the kernels
@pytest.mark.parametrize("rows", [128, 256, 200, 37], ids=lambda r: f"rows{r}")
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_kernels_interpreted_equal_the_jax_numpy_form(rows, dtype):
    n, width = 4, 256
    keys = jax.random.split(jax.random.key(rows), 5)
    x = (1.5 * jax.random.normal(keys[0], (rows, n * width), jnp.float32)).astype(dtype)
    y = jax.random.normal(keys[1], (rows, width), jnp.float32).astype(dtype)
    phi_t = jax.random.normal(keys[2], (hc.map_count(n), n * width), jnp.float32) / np.sqrt(n * width)
    alpha = jnp.asarray([1.0, 0.8, 1.2])
    bias = 0.5 * jax.random.normal(keys[3], (hc.map_count(n),))
    kw = dict(n=n, iters=20, eps=1e-6)
    u_want, maps_want = hc.hc_pre(x, phi_t, alpha, bias, impl="xla", **kw)
    if dtype != jnp.bfloat16:  # the kernels are for the served type: another takes the `jax.numpy` form, or is refused by name
        with pytest.raises(ValueError, match="impl 'pallas' takes bfloat16 streams, not float32"):
            hc.hc_pre(x, phi_t, alpha, bias, impl="pallas", **kw)
        with pytest.raises(ValueError, match="impl 'pallas' takes bfloat16 streams, not float32"):
            hc.hc_post(x, y, maps_want, n=n, impl="pallas")
        u_got, maps_got = hc.hc_pre(x, phi_t, alpha, bias, **kw)
        np.testing.assert_array_equal(np.asarray(u_got), np.asarray(u_want))
        np.testing.assert_array_equal(np.asarray(maps_got), np.asarray(maps_want))
        np.testing.assert_array_equal(np.asarray(hc.hc_post(x, y, maps_want, n=n)),
                                      np.asarray(hc.hc_post(x, y, maps_want, n=n, impl="xla")))
        return
    u_got, maps_got = hc.hc_pre(x, phi_t, alpha, bias, impl="pallas", **kw)
    assert u_got.dtype == dtype and u_got.shape == (rows, width) and maps_got.shape == (rows, hc.MAP_LANES)
    np.testing.assert_allclose(np.asarray(maps_got), np.asarray(maps_want), atol=3e-6, rtol=0)
    one_ulp = 2.0 ** -6  # a bfloat16 result of size 2-4 may round the other way
    np.testing.assert_allclose(np.asarray(u_got, np.float32), np.asarray(u_want, np.float32), atol=one_ulp, rtol=0)
    out_want = hc.hc_post(x, y, maps_want, n=n, impl="xla")
    out_got = hc.hc_post(x, y, maps_want, n=n, impl="pallas")
    assert out_got.dtype == dtype and out_got.shape == x.shape
    np.testing.assert_allclose(np.asarray(out_got, np.float32), np.asarray(out_want, np.float32), atol=2 * one_ulp, rtol=0)
    # the block's rows are leading axes' product: [slots, 1, n C] as a decode step hands them over
    u3, maps3 = hc.hc_pre(x.reshape(rows, 1, n * width), phi_t, alpha, bias, impl="pallas", **kw)
    assert u3.shape == (rows, 1, width) and maps3.shape == (rows, 1, hc.MAP_LANES)
    np.testing.assert_array_equal(np.asarray(u3[:, 0], np.float32), np.asarray(u_got, np.float32))


def test_the_ops_refuse_what_they_are_not():
    x = jnp.zeros((8, 4 * 64))
    with pytest.raises(ValueError, match=r"phi_t \(24, 128\) is not \[24, 256\] for 4 streams"):
        hc.hc_maps(x, jnp.zeros((24, 128)), jnp.zeros(3), jnp.zeros(24), n=4, iters=2, eps=1e-6)
    with pytest.raises(ValueError, match="unknown impl 'mosaic'"):
        hc.hc_post(x, jnp.zeros((8, 64)), jnp.zeros((8, 128)), n=4, impl="mosaic")
    with pytest.raises(ValueError, match="hc_mult=11: 1 to 10 residual streams"):
        dataclasses.replace(latent_moe.latent_moe_tiny(), hc_mult=11)
    with pytest.raises(ValueError, match="only DeepSeek-V3's \"yarn\" is built"):
        dataclasses.replace(latent_moe.latent_moe_tiny(), rope_scaling={"type": "linear", "factor": 2})


# ------------------------------------------------- (f) one stream is Kimi's model
def test_one_stream_is_the_plain_residual_with_kimis_tree_and_programs():
    kimi = harness.load_module("reference", "latent_moe")
    from test_latent_moe import TINY as KIMI_TINY

    params = kimi.init_params(KIMI_TINY, jax.random.key(11), "float32")
    plain = harness.load_module("adapters", "latent_moe").build_model(KIMI_TINY, params, "float32")
    config = plain.module.config
    assert (config.hc_mult, config.q_lora_rank, config.rope_scaling) == (1, None, None)
    ids = _ids(5, 24)[None, :]
    got = np.asarray(plain.module.apply(plain.params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, np.asarray(kimi.logits(params, KIMI_TINY, jnp.asarray(ids))), atol=2e-5, rtol=0)
    # a freshly initialised module has the tree it had: no maps, one `wq`
    made = latent_moe.create_latent_moe_model(latent_moe.latent_moe_tiny()).params["params"]
    assert sorted(made["layer_1"]) == ["attention", "input_norm", "moe", "post_attn_norm"]
    assert sorted(made["layer_1"]["attention"]) == ["kv_norm", "wkv_a", "wkv_b", "wo", "wq"]
    # and with four streams it gains two sets of maps a layer and the queries' two factors
    wide = latent_moe.create_latent_moe_model(latent_moe.latent_moe_hc_tiny()).params["params"]
    assert sorted(wide["layer_3"]) == ["attention", "hc_attn", "hc_ffn", "input_norm", "moe", "post_attn_norm"]
    assert sorted(wide["layer_0"]["attention"]) == ["kv_norm", "q_norm", "wkv_a", "wkv_b", "wo", "wq_a", "wq_b"]
    assert wide["layer_0"]["hc_ffn"]["phi_t"].shape == (24, 256) and wide["layer_0"]["hc_ffn"]["phi_t"].dtype == jnp.float32
    # the plain residual's programs hold none of the new scopes; the four-stream ones hold all three
    engine = ContinuousBatcher(plain, num_slots=2, max_length=32, chunk_size=4, page_size=PAGE)
    lowered = engine._chunk_fn.lower(*engine._chunk_operands()).as_text(debug_info=True)
    assert not any(scope in lowered for scope in ("hc_pre", "hc_post", "mla_q_lora")) and "mla_absorb" in lowered
    assert "residual_streams" not in engine.stats
    assert "hc_rows" not in engine.base_config.chunk_span_counts(16) and engine.base_config.chunk_span_counts(16) == {}


def test_the_four_stream_programs_hold_the_new_scopes(model):
    engine = ContinuousBatcher(model, num_slots=2, max_length=32, chunk_size=4, page_size=PAGE)
    lowered = engine._chunk_fn.lower(*engine._chunk_operands()).as_text(debug_info=True)
    for scope in ("hc_pre", "hc_post", "mla_q_lora", "mla_absorb", "latent_read", "moe_route", "moe_shared"):
        assert scope in lowered, scope


# -------------------------------------------------- (g) int8 weights, refusals
def test_int8_weights_leave_the_maps_and_the_routers_bias_float32(model):
    engine = ContinuousBatcher(model, num_slots=2, max_length=48, chunk_size=4, page_size=PAGE, weight_dtype="int8")
    layer = engine.params["params"]["layer_2"]
    for entry in (layer["attention"]["wq_a"]["kernel"], layer["attention"]["wq_b"]["kernel"],
                  layer["attention"]["wkv_b"]["kernel"], layer["moe"]["experts"]["w_up"]["kernel"]):
        assert set(entry) == {"q", "scale"} and entry["q"].dtype == jnp.int8
    for maps in (layer["hc_attn"], layer["hc_ffn"]):
        assert {k: v.dtype for k, v in maps.items()} == {"phi_t": jnp.float32, "alpha": jnp.float32, "bias": jnp.float32}
    assert layer["moe"]["router_bias"].dtype == jnp.float32
    out = engine.run([Request(0, _ids(6, 12), max_new_tokens=6)])
    assert len(out[0]) == 6 and engine.stats["finish_reasons"]["length"] == 1


@pytest.mark.parametrize("argument,names", [
    ({"kv_cache_dtype": "int8"}, "quantized pool for latent rows is not built"),
    ({"tp": 2}, "layout is not built"),
])
def test_a_four_stream_latent_cache_still_refuses_what_is_not_built(model, argument, names):
    with pytest.raises(ValueError, match=names):
        ContinuousBatcher(model, **{"num_slots": 2, "max_length": 32, "page_size": PAGE, **argument})


def test_registry_names_the_model_and_its_tiny_preset():
    from accelerate_tpu.models import MODEL_REGISTRY, get_model_config

    family, factory = MODEL_REGISTRY["xing4-29b-a4b"]
    config = factory()
    assert family == "latent_moe" and MODEL_REGISTRY["latent-moe-hc-tiny"][0] == "latent_moe"
    assert (config.hidden_size, config.num_hidden_layers, config.q_lora_rank, config.hc_mult) == (3584, 40, 768, 4)
    assert latent_moe.yarn_correction_range(config.rope_scaling, 64, config.rope_theta) == (10, 23)
    assert config.softmax_scale * np.sqrt(192) == pytest.approx(2.0047, abs=5e-5)
    # a worker builds it from a spec, under the family's name or the published `model_type`
    from accelerate_tpu.worker import build_model_from_spec, spec_for_model

    tiny = latent_moe.create_latent_moe_model(latent_moe.latent_moe_hc_tiny())
    spec = spec_for_model(tiny)
    assert spec["family"] == "latent_moe" and spec["config"]["hc_mult"] == 4
    built = build_model_from_spec(dict(spec, family="xing4_0", params_path=None))
    assert built.module.config == tiny.module.config and "hc_attn" in built.params["params"]["layer_0"]
    published = get_model_config("xing4-29b-a4b")
    assert published["q_lora_rank"] == 768 and published["hc_mult"] == 4 and published["rope_scaling"]["factor"] == 64
