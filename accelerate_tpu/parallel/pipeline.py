"""Pipeline parallelism over the "stage" mesh axis.

The reference reaches pipeline parallelism only through external native runtimes:
Megatron-LM's 1F1B schedule for training (reference utils/megatron_lm.py:1004-1010) and
PiPPy's fx-traced stages + c10d send/recv for inference (reference inference.py:126).
Here PP is in-tree and TPU-native: stages live on the "stage" axis of the one global
mesh, activations hop between stages with `lax.ppermute` over ICI, and the microbatch
schedule is a `lax.scan` over pipeline ticks inside one jitted SPMD program — XLA
overlaps each stage's matmuls with the neighbor DMA, and autodiff through the scan
produces the backward schedule (GPipe-style, rematerialized per tick so activation
memory stays O(microbatches), not O(microbatches × layers)).

Layout: a model's stack decomposes via the `LayeredApply` protocol
(accelerate_tpu.big_modeling) into prelude / N homogeneous layers / tail. Layer params
are stacked on a leading [L] axis sharded over "stage" (each stage holds L/S layers and
scans them locally); prelude and tail are replicated — only their owning stage computes
them (a `lax.cond` gates the FLOPs) and shard_map's transpose inserts the psum that
makes their gradients globally correct.

Schedule: tick t ∈ [0, M+S-1): stage 0 injects microbatch min(t, M-1), every stage runs
its local layer chunk, the last stage folds microbatch t-(S-1) into the loss, and the
carry rotates +1 stage. Injections after t=M-1 are duplicates that never reach the tail
inside the loop — they occupy the same slots the pipeline bubble would leave idle.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import numpy as np

# Path rules consumed by parallel/sharding.py: stacked layer params (and their optimizer
# moments, whose paths nest under e.g. "0/mu/layers/...") shard dim 0 over "stage".
# enc_layers/dec_layers are the encoder-decoder pipeline's two stacked bodies.
PIPELINE_SHARDING_RULES = [(r"(^|/)(enc_|dec_)?layers(/|$)", ("stage",))]


def stack_layer_params(layers):
    """Stack a list of per-layer param pytrees into one pytree with leading [L] axes."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


def unstack_layer_params(stacked, num_layers: int):
    import jax

    return [jax.tree_util.tree_map(lambda x: x[i], stacked) for i in range(num_layers)]


def stack_layer_params_sharded(layers, sharding_tree):
    """Stack per-layer param pytrees directly into stage-sharded [L, ...] buffers,
    assembling each device's [L/S, ...] slice individually so the full stacked model
    never materializes on one device.

    Deliberately NOT `jit(stack_layer_params, out_shardings=...)`: on the
    forced-host-device CPU backend (seen under jax 0.4.37, not re-checked since)
    the GSPMD-partitioned concatenate reads its input
    with a stride equal to the size of the replicated mesh axes (out.flat[k] ==
    ref.flat[data_size * k]), silently corrupting every stacked buffer — the root
    cause of the pipeline parity drift."""
    import jax
    import numpy as np

    num_layers = len(layers)

    def per_leaf(shard, *leaves):
        shape = (num_layers,) + tuple(leaves[0].shape)
        host = [np.asarray(x) for x in leaves]

        def cb(idx):
            rows = range(*idx[0].indices(num_layers))
            return np.stack([host[i][idx[1:]] for i in rows])

        return jax.make_array_from_callback(shape, shard, cb)

    return jax.tree_util.tree_map(per_leaf, sharding_tree, *layers)


def _dict_path_get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _dict_path_set(tree, path, value):
    """Copy-on-write set: returns a new nested dict with `path` replaced by `value`,
    creating intermediate dicts as needed (tied paths are pruned from the stored tail)."""
    if not path:
        return value
    out = dict(tree)
    out[path[0]] = _dict_path_set(tree.get(path[0], {}), path[1:], value)
    return out


def _dict_path_del(tree, path):
    out = dict(tree)
    if len(path) == 1:
        del out[path[0]]
        return out
    out[path[0]] = _dict_path_del(tree[path[0]], path[1:])
    if not out[path[0]]:
        del out[path[0]]
    return out


def find_tied_leaves(prelude, tail):
    """Tail leaves sharing a buffer with a prelude leaf (tied weights, e.g. a tied
    lm head — reference finds these via data_ptr maps, utils/modeling.py:606). Returns
    [(tail_path, prelude_path)] with paths as tuples of dict keys."""
    import jax

    def _paths(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return [(tuple(getattr(k, "key", k) for k in path), leaf) for path, leaf in flat]

    prelude_by_id = {id(leaf): path for path, leaf in _paths(prelude)}
    return [
        (path, prelude_by_id[id(leaf)])
        for path, leaf in _paths(tail)
        if id(leaf) in prelude_by_id
    ]


def default_causal_lm_logits_loss(logits, batch):
    """Shifted next-token cross-entropy on a microbatch, as a `(loss_sum, weight)` pair
    (mirrors models.llama.causal_lm_loss but from logits — the tail output — instead of
    params). Returning the unnormalized pair lets the pipeline produce the globally
    token-weighted mean even when label masking is uneven across microbatches/shards."""
    import jax
    import jax.numpy as jnp

    labels = batch.get("labels", batch["input_ids"])
    shift_logits = logits[:, :-1].astype(jnp.float32)
    shift_labels = labels[:, 1:]
    logp = jax.nn.log_softmax(shift_logits, axis=-1)
    valid = (shift_labels >= 0).astype(jnp.float32)
    safe_labels = jnp.maximum(shift_labels, 0)
    nll = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    return (nll * valid).sum(), valid.sum()


def _default_batch_to_args(batch):
    if isinstance(batch, dict):
        return (batch["input_ids"], batch.get("attention_mask"))
    return (batch,)


def default_seq2seq_logits_loss(logits, batch):
    """Teacher-forced cross-entropy on decoder targets from logits, as a
    `(loss_sum, weight)` pair (mirrors models.t5.seq2seq_lm_loss; labels align
    with decoder positions — no shift)."""
    import jax
    import jax.numpy as jnp

    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return (nll * valid).sum(), valid.sum()


def _default_seq2seq_batch_to_args(batch):
    return (batch["input_ids"], batch["decoder_input_ids"], batch.get("attention_mask"))


from ..modeling import _cast_floating


class PipelineSpec:
    """Stage functions for one model: an adapter over the `LayeredApply` protocol plus a
    logits-level loss. This is the PiPPy `Pipe.from_tracing` replacement — models declare
    their stage decomposition instead of being fx-traced."""

    def __init__(
        self,
        layered,
        loss_on_logits: Optional[Callable] = None,
        batch_to_args: Optional[Callable] = None,
    ):
        self.layered = layered
        self.loss_on_logits = loss_on_logits or default_causal_lm_logits_loss
        self.batch_to_args = batch_to_args or _default_batch_to_args

    def prelude(self, prelude_params, batch):
        return self.layered.apply_prelude(prelude_params, *self.batch_to_args(batch))

    def layer(self, layer_params, carry):
        return self.layered.apply_layer(layer_params, carry)

    def tail(self, tail_params, carry):
        return self.layered.apply_tail(tail_params, carry)


class EncoderDecoderPipelineSpec(PipelineSpec):
    """Stage functions for a two-stack (encoder-decoder) model, over the
    `T5PipelineApply`-shaped protocol: split -> (prelude, enc_layers, dec_layers,
    tail), apply_prelude/apply_enc_layer/apply_promote/apply_dec_layer/apply_tail.
    The reference reaches this only through Megatron's T5 schedule
    (utils/megatron_lm.py:702,1004-1010)."""

    def __init__(
        self,
        layered,
        loss_on_logits: Optional[Callable] = None,
        batch_to_args: Optional[Callable] = None,
    ):
        super().__init__(
            layered,
            loss_on_logits or default_seq2seq_logits_loss,
            batch_to_args or _default_seq2seq_batch_to_args,
        )

    def promote(self, prelude_params, carry):
        return self.layered.apply_promote(prelude_params, carry)

    def enc_layer(self, layer_params, carry):
        return self.layered.apply_enc_layer(layer_params, carry)

    def dec_layer(self, layer_params, carry):
        return self.layered.apply_dec_layer(layer_params, carry)

    def static_carry(self, prelude_params, batch):
        """Input-independent carry entries (e.g. T5's relative-position biases):
        computed once per stage from the replicated prelude, merged into the carry
        before each layer application, and NEVER rotated over ICI."""
        fn = getattr(self.layered, "apply_static_carry", None)
        if fn is None:
            return {}
        return fn(prelude_params, *self.batch_to_args(batch))


def _split_microbatches(batch, num_microbatches: int):
    import jax

    def _split(x):
        if x.shape[0] % num_microbatches != 0:
            raise ValueError(
                f"Local batch {x.shape[0]} not divisible by num_microbatches={num_microbatches}"
            )
        return x.reshape((num_microbatches, x.shape[0] // num_microbatches) + x.shape[1:])

    return jax.tree_util.tree_map(_split, batch)


def _build_local_fns(
    spec, num_microbatches: int, compute_dtype=None, remat: bool = True, encoder_decoder: bool = False
):
    """Per-device (shard_map-level) pipelined loss and forward — ONE implementation
    for both schedules, parameterized by the tick body:

    - single-body (decoder-only): one stream; a microbatch rides the ring once
      (drain S-1, schedule M + S - 1 ticks), each stage scanning its local chunk
      of the one stacked layer body.
    - encoder-decoder (`encoder_decoder=True`): every stage holds a chunk of BOTH
      stacks and two streams are in flight; a microbatch rides the ring twice —
      encoder chunks on hops [0, S), `spec.promote` (the encoder final norm) as it
      re-enters stage 0, decoder chunks with cross-attention on hops [S, 2S) — so
      the drain is 2S-1 and the schedule M + 2S - 1 ticks. The carry pytree holds
      both hidden streams, making it uniform across every hop.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    M = num_microbatches

    if encoder_decoder:
        enc_fn, dec_fn = spec.enc_layer, spec.dec_layer
        if remat:
            enc_fn, dec_fn = jax.checkpoint(spec.enc_layer), jax.checkpoint(spec.dec_layer)
    else:
        layer_fn = jax.checkpoint(spec.layer) if remat else spec.layer

    def _prep(params, batch):
        if compute_dtype is not None:
            params = _cast_floating(params, compute_dtype)
            batch = _cast_floating(batch, compute_dtype)
        return params, batch

    def _index_mb(mbs, i):
        return jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), mbs
        )

    def _pipeline_scan(params, batch, fold_output):
        """Builds (tick, init_streams, total_ticks); `fold_output(acc, tail_p, x,
        out_mb, out_i, valid)` folds the last stage's finished carry into an
        accumulator. The scan carry is (streams_tuple, acc)."""
        prelude_p, tail_p = params["prelude"], params["tail"]
        S = lax.axis_size("stage")
        idx = lax.axis_index("stage")
        mbs = _split_microbatches(batch, M)
        mb0 = _index_mb(mbs, jnp.int32(0))
        # Input-independent carry entries (spec.static_carry, e.g. T5's relative
        # biases): every stage computes them locally from the replicated prelude;
        # they merge into the carry before each layer application and never ride
        # the ppermute ring.
        static = {}
        if encoder_decoder and hasattr(spec, "static_carry"):
            static = spec.static_carry(prelude_p, mb0)

        def _strip(c):
            return {k: v for k, v in c.items() if k not in static} if static else c

        def _merge(c):
            return {**c, **static} if static else c

        carry_struct = jax.eval_shape(lambda p, m: _strip(spec.prelude(p, m)), prelude_p, mb0)
        zeros = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), carry_struct)
        perm = [(i, (i + 1) % S) for i in range(S)]
        drain = (2 * S - 1) if encoder_decoder else (S - 1)

        def rotate(x):
            return jax.tree_util.tree_map(lambda a: lax.ppermute(a, "stage", perm), x)

        def tick(carry, t):
            streams, acc = carry
            mb = _index_mb(mbs, jnp.clip(t, 0, M - 1))
            if encoder_decoder:
                s0, s1 = streams
                # Stage 0 retires both incoming carries: the enc-stream carry that
                # just completed its S encoder chunks promotes into the dec stream
                # (replacing the dec carry that folded last tick), and a fresh
                # microbatch injects into the enc stream.
                x1 = lax.cond(
                    idx == 0, lambda s: _strip(spec.promote(prelude_p, _merge(s))), lambda s: s1, s0
                )
                x0 = lax.cond(
                    idx == 0, lambda s: _strip(spec.prelude(prelude_p, mb)), lambda s: s, s0
                )
                x0, _ = lax.scan(
                    lambda h, lp: (_strip(enc_fn(lp, _merge(h))), None), x0, params["enc_layers"]
                )
                x1, _ = lax.scan(
                    lambda h, lp: (_strip(dec_fn(lp, _merge(h))), None), x1, params["dec_layers"]
                )
                out_x, new_streams = _merge(x1), (rotate(x0), rotate(x1))
            else:
                (s0,) = streams
                # Only stage 0 pays the prelude FLOPs; everyone else keeps the
                # carry it received last tick.
                x = lax.cond(idx == 0, lambda s: spec.prelude(prelude_p, mb), lambda s: s, s0)
                x, _ = lax.scan(lambda h, lp: (layer_fn(lp, h), None), x, params["layers"])
                out_x, new_streams = x, (rotate(x),)
            out_i = jnp.clip(t - drain, 0, M - 1)
            valid = jnp.logical_and(t >= drain, idx == S - 1)
            acc = fold_output(acc, tail_p, out_x, _index_mb(mbs, out_i), out_i, valid)
            return (new_streams, acc), None

        init_streams = (zeros, zeros) if encoder_decoder else (zeros,)
        return tick, init_streams, M + drain, (prelude_p, tail_p)

    def _loss_pair(tail_p, carry, mb):
        """Normalize loss_on_logits output to a (loss_sum, weight) pair: fns returning a
        plain scalar (a microbatch mean) get weight 1 — equal-weight averaging; pair
        returns give exact token-weighted parity with the unpipelined loss.

        Both entries are shape (1,), NOT 0-d: every float scalar in this body risks
        becoming a 0-d residual of the differentiated shard_map, whose partial-eval
        missed scalar-residual promotion for forwarded residuals when this was
        written (jax 0.4.37) — the transpose then failed _check_names
        (leading-axis sharding on a 0-d aval). Shape (1,) is right on every version."""
        out = spec.loss_on_logits(spec.tail(tail_p, carry), mb)
        if isinstance(out, tuple):
            s, w = out
            return s.astype(jnp.float32).reshape(1), w.astype(jnp.float32).reshape(1)
        return out.astype(jnp.float32).reshape(1), jnp.ones((1,), jnp.float32)

    def local_loss(params, batch):
        params, batch = _prep(params, batch)

        def fold(acc, tail_p, x, out_mb, out_i, valid):
            # Only the last stage pays the tail (lm_head) FLOPs.
            s, w = lax.cond(
                valid,
                lambda c: _loss_pair(tail_p, c, out_mb),
                lambda c: (jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.float32)),
                x,
            )
            return (acc[0] + s, acc[1] + w)

        tick, init_streams, total, _ = _pipeline_scan(params, batch, fold)
        (_, (loss_sum, weight)), _ = lax.scan(
            tick,
            (init_streams, (jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.float32))),
            jnp.arange(total),
        )
        axes = ("stage", "data", "fsdp")
        loss_sum = lax.psum(loss_sum, axes)
        weight = lax.psum(weight, axes)
        # Return the unreduced (loss_sum, weight) pair; the caller divides OUTSIDE the
        # shard_map. Keeping the division inside makes `weight` a 0-d float residual of
        # the differentiated body — see _loss_pair for why no float scalar may
        # become a residual of the differentiated shard_map.
        return loss_sum, weight

    def local_forward(params, batch):
        params, batch = _prep(params, batch)
        prelude_p, tail_p = params["prelude"], params["tail"]
        mbs = _split_microbatches(batch, M)
        mb0 = _index_mb(mbs, np.int32(0))
        carry_struct = jax.eval_shape(spec.prelude, prelude_p, mb0)
        out_struct = jax.eval_shape(spec.tail, tail_p, carry_struct)
        buf0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros((M,) + s.shape, s.dtype), out_struct
        )

        def fold(buf, tail_p, x, out_mb, out_i, valid):
            out = lax.cond(
                valid,
                lambda c: spec.tail(tail_p, c),
                lambda c: jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), out_struct),
                x,
            )
            return jax.tree_util.tree_map(
                lambda b, o: lax.cond(
                    valid,
                    lambda args: lax.dynamic_update_index_in_dim(args[0], args[1], out_i, 0),
                    lambda args: args[0],
                    (b, o),
                ),
                buf,
                out,
            )

        tick, init_streams, total, _ = _pipeline_scan(params, batch, fold)
        (_, buf), _ = lax.scan(tick, (init_streams, buf0), jnp.arange(total))
        # Outputs live on the last stage only; psum broadcasts them (zeros elsewhere).
        buf = jax.tree_util.tree_map(lambda b: lax.psum(b, "stage"), buf)
        return jax.tree_util.tree_map(lambda b: b.reshape((-1,) + b.shape[2:]), buf)

    return local_loss, local_forward


class PipelinedModel:
    """A model placed on the mesh's "stage" axis, quacking like `PreparedModel` so it
    slots into `Accelerator.backward`/`AcceleratedOptimizer` unchanged.

    params = {"prelude": replicated, "layers": [L, ...] stacked & stage-sharded,
    "tail": replicated}. `loss(params, batch)` is the pipelined scan; `__call__(batch)`
    is the pipelined forward returning logits.
    """

    is_pipelined = True
    # Pipeline params always live in device memory (stage-sharded HBM); the
    # host-offload tiers (modeling.py:145-161) don't compose with the stage scan.
    offload_params = False

    def to_compute_memory(self, params):
        """PreparedModel protocol (modeling.py:145): identity — never offloaded."""
        return params

    def to_storage_memory(self, params):
        """PreparedModel protocol (modeling.py:154): identity — never offloaded."""
        return params

    def __init__(
        self,
        model,
        layered,
        mesh,
        num_microbatches: int = 4,
        loss_on_logits: Optional[Callable] = None,
        batch_to_args: Optional[Callable] = None,
        compute_dtype=None,
        autocast: bool = True,
        remat: bool = True,
    ):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if mesh.shape.get("model", 1) > 1 or mesh.shape.get("seq", 1) > 1:
            raise NotImplementedError(
                "Pipeline parallelism currently composes with data/fsdp axes only "
                "(tp/sp inside pipeline stages needs manual-collective layers)."
            )
        self.mesh = mesh
        self.module = getattr(model, "module", None)
        self.layered = layered
        self.compute_dtype = compute_dtype
        self.autocast_enabled = autocast and compute_dtype is not None
        self.num_microbatches = num_microbatches
        # Two-stack (encoder-decoder) decompositions implement the
        # T5PipelineApply-shaped protocol and run the two-phase ring schedule.
        self.is_encoder_decoder = hasattr(layered, "apply_enc_layer")
        self.spec = (
            EncoderDecoderPipelineSpec(layered, loss_on_logits, batch_to_args)
            if self.is_encoder_decoder
            else PipelineSpec(layered, loss_on_logits, batch_to_args)
        )

        import jax

        # Stage assignment is planner-emitted (plan_pipeline_stages balances
        # contiguous ranges on per-layer bytes); the SPMD runner below stacks
        # layer params into one [L, ...] buffer sharded P("stage") on the
        # leading dim, which can only EXECUTE the uniform (equal-count) shape —
        # non-uniform balanced plans need an MPMD runner.
        from .planner import plan_pipeline_stages

        def _stage_plan(stack, kind):
            if len(stack) % n_stages != 0:
                raise ValueError(
                    f"{len(stack)} {kind} layers not divisible by {n_stages} pipeline "
                    f"stages (the SPMD stage runner scans equal-count stages only; "
                    f"non-uniform plans run on the MPMD runner — build the mesh with "
                    f"a 'pipeline' axis and use parallel.mpmd.prepare_mpmd_pipeline "
                    f"or Accelerator.prepare(sharding_rules='auto'))"
                )
            plan = plan_pipeline_stages(stack, n_stages)
            if not plan.uniform:
                raise ValueError(
                    f"{plan.num_layers} {kind} layers not divisible by {n_stages} "
                    f"pipeline stages (the planner's byte-balanced assignment "
                    f"{plan.assignment} is non-uniform; the SPMD stage runner "
                    f"scans equal-count stages only — non-uniform plans run on the "
                    f"MPMD runner: build the mesh with a 'pipeline' axis and use "
                    f"parallel.mpmd.prepare_mpmd_pipeline or "
                    f"Accelerator.prepare(sharding_rules='auto'))"
                )
            return plan

        n_stages = mesh.shape["stage"]
        if self.is_encoder_decoder:
            prelude, enc_layers, dec_layers, tail = layered.split(model.params)
            self.num_layers = (len(enc_layers), len(dec_layers))
            self.stage_plans = {
                "enc_layers": _stage_plan(enc_layers, "encoder"),
                "dec_layers": _stage_plan(dec_layers, "decoder"),
            }
            self.stage_plan = self.stage_plans["dec_layers"]
            layer_groups = {"enc_layers": enc_layers, "dec_layers": dec_layers}
        else:
            prelude, layers, tail = layered.split(model.params)
            self.num_layers = len(layers)
            # Stages scan ONE layer body, so every layer entry must share a pytree
            # structure. Mixed-structure streaming decompositions (T5LayeredApply)
            # can't scan — point at the pipeline protocol instead.
            structures = {jax.tree_util.tree_structure(lp) for lp in layers}
            if len(structures) > 1:
                raise NotImplementedError(
                    "Pipeline parallelism requires homogeneous layer blocks (one "
                    "scanned body); this LayeredApply yields mixed structures "
                    "(encoder-decoder). Use the two-stack pipeline protocol instead "
                    "(e.g. models.t5.T5PipelineApply), or tier-streamed execution: "
                    "accelerate_tpu.big_modeling.dispatch_model/cpu_offload with the "
                    "same LayeredApply."
                )
            self.stage_plan = _stage_plan(layers, "transformer")
            self.stage_plans = {"layers": self.stage_plan}
            layer_groups = {"layers": layers}
        self.sharding_rules = list(self.stage_plan.rules)
        # Tied weights (e.g. embed_tokens reused by a tied lm head) appear in both the
        # prelude and the tail after split. Store them ONCE (in the prelude) and
        # re-inject the prelude's copy into the tail view inside the differentiated
        # functions — otherwise the two copies would receive independent partial
        # gradients and silently diverge under the optimizer.
        self._ties = find_tied_leaves(prelude, tail)
        for tail_path, _ in self._ties:
            tail = _dict_path_del(tail, tail_path)
        # Stack the per-layer pytrees directly into stage-sharded buffers, one
        # device-local [L/S, ...] slice at a time (stack_layer_params_sharded) so the
        # full stacked model never materializes on one device.
        self.param_sharding = {
            "prelude": jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), prelude),
            "tail": jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), tail),
        }
        stacked_groups = {}
        for group_name, stack in layer_groups.items():
            stacked_struct = jax.eval_shape(stack_layer_params, stack)
            group_sharding = jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P("stage")), stacked_struct
            )
            stacked_groups[group_name] = stack_layer_params_sharded(stack, group_sharding)
            self.param_sharding[group_name] = group_sharding
        from .sharding import place_params

        placed = place_params(
            {"prelude": prelude, "tail": tail},
            {"prelude": self.param_sharding["prelude"], "tail": self.param_sharding["tail"]},
        )
        self.params = {"prelude": placed["prelude"], "tail": placed["tail"], **stacked_groups}

        local_loss, local_forward = _build_local_fns(
            self.spec,
            num_microbatches,
            compute_dtype=compute_dtype if self.autocast_enabled else None,
            remat=remat,
            encoder_decoder=self.is_encoder_decoder,
        )
        from .sharding import data_spec as _data_spec

        from jax import shard_map

        data_spec = _data_spec(mesh)
        param_specs = {
            "prelude": P(),
            "tail": P(),
            **{name: P("stage") for name in layer_groups},
        }
        # check_vma off: the scan carry deliberately mixes device-varying values (the
        # rotating activations) with unvarying zeros at t=0, which the VMA type system
        # rejects; correctness is covered by the parity tests.
        smap_kwargs = dict(mesh=mesh, in_specs=(param_specs, data_spec), check_vma=False)

        def _with_ties(fn):
            if not self._ties:
                return fn
            ties = self._ties

            def inner(params, batch):
                tail = params["tail"]
                for tail_path, prelude_path in ties:
                    tail = _dict_path_set(
                        tail, tail_path, _dict_path_get(params["prelude"], prelude_path)
                    )
                return fn({**params, "tail": tail}, batch)

            return inner

        _loss_pair_fn = shard_map(
            _with_ties(local_loss), out_specs=(P(), P()), **smap_kwargs
        )

        def _loss(params, batch):
            import jax.numpy as jnp

            loss_sum, weight = _loss_pair_fn(params, batch)
            return (loss_sum / jnp.maximum(weight, 1e-9))[0]

        self._loss_fn = _loss
        self._forward_fn = shard_map(_with_ties(local_forward), out_specs=data_spec, **smap_kwargs)
        self._jit_forward = None
        # Accelerator.autocast toggles clear this on every registered model; the
        # pipeline's compute dtype is baked into the shard_map fns at construction, so
        # clearing it is a harmless no-op here.
        self._jit_cache: dict = {}

    # -- PreparedModel-compatible surface ---------------------------------------------
    def loss(self, params, batch):
        """Differentiable pipelined loss — the canonical argument to Accelerator.backward."""
        return self._loss_fn(params, batch)

    def __call__(self, batch):
        import jax

        if self._jit_forward is None:
            self._jit_forward = jax.jit(self._forward_fn)
        return self._jit_forward(self.params, batch)

    def eval_apply(self, batch):
        return self(batch)

    def state_dict(self):
        return self.params

    def load_state_dict(self, params):
        from .sharding import place_params

        # place_params (not device_put): loaded buffers must not alias the caller's
        # arrays — the optimizer's donated update deletes ours every step.
        self.params = place_params(params, self.param_sharding)

    def merged_params(self):
        """Params back in the original (unstacked) model layout — for saving checkpoints
        interchangeable with the non-pipelined model."""
        if self.is_encoder_decoder:
            n_enc, n_dec = self.num_layers
            enc = unstack_layer_params(self.params["enc_layers"], n_enc)
            dec = unstack_layer_params(self.params["dec_layers"], n_dec)
            return self.layered.join(self.params["prelude"], enc, dec, self.params["tail"])
        layers = unstack_layer_params(self.params["layers"], self.num_layers)
        return self.layered.join(self.params["prelude"], layers, self.params["tail"])

    @property
    def num_parameters(self) -> int:
        import jax

        return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(self.params))

    def __repr__(self):
        return (
            f"PipelinedModel(layers={self.num_layers}, stages={self.mesh.shape['stage']}, "
            f"microbatches={self.num_microbatches}, params={self.num_parameters:,})"
        )


def prepare_pipeline(
    model,
    layered,
    mesh=None,
    num_microbatches: int = 4,
    loss_on_logits: Optional[Callable] = None,
    batch_to_args: Optional[Callable] = None,
    compute_dtype=None,
    remat: bool = True,
) -> PipelinedModel:
    """Build a PipelinedModel from a Model bundle + its LayeredApply decomposition
    (the user-facing PP entry, Megatron `pp_degree` / PiPPy `prepare_pippy` parity)."""
    from ..state import AcceleratorState, PartialState

    if mesh is None:
        mesh = AcceleratorState().mesh
    # FSDP sync_module_states applies to pipelined models too (prepare_model's
    # broadcast can't reach them — they arrive at Accelerator.prepare already
    # placed): rank 0's initial weights win BEFORE stage placement.
    shared = AcceleratorState._shared_state
    fsdp = shared.get("fsdp_plugin") if shared else None
    if (
        fsdp is not None
        and getattr(fsdp, "sync_module_states", False)
        and PartialState._shared_state
        and PartialState().num_processes > 1
    ):
        from ..utils.operations import broadcast

        model.params = broadcast(model.params, from_process=0)
    if compute_dtype is None:
        # Inherit the Accelerator's mixed-precision policy (prepare_model parity —
        # accelerator.py sets compute_dtype from state for non-pipelined models).
        shared = AcceleratorState._shared_state
        if shared and shared.get("_mixed_precision") in ("bf16", "fp16", "fp8"):
            compute_dtype = AcceleratorState().compute_dtype
    return PipelinedModel(
        model,
        layered,
        mesh,
        num_microbatches=num_microbatches,
        loss_on_logits=loss_on_logits,
        batch_to_args=batch_to_args,
        compute_dtype=compute_dtype,
        remat=remat,
    )
