"""Fused training step: one compiled program per optimizer step.

The eager-feel path (`Accelerator.backward` -> `optimizer.step` -> `zero_grad`)
dispatches >=3 compiled programs per step (grad, accumulate-add, update) with host
round-trips between them — the reference's backward/step choreography
(accelerator.py:2093-2121, optimizer.py:125-168) translated call-for-call. On TPU
the dispatch gaps are dead MXU time, so the hot path belongs in ONE jitted call:
value_and_grad + optional global-norm clip + optax update, with donated
params/opt-state so XLA updates weights in place in HBM.

Gradient accumulation becomes a `lax.scan` over microbatches inside the same
program (SURVEY §7 "hard parts": the `sync_gradients` boundary is the scan
boundary), instead of N eager microbatch dispatches plus an accumulate-add each.

The eager API remains the compatibility surface; `Accelerator.train_step` is the
performance path used by `bench.py` and `examples/`.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

from .logging import get_logger

logger = get_logger(__name__)


class FusedTrainStep:
    """Callable `step_fn(batch) -> loss` running grad+clip+update as one program.

    - `loss_fn(params, *args, **kwargs)` returns a scalar loss (or `(loss, aux)`);
      defaults to the model bundle's `loss`.
    - `accumulation_steps=k > 1`: the call takes ONE positional batch pytree whose
      arrays stack k microbatches along dim 0 (shape `[k*b, ...]`); gradients are
      accumulated across a `lax.scan` and the mean microbatch loss is returned
      (aux outputs are not available in this mode).
    - fp16 dynamic loss scaling and skipped-step detection follow the eager path's
      contract (`optimizer.step_was_skipped`, scaler backoff).
    - The learning-rate override installed by `AcceleratedScheduler.step()` via
      `optimizer.set_learning_rate` is honored (requires `optax.inject_hyperparams`,
      same as the eager path).
    - `steps_per_call=K > 1` runs K FULL optimizer steps as one compiled program
      (an outer `lax.scan` whose carry is (params, opt_state)): the call takes one
      batch pytree stacking K step-batches along dim 0 (`[K*b, ...]`) and returns
      the last step's loss (loss functions returning `(loss, aux)` are rejected —
      the scan would drop every step's aux). This is the device-training-loop mode: per-call host
      work (argument processing, dispatch) is paid once
      per K steps instead of per step, which is where small-step configs lose
      their MFU. LR override and loss scale are read once per call, so a
      scheduler advances in K-step strides; dynamic fp16 scaling needs per-step
      host decisions and is rejected (use bf16 — TPU-native — or K=1).
    """

    def __init__(
        self,
        model,
        optimizer,
        loss_fn: Optional[Callable] = None,
        max_grad_norm: Optional[float] = None,
        accumulation_steps: int = 1,
        gradient_state=None,
        steps_per_call: int = 1,
        tracer=None,
    ):
        self.model = model
        self.optimizer = optimizer
        # Optional telemetry tracer (Accelerator.train_step passes its own):
        # program (re)builds and skipped fp16 steps become trace events, so a
        # timeline shows WHY a step was slow (fresh trace) or absent (skip).
        self.tracer = tracer
        self.loss_fn = loss_fn if loss_fn is not None else model.loss
        self.max_grad_norm = max_grad_norm
        self.accumulation_steps = int(accumulation_steps or 1)
        self.steps_per_call = int(steps_per_call or 1)
        if self.steps_per_call > 1:
            scaler = optimizer.scaler
            if scaler is not None and scaler.enabled:
                raise ValueError(
                    "steps_per_call > 1 cannot honor dynamic fp16 loss scaling "
                    "(scale updates are per-step host decisions); use bf16 mixed "
                    "precision or steps_per_call=1"
                )
            if optimizer.offload_opt_state:
                raise ValueError(
                    "steps_per_call > 1 is incompatible with offloaded optimizer "
                    "state (each step streams state through HBM group by group)"
                )
        self.gradient_state = gradient_state
        self._jitted: dict = {}

    # ---- program construction ---------------------------------------------------------
    def _build(self, with_lr: bool):
        import jax
        import jax.numpy as jnp

        tx = self.optimizer.tx
        k = self.accumulation_steps
        max_norm = self.max_grad_norm
        scaler = self.optimizer.scaler
        use_scaler = scaler is not None and scaler.enabled
        loss_fn = self.loss_fn
        mesh = getattr(self.model, "mesh", None)

        def grads_of(params, scale, *args, **kwargs):
            def scaled(p):
                out = loss_fn(p, *args, **kwargs)
                loss, aux = out if isinstance(out, tuple) else (out, None)
                return loss * scale, (loss, aux)

            with jax.named_scope("forward_backward"):
                return jax.grad(scaled, has_aux=True)(params)

        def split_leading(batch, n, what):
            def _split(x):
                if x.shape[0] % n:
                    raise ValueError(f"{what}={n} must divide the batch dim ({x.shape[0]})")
                return x.reshape((n, x.shape[0] // n) + x.shape[1:])

            mb = jax.tree_util.tree_map(_split, batch)
            if mesh is not None and ("data" in mesh.shape or "fsdp" in mesh.shape):
                from jax.sharding import NamedSharding, PartitionSpec

                axes = tuple(a for a in ("data", "fsdp") if a in mesh.shape)
                spec = NamedSharding(mesh, PartitionSpec(None, axes))

                def _constrain(x):
                    if x.ndim >= 2:
                        return jax.lax.with_sharding_constraint(x, spec)
                    return x

                mb = jax.tree_util.tree_map(_constrain, mb)
            return mb

        def split_microbatches(batch):
            return split_leading(batch, k, "accumulation_steps")

        to_compute = getattr(self.model, "to_compute_memory", lambda p: p)
        opt_to_compute = self.optimizer.opt_to_compute_memory

        def compute_grads(params, scale, *args, **kwargs):
            if k > 1:
                if len(args) != 1 or kwargs:
                    raise ValueError(
                        "accumulation_steps > 1 takes exactly one positional batch pytree"
                    )
                microbatches = split_microbatches(args[0])
                # reduce_dtype (FSDP MixedPrecision parity): the accumulation
                # buffer dtype. With bf16 params, k bf16 adds roll off mantissa
                # bits; an fp32 buffer keeps the accumulated gradient exact, cast
                # back to the param dtype only at the update.
                reduce_dtype = getattr(self.model, "reduce_dtype", None)

                def body(acc, mbatch):
                    g, (loss, _aux) = grads_of(params, scale, mbatch)
                    if reduce_dtype is not None:
                        g = jax.tree_util.tree_map(lambda x: x.astype(reduce_dtype), g)
                    return jax.tree_util.tree_map(jnp.add, acc, g), loss

                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, reduce_dtype or p.dtype), params
                )
                grads, losses = jax.lax.scan(body, zeros, microbatches)
                if reduce_dtype is not None:
                    grads = jax.tree_util.tree_map(lambda g, p: g.astype(p.dtype), grads, params)
                return grads, jnp.mean(losses), None
            grads, (loss, aux) = grads_of(params, scale, *args, **kwargs)
            return grads, loss, aux

        if self.optimizer.offload_opt_state:
            # Chunked-offload mode: the update CANNOT live in this program (streaming
            # the whole host-resident state would OOM HBM — optimizer.py
            # apply_chunked_update). This program does grads + unscale/finite/clip
            # (the shared unscale_and_clip, same ordering as apply_update_core); the
            # per-group update programs follow in __call__.
            from .optimizer import unscale_and_clip

            def grads_program(params, scale, inv_scale, *args, **kwargs):
                params = to_compute(params)
                grads, loss, aux = compute_grads(params, scale, *args, **kwargs)
                grads, finite = unscale_and_clip(grads, inv_scale, max_norm, use_scaler)
                return grads, loss, aux, finite

            return jax.jit(grads_program)

        # Pin updated params/opt-state to their DERIVED shardings: the jit has no
        # out_shardings, so without constraints XLA may re-layout outputs (e.g.
        # shard a replicated embedding over fsdp after step 1), silently drifting
        # from the wrap policy the user configured and changing the collective
        # pattern between the first and later steps.
        param_out_sharding = getattr(self.model, "param_compute_sharding", None)
        opt_out_sharding = getattr(self.optimizer, "_opt_compute_sharding", None) or getattr(
            self.optimizer, "opt_state_sharding", None
        )

        from .optimizer import apply_update_core

        def one_step(params, opt_state, scale, inv_scale, lr, *args, **kwargs):
            grads, loss, aux = compute_grads(params, scale, *args, **kwargs)
            new_params, new_opt_state, finite = apply_update_core(
                tx,
                params,
                opt_state,
                grads,
                inv_scale,
                lr if with_lr else None,
                use_scaler=use_scaler,
                max_norm=max_norm,
            )
            if param_out_sharding is not None:
                new_params = jax.lax.with_sharding_constraint(new_params, param_out_sharding)
            if opt_out_sharding is not None:
                new_opt_state = jax.lax.with_sharding_constraint(new_opt_state, opt_out_sharding)
            return new_params, new_opt_state, loss, aux, finite

        n_steps = self.steps_per_call

        def fused(params, opt_state, scale, inv_scale, lr, *args, **kwargs):
            # Host-offloaded tiers stream to device memory at the top of the
            # program; the caller writes results back to pinned host.
            params = to_compute(params)
            opt_state = opt_to_compute(opt_state)
            if n_steps == 1:
                return one_step(params, opt_state, scale, inv_scale, lr, *args, **kwargs)

            # Device training loop: scan K full optimizer steps over K stacked
            # step-batches. One dispatch, one donation round trip, K updates.
            if len(args) != 1 or kwargs:
                raise ValueError("steps_per_call > 1 takes exactly one positional batch pytree")
            step_batches = split_leading(args[0], n_steps, "steps_per_call")

            def body(carry, sbatch):
                p, s = carry
                new_p, new_s, loss, aux, finite = one_step(p, s, scale, inv_scale, lr, sbatch)
                if aux is not None:
                    # Trace-time check: the scan returns only the last step's
                    # loss, so an aux value would be silently dropped and the
                    # caller's `loss, aux = step_fn(batch)` unpack would break.
                    raise ValueError(
                        "steps_per_call > 1 does not support loss functions that "
                        "return (loss, aux); use steps_per_call=1 for aux outputs"
                    )
                return (new_p, new_s), (loss, finite)

            (new_params, new_opt_state), (losses, finites) = jax.lax.scan(
                body, (params, opt_state), step_batches
            )
            return new_params, new_opt_state, losses[-1], None, jnp.all(finites)

        return jax.jit(fused, donate_argnums=(0, 1))

    # ---- the hot call -----------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        import jax.numpy as jnp

        opt = self.optimizer
        scaler = opt.scaler
        use_scaler = scaler is not None and scaler.enabled
        loss_scale = scaler.scale if use_scaler else 1.0
        scale = loss_scale / self.accumulation_steps
        inv_scale = 1.0 / loss_scale
        lr = opt._lr_override
        with_lr = lr is not None
        # In offload mode the jitted program is grads-only — lr enters via
        # apply_chunked_update — so one cache entry serves both lr states
        # (a with_lr-keyed cache would recompile the identical program the
        # first time a scheduler installs an override). The sentinel keeps it
        # distinct from the fused program in case offload_opt_state is toggled
        # mid-run (e.g. LocalSGD collapse).
        if opt.offload_opt_state and self.steps_per_call > 1:
            # Guarded at construction, but offload can be toggled after (e.g.
            # LocalSGD collapse): the offload program has no step scan and would
            # silently consume the [K*b] stacked batch as ONE giant step.
            raise ValueError(
                "steps_per_call > 1 is incompatible with offloaded optimizer state "
                "(toggled on after train_step was built); rebuild with steps_per_call=1"
            )
        cache_key = "offload" if opt.offload_opt_state else with_lr
        if cache_key not in self._jitted:
            if self.tracer is not None:
                self.tracer.event(
                    "train.build_program", category="train", key=str(cache_key)
                )
            self._jitted[cache_key] = self._build(cache_key)
        # Scalars change rarely (scale only on scaler growth/backoff, lr per
        # scheduler step); cache their device buffers so the hot loop doesn't pay
        # three host->device transfers per step.
        key = (scale, inv_scale, lr if with_lr else 0.0)
        if key != getattr(self, "_scalar_key", None):
            self._scalar_key = key
            self._scalar_bufs = tuple(jnp.asarray(v, jnp.float32) for v in key)
        if opt.offload_opt_state:
            # grads program (unscale+clip inside), then the chunked per-group update.
            grads, loss, aux, finite = self._jitted[cache_key](
                self.model.params, self._scalar_bufs[0], self._scalar_bufs[1], *args, **kwargs
            )
            new_params, finite = opt.apply_chunked_update(
                self.model.params, grads, 1.0, lr, finite=finite
            )
            self.model.params = new_params
        else:
            new_params, new_opt_state, loss, aux, finite = self._jitted[cache_key](
                self.model.params,
                opt.opt_state,
                *self._scalar_bufs,
                *args,
                **kwargs,
            )
            if hasattr(self.model, "to_storage_memory"):
                new_params = self.model.to_storage_memory(new_params)
            self.model.params = new_params
            opt.opt_state = opt.opt_to_storage_memory(new_opt_state)
        opt._grads = None
        opt._accum_count = 0
        if use_scaler:
            found_inf = not bool(finite)
            scaler.update(found_inf)
            opt.step_was_skipped = found_inf
            if found_inf:
                logger.warning(
                    "Skipping fused step: non-finite gradients (loss scale -> %s)", scaler.scale
                )
                if self.tracer is not None:
                    self.tracer.event(
                        "train.step_skipped", category="train",
                        loss_scale=float(scaler.scale),
                    )
        else:
            opt.step_was_skipped = False
        # Every fused call IS a full optimizer step: mark the sync boundary so
        # schedulers/clipping/gather_for_metrics see the same contract as the
        # eager accumulate() flow.
        if self.gradient_state is not None:
            self.gradient_state._set_sync_gradients(True)
        from .utils.environment import fence_if_cpu

        fence_if_cpu(loss)
        if aux is not None:
            return loss, aux
        return loss
