"""Optimizer wrapper (L3): optax under an Accelerate-shaped interface.

TPU-native redesign of reference optimizer.py (214 LoC). The reference's core trick —
lazily all-reducing gradients exactly once per optimizer step on XLA
(optimizer.py:140-146) — disappears here: gradients of a sharded-batch loss w.r.t.
replicated/sharded params already carry the correct psum/reduce-scatter from GSPMD. What
remains, and is kept contract-identical:

  - `step()` is a no-op while `GradientState.sync_gradients` is False (accumulation);
  - `zero_grad()` clears the accumulated gradient buffer;
  - fp16 dynamic loss scaling with skipped-step detection (`optimizer.step_was_skipped`,
    reference optimizer.py:153-168) — bf16 (the TPU default) never needs it;
  - gradient clipping folded into the jitted update (reference clips pre-step,
    accelerator.py:2221).

All device math is jitted with donated buffers: accumulate-add donates the accumulator,
the fused update donates (params, opt_state, grads).
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

from .logging import get_logger
from .state import AcceleratorState, GradientState
from .utils.dataclasses import GradScalerKwargs
from .utils.environment import fence_if_cpu

logger = get_logger(__name__)


class GradScaler:
    """Dynamic loss scaling for fp16 (reference uses torch.cuda.amp.GradScaler,
    accelerator.py:455-479; this is the functional JAX equivalent)."""

    def __init__(self, kwargs: Optional[GradScalerKwargs] = None):
        kwargs = kwargs or GradScalerKwargs()
        self.scale = float(kwargs.init_scale)
        self.growth_factor = kwargs.growth_factor
        self.backoff_factor = kwargs.backoff_factor
        self.growth_interval = kwargs.growth_interval
        self.enabled = kwargs.enabled
        self._growth_tracker = 0

    def update(self, found_inf: bool):
        if not self.enabled:
            return
        if found_inf:
            self.scale *= self.backoff_factor
            self._growth_tracker = 0
        else:
            self._growth_tracker += 1
            if self._growth_tracker >= self.growth_interval:
                self.scale *= self.growth_factor
                self._growth_tracker = 0

    def state_dict(self):
        return {"scale": self.scale, "growth_tracker": self._growth_tracker}

    def load_state_dict(self, state):
        self.scale = state["scale"]
        self._growth_tracker = state["growth_tracker"]


def unscale_and_clip(grads, inv_scale, max_norm: Optional[float], use_scaler: bool):
    """Traced: unscale -> finite check -> optional global-norm clip. The ONE place
    this logic lives; apply_update_core and the offload grads program share it.
    Returns (grads, finite)."""
    import jax
    import jax.numpy as jnp

    # Preserve the gradient dtype: inv_scale is a strong fp32 scalar and would
    # silently promote bf16 grads (and through them the whole update + params)
    # to fp32, breaking param_dtype storage.
    with jax.named_scope("clip"):
        grads = jax.tree_util.tree_map(lambda g: (g * inv_scale).astype(g.dtype), grads)
        finite = jnp.array(True)
        if use_scaler:
            finite = jnp.all(
                jnp.stack([jnp.all(jnp.isfinite(g)) for g in jax.tree_util.tree_leaves(grads)])
            )
        if max_norm is not None:
            norm = jnp.sqrt(
                sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree_util.tree_leaves(grads))
            )
            factor = jnp.minimum(1.0, max_norm / (norm + 1e-6))
            grads = jax.tree_util.tree_map(lambda g: (g * factor).astype(g.dtype), grads)
    return grads, finite


def update_and_revert(tx, params, opt_state, grads, lr_override, finite, use_scaler: bool):
    """Traced: optional LR override -> tx.update -> skip-revert on non-finite. Shared
    by the whole-tree update and each chunked-offload group program.
    Returns (new_params, new_opt_state)."""
    import jax
    import jax.numpy as jnp

    if lr_override is not None and hasattr(opt_state, "hyperparams"):
        opt_state = opt_state._replace(hyperparams={**opt_state.hyperparams, "learning_rate": lr_override})
    with jax.named_scope("optimizer_update"):
        updates, new_opt_state = tx.update(grads, opt_state, params)
        new_params = jax.tree_util.tree_map(lambda p, u: (p + u).astype(p.dtype), params, updates)
        if use_scaler:
            # Skipped step on non-finite grads: keep the old state untouched.
            new_params = jax.tree_util.tree_map(
                lambda new, old: jnp.where(finite, new, old), new_params, params
            )
            new_opt_state = jax.tree_util.tree_map(
                lambda new, old: jnp.where(finite, new, old) if hasattr(new, "shape") else new,
                new_opt_state,
                opt_state,
            )
    return new_params, new_opt_state


def apply_update_core(
    tx,
    params,
    opt_state,
    grads,
    inv_scale,
    lr_override=None,
    *,
    use_scaler: bool = False,
    max_norm: Optional[float] = None,
):
    """Shared traced body of the optimizer update, used by both the eager
    `AcceleratedOptimizer._update_fn` and the fused train step so their semantics
    cannot drift: unscale grads -> finite check -> optional global-norm clip ->
    optional LR override -> tx.update -> skip-revert on non-finite.

    Matches the reference ordering: gradients are unscaled BEFORE clipping
    (reference accelerator.py:2186 unscale_gradients inside clip_grad_norm_).
    Returns (new_params, new_opt_state, finite).
    """
    grads, finite = unscale_and_clip(grads, inv_scale, max_norm, use_scaler)
    new_params, new_opt_state = update_and_revert(
        tx, params, opt_state, grads, lr_override, finite, use_scaler
    )
    return new_params, new_opt_state, finite


class DiskOptState:
    """Optimizer state resident on DISK — the NVMe tier of ZeRO-offload
    (reference DeepSpeed fields dataclasses.py:704-719).

    Param-shaped slots (Adam moments, ...) live in one NativeOffloadStore blob
    keyed "slot{i}/{param_path}"; shared scalar slots (step counts,
    hyperparams) stay in memory. The chunked update loop async-prefetches group
    N+1 while group N's program runs and writes results back in place, so peak
    HBM *and* host RSS stay at one parameter group."""

    def __init__(self, store, state_def, slot_is_param, scalars, param_paths, decompose, recompose):
        self.store = store
        self.state_def = state_def
        self.slot_is_param = slot_is_param
        self.scalars = scalars
        self.param_paths = param_paths
        self._decompose = decompose
        self._recompose = recompose
        # A step that failed after some groups' write-backs leaves the blob
        # partially advanced relative to the params (the in-memory tier only
        # commits after the whole loop). Poison the state so a retry fails loudly
        # instead of silently double-applying moment updates; load() clears it.
        self.poisoned = False

    def check_usable(self):
        if self.poisoned:
            raise RuntimeError(
                "disk optimizer state is inconsistent: a previous step failed after "
                "some parameter groups were written back. Restore with load_state() "
                "(or rebuild the optimizer) before continuing."
            )

    def prefetch_group(self, paths):
        self.store.prefetch_many(
            [f"slot{i}/{p}" for i, is_p in enumerate(self.slot_is_param) if is_p for p in paths]
        )

    def read_group(self, paths, scalars=None):
        """Group state pytree; `scalars` overrides the in-memory slot values (the
        chunked loop passes a pre-step snapshot so every group sees the ORIGINAL
        shared scalars, not a prior group's increment)."""
        scalars = self.scalars if scalars is None else scalars
        vals = []
        for i, is_p in enumerate(self.slot_is_param):
            if is_p:
                vals.append({p: self.store.read(f"slot{i}/{p}") for p in paths})
            else:
                vals.append(scalars[i])
        return self.state_def.unflatten(vals)

    def write_group(self, paths, new_group_state):
        import jax

        for i, val in enumerate(self.state_def.flatten_up_to(new_group_state)):
            if self.slot_is_param[i]:
                for p in paths:
                    self.store.write(f"slot{i}/{p}", np.asarray(jax.device_get(val[p])))
            else:
                self.scalars[i] = val

    def materialize(self):
        """Full state pytree on host (checkpointing; costs one pass over the blob)."""
        slots = [
            {p: self.store.read(f"slot{i}/{p}") for p in self.param_paths} if is_p else self.scalars[i]
            for i, is_p in enumerate(self.slot_is_param)
        ]
        return self._recompose(slots, self.state_def)

    def load(self, full_state):
        """Overwrite the blob from a full state pytree (checkpoint restore)."""
        import jax

        slots, _ = self._decompose(full_state)
        for i, slot in enumerate(slots):
            if self.slot_is_param[i]:
                for p, arr in slot.items():
                    self.store.write(f"slot{i}/{p}", np.asarray(jax.device_get(arr)))
            else:
                self.scalars[i] = slot
        self.poisoned = False


class AcceleratedOptimizer:
    """Wraps an `optax.GradientTransformation` bound to a `PreparedModel`
    (reference AcceleratedOptimizer optimizer.py:38).

    Holds the (sharded) optimizer state and the gradient-accumulation buffer; `step()`
    applies the fused, jitted update and writes new params back into the model.
    """

    def __init__(
        self,
        optimizer,
        model=None,
        scaler: Optional[GradScaler] = None,
        mesh=None,
        fsdp_plugin=None,
    ):
        import jax

        self.tx = optimizer
        self.model = model
        self.scaler = scaler
        self.gradient_state = GradientState()
        self.step_was_skipped = False
        self._accum_count = 0
        self._grads = None
        self._grads_unscaled = False  # set by clip_*: grads already divided by loss scale
        self._jit_cache: dict = {}

        self.offload_opt_state = False
        self._opt_compute_sharding = None
        self.is_mpmd = model is not None and getattr(model, "is_mpmd", False)
        if self.is_mpmd:
            # MPMD pipeline model: optimizer state lives PER STAGE, each piece
            # on its own stage submesh placed by that stage's ZeRO opt-rules
            # table — a single-mesh opt_state/opt_state_sharding here would be
            # meaningless (model.params spans several disjoint meshes). The
            # model owns the per-stage states and the per-stage update
            # programs; the step itself runs through Accelerator.train_step.
            self.mesh = mesh if mesh is not None else getattr(model, "mesh", None)
            self.opt_state_sharding = None
            self.opt_state = None
            model.init_optimizer_state(self.tx)
            self._lr_override = None
            return
        if model is not None:
            from .parallel.sharding import (
                derive_opt_state_shardings,
                host_memory_available,
                host_memory_kind,
                with_memory_kind,
            )

            if mesh is None:
                mesh = model.mesh
            self.mesh = mesh
            rules = getattr(model, "sharding_rules", None)
            # Planner-emitted ZeRO table (plan.opt_rules, stamped on the bundle
            # by prepare_model under sharding_rules="auto"): authoritative for
            # matched moments — shards the weight update along "data" even
            # where the params replicate.
            opt_rules = getattr(model, "opt_sharding_rules", None)
            if mesh is not None:
                state_shapes = jax.eval_shape(self.tx.init, model.params)
                self.opt_state_sharding = derive_opt_state_shardings(
                    state_shapes, mesh, fsdp_plugin, rules, opt_rules=opt_rules
                )
                offload_device = str(getattr(fsdp_plugin, "offload_optimizer_device", None) or "").lower()
                want_disk = offload_device in ("disk", "nvme")
                want_offload = bool(getattr(fsdp_plugin, "offload_optimizer_state", False)) and not want_disk
                if want_offload and not host_memory_available():
                    logger.warning(
                        "offload_optimizer_state requested but this backend exposes no "
                        "host-tier memory space (pinned_host/unpinned_host); optimizer "
                        "state stays in device memory."
                    )
                    want_offload = False
                if want_disk:
                    # NVMe tier: needs no pinned_host memory space — staging runs
                    # through host numpy around each group program.
                    import tempfile

                    directory = getattr(fsdp_plugin, "offload_dir", None) or tempfile.mkdtemp(
                        prefix="accelerate_tpu_optstate_"
                    )
                    self.offload_opt_state = True
                    self._opt_compute_sharding = self.opt_state_sharding
                    self.opt_state = self._disk_offload_init(model.params, state_shapes, directory)
                elif want_offload:
                    # ZeRO-offload tier (reference accelerator.py:1563-1785,
                    # dataclasses.py:704-719): optimizer state lives in pinned host
                    # memory; updates stream it through HBM one param GROUP at a
                    # time (apply_chunked_update). Init is chunked the same way —
                    # materializing the full state on device first would OOM by
                    # itself (fp32 Adam moments are 8 bytes/param: 12 GB for
                    # llama-1b against a 16 GB chip).
                    self.offload_opt_state = True
                    self._opt_compute_sharding = self.opt_state_sharding
                    self.opt_state_sharding = with_memory_kind(
                        self.opt_state_sharding, host_memory_kind()
                    )
                    self.opt_state = self._chunked_offload_init(model.params, state_shapes)
                else:
                    self.opt_state = jax.jit(self.tx.init, out_shardings=self.opt_state_sharding)(model.params)
            else:
                self.opt_state_sharding = None
                self.opt_state = self.tx.init(model.params)
        else:
            self.mesh = None
            self.opt_state_sharding = None
            self.opt_state = None

        self._lr_override = None

    # ---- MPMD guard ------------------------------------------------------------------
    def _reject_mpmd(self, what: str) -> None:
        """Fail loudly, not deep inside the update machinery: on the MPMD
        pipeline route this wrapper holds NO single-mesh opt_state (it lives
        per stage, on per-stage submeshes, owned by the model) — mirrors the
        error Accelerator.backward() raises on the same route."""
        if getattr(self, "is_mpmd", False):
            raise NotImplementedError(
                f"{what} operates on a single-mesh optimizer state, but this "
                "optimizer is bound to an MPMD pipeline model whose optimizer "
                "state lives per stage on per-stage submeshes. Use step_fn = "
                "Accelerator.train_step() — it runs the 1F1B schedule with "
                "per-stage accumulation and updates."
            )

    # ---- offload tier movement -------------------------------------------------------
    def opt_to_compute_memory(self, opt_state):
        """Traceable: stream host-offloaded optimizer state into device memory
        (identity when not offloaded)."""
        import jax

        if self.offload_opt_state and self._opt_compute_sharding is not None:
            return jax.device_put(opt_state, self._opt_compute_sharding)
        return opt_state

    def opt_to_storage_memory(self, opt_state):
        """Eager: place updated optimizer state back on its storage tier."""
        import jax

        if self.offload_opt_state and self.opt_state_sharding is not None:
            return jax.device_put(opt_state, self.opt_state_sharding)
        return opt_state

    # ---- chunked offload update ------------------------------------------------------
    # True ZeRO-offload cannot stream the WHOLE optimizer state to HBM for the
    # update: for llama-1b the fp32 Adam moments alone are 12 GB against a 16 GB
    # v5e chip (measured OOM). Instead the update runs as one small program per
    # parameter GROUP, so peak device memory is one group's params+grads+state.
    # The reference reaches the same place with DeepSpeed's CPU-Adam
    # (accelerator.py:1563-1785); here each group program is still an XLA program
    # with the streaming H2D/D2H on the program boundary.

    # ---- disk (NVMe) tier ------------------------------------------------------------
    def _disk_offload_init(self, params, state_shapes, directory):
        """Build the DISK-resident optimizer state (DeepSpeed NVMe-offload parity,
        reference dataclasses.py:704-719): per-group tx.init on device -> host ->
        one NativeOffloadStore blob; shared scalars (step counts, hyperparams)
        stay in memory. Neither HBM nor host RSS ever holds more than one group."""
        import jax

        from .native.offload import NativeOffloadStore
        from .parallel.sharding import tree_paths_and_leaves

        logger.warning_once(
            "offload_optimizer_device=disk: optimizer state lives in %s and updates "
            "run per parameter group (chunked streaming with async prefetch). "
            "Optax transforms needing cross-parameter statistics would compute them "
            "per group; use max_grad_norm / clip_grad_norm_ for global clipping.",
            directory,
        )
        groups = self._offload_groups(params)
        self._jit_cache["chunk_groups"] = groups
        self._jit_cache["chunk_slicer"] = self._state_slicer(params)
        chunker = self._state_chunker(params)
        self._jit_cache["chunk_chunker"] = chunker
        decompose, _group_state, _absorb, recompose = chunker
        slots_shapes, state_def = decompose(state_shapes)
        slot_is_param = [isinstance(s, dict) for s in slots_shapes]
        flat_params = dict(tree_paths_and_leaves(params)[0])

        store = NativeOffloadStore(directory)
        # Fresh state, fresh blob: a leftover store from a previous run holds
        # stale entries whose bytes would be orphaned by the append-then-repoint
        # save(), growing the blob by a full state copy per restart.
        store.reset()
        scalars = [None] * len(slots_shapes)
        for paths in groups:
            p_g = {p: flat_params[p] for p in paths}
            s_g = jax.jit(self.tx.init)(p_g)  # tpu-lint: disable=jit-in-loop (one-shot setup per group)
            for i, val in enumerate(state_def.flatten_up_to(s_g)):
                if slot_is_param[i]:
                    store.save(
                        {f"slot{i}/{p}": np.asarray(jax.device_get(a)) for p, a in val.items()},
                        flush_index=False,
                    )
                else:
                    scalars[i] = val
            del s_g  # one group of device state at a time
        store.flush_index()
        all_paths = [p for g in groups for p in g]
        return DiskOptState(store, state_def, slot_is_param, scalars, all_paths, decompose, recompose)

    def _offload_groups(self, params):
        """Partition param leaf-paths into groups under a byte budget."""
        import os

        import numpy as np

        from .parallel.sharding import tree_paths_and_leaves

        budget = int(os.environ.get("ACCELERATE_TPU_OFFLOAD_CHUNK_MB", "256")) * 1024 * 1024
        groups, cur, cur_bytes = [], [], 0
        for path, leaf in tree_paths_and_leaves(params)[0]:
            nbytes = int(np.prod(np.shape(leaf))) * getattr(leaf, "dtype", np.dtype("float32")).itemsize
            if cur and cur_bytes + nbytes > budget:
                groups.append(cur)
                cur, cur_bytes = [], 0
            cur.append(path)
            cur_bytes += nbytes
        if cur:
            groups.append(cur)
        return groups

    def _chunked_offload_init(self, params, state_shapes):
        """Build the pinned-host optimizer state without ever holding more than one
        group's state in HBM: per-group tx.init on device -> pinned-host writeback,
        then assemble the global tree directly from the group pieces (no full-size
        zeros skeleton). Group-independent scalars (step counts, hyperparams) take
        the last group's init value — identical across groups for any element-wise
        transform; transforms needing cross-parameter state are unsupported here
        (warned below) — use max_grad_norm/clip_grad_norm_ for global clipping."""
        import jax

        from .parallel.sharding import tree_paths_and_leaves

        logger.warning_once(
            "offload_optimizer_state: updates run per parameter group (chunked "
            "streaming). Optax transforms needing cross-parameter statistics inside "
            "the chain (e.g. clip_by_global_norm) would compute them per group; use "
            "max_grad_norm / clip_grad_norm_ for global clipping instead."
        )
        groups = self._offload_groups(params)
        slice_state = self._state_slicer(params)
        self._jit_cache["chunk_groups"] = groups
        self._jit_cache["chunk_slicer"] = slice_state
        ptreedef, param_paths, is_param_shaped, _to_flat = self._param_tree_tools(params)
        flat_params = dict(tree_paths_and_leaves(params)[0])

        group_states = []
        for paths in groups:
            p_g = {p: flat_params[p] for p in paths}
            s_g = jax.jit(self.tx.init)(p_g)  # tpu-lint: disable=jit-in-loop (one-shot setup per group)
            group_states.append(jax.device_put(s_g, slice_state(self.opt_state_sharding, paths)))

        def assemble(template_node, *group_nodes):
            if is_param_shaped(template_node):
                flat = {}
                for gn in group_nodes:
                    flat.update(gn)
                return jax.tree_util.tree_unflatten(ptreedef, [flat[p] for p in param_paths])
            return group_nodes[-1]

        return jax.tree_util.tree_map(assemble, state_shapes, *group_states, is_leaf=is_param_shaped)

    @staticmethod
    def _param_tree_tools(params):
        """Shared decomposition contract for optax states whose subtrees mirror the
        params treedef (adam/sgd/adafactor-family — every element-wise transform):
        (ptreedef, param_paths, is_param_shaped, to_flat)."""
        import jax

        from .parallel.sharding import tree_paths_and_leaves

        ptreedef = jax.tree_util.tree_structure(params)
        param_paths = [p for p, _ in tree_paths_and_leaves(params)[0]]

        def is_param_shaped(x):
            try:
                return jax.tree_util.tree_structure(x) == ptreedef
            except Exception:
                return False

        def to_flat(subtree):
            return dict(zip(param_paths, jax.tree_util.tree_leaves(subtree)))

        return ptreedef, param_paths, is_param_shaped, to_flat

    def _state_slicer(self, params):
        """slice_fn(state, paths) -> group state with param-mirroring subtrees
        replaced by flat {path: leaf} dicts (used for states AND their sharding
        trees; the write-back side lives in _state_chunker)."""
        import jax

        _ptreedef, _param_paths, is_param_shaped, to_flat = self._param_tree_tools(params)

        def slice_state(state, paths):
            pathset = set(paths)
            return jax.tree_util.tree_map(
                # Param-shaped subtrees (mu/nu/...) slice to the group's leaves;
                # anything else (step counts, hyperparams scalars) passes through.
                lambda sub: {p: v for p, v in to_flat(sub).items() if p in pathset}
                if is_param_shaped(sub)
                else sub,
                state,
                is_leaf=is_param_shaped,
            )

        return slice_state

    def _state_chunker(self, params):
        """O(P)-per-step decomposition of an optax state for the chunked-offload loop
        (vs O(groups x P) for slice-per-group): `decompose` flattens the state
        ONCE into slots (param-shaped subtrees -> path-keyed dicts, scalars as-is),
        `group_state` builds a group's sliced state in O(|group|), `absorb` writes a
        group's updated slots back in O(|group|), `recompose` rebuilds the full tree
        once after the loop."""
        import jax

        ptreedef, param_paths, is_param_shaped, to_flat = self._param_tree_tools(params)

        def decompose(state):
            leaves, state_def = jax.tree_util.tree_flatten(state, is_leaf=is_param_shaped)
            slots = [to_flat(l) if is_param_shaped(l) else l for l in leaves]
            return slots, state_def

        def group_state(slots, state_def, paths):
            return state_def.unflatten(
                [{p: d[p] for p in paths} if isinstance(d, dict) else d for d in slots]
            )

        def absorb(slots, state_def, new_group_state):
            # flatten_up_to stops at state_def's leaf positions, so each value is the
            # group's path-dict (param slot) or scalar (shared slot; last group wins).
            for i, val in enumerate(state_def.flatten_up_to(new_group_state)):
                if isinstance(slots[i], dict):
                    slots[i].update(val)
                else:
                    slots[i] = val

        def recompose(slots, state_def):
            return state_def.unflatten(
                [
                    jax.tree_util.tree_unflatten(ptreedef, [d[p] for p in param_paths])
                    if isinstance(d, dict)
                    else d
                    for d in slots
                ]
            )

        return decompose, group_state, absorb, recompose

    def apply_chunked_update(self, params, grads, inv_scale, lr_override, finite=None):
        """Offload-tier update: global finite check first (an fp16 skipped step must
        leave every group untouched), then tx.update one group at a time with the
        group's state streamed pinned_host -> HBM -> pinned_host around its program.
        `finite` may be precomputed by the caller's grads program.
        Returns (new_params, finite).

        NOTE: tx.update runs per GROUP, which is exact for element-wise transforms
        (adam/sgd/adafactor families). A transform needing cross-parameter statistics
        inside the chain (e.g. optax.clip_by_global_norm) would compute them per
        group — use `max_grad_norm` / `clip_grad_norm_` instead (warned at init)."""
        import jax
        import jax.numpy as jnp

        use_scaler = self.scaler is not None and self.scaler.enabled
        with_lr = lr_override is not None

        params_offloaded = bool(getattr(self.model, "offload_params", False))
        if "chunk_groups" not in self._jit_cache:
            self._jit_cache["chunk_groups"] = self._offload_groups(params)
            self._jit_cache["chunk_slicer"] = self._state_slicer(params)
        if "chunk_chunker" not in self._jit_cache:
            self._jit_cache["chunk_chunker"] = self._state_chunker(params)
        if "chunk_static" not in self._jit_cache:
            # Static tree metadata: paths, treedef, and the offload-tier sharding
            # flat-dicts never change after init; per-step values are re-zipped
            # against the cached paths below (tree_leaves order is deterministic).
            ptreedef, param_paths, _ips, _tf = self._param_tree_tools(params)
            from .parallel.sharding import tree_paths_and_leaves

            p_compute_flat = p_storage_flat = None
            if params_offloaded:
                p_compute_flat = dict(tree_paths_and_leaves(self.model.param_compute_sharding)[0])
                p_storage_flat = dict(tree_paths_and_leaves(self.model.param_sharding)[0])
            self._jit_cache["chunk_static"] = (ptreedef, param_paths, p_compute_flat, p_storage_flat)
        groups = self._jit_cache["chunk_groups"]
        slice_state = self._jit_cache["chunk_slicer"]
        decompose, group_state, absorb, recompose = self._jit_cache["chunk_chunker"]
        params_treedef, param_paths, p_compute_flat, p_storage_flat = self._jit_cache["chunk_static"]
        flat_params = dict(zip(param_paths, jax.tree_util.tree_leaves(params)))
        flat_grads = dict(zip(param_paths, jax.tree_util.tree_leaves(grads)))

        if finite is None:
            finite = jnp.array(True)
            if use_scaler:
                if "chunk_finite" not in self._jit_cache:
                    self._jit_cache["chunk_finite"] = jax.jit(
                        lambda g, inv: unscale_and_clip(g, inv, None, True)[1]
                    )
                finite = self._jit_cache["chunk_finite"](grads, jnp.asarray(float(inv_scale), jnp.float32))

        new_flat = dict(flat_params)
        disk_state = self.opt_state if isinstance(self.opt_state, DiskOptState) else None
        if disk_state is None:
            state_slots, state_def = decompose(self.opt_state)
            # Reads come from state_slots (every group's update must see the ORIGINAL
            # shared scalars — e.g. Adam's count — not a prior group's increment);
            # writes land in out_slots. Param-slot dicts are shared objects, which is
            # safe: groups touch disjoint path sets.
            out_slots = list(state_slots)
        else:
            disk_state.check_usable()
            # Same original-scalars contract for the disk tier: snapshot the
            # in-memory scalar slots before any group writes its increment back.
            scalar_snapshot = list(disk_state.scalars)
            disk_state.prefetch_group(groups[0])
            if "disk_writer" not in self._jit_cache:
                import concurrent.futures

                self._jit_cache["disk_writer"] = concurrent.futures.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="optstate-writeback"
                )
            writer = self._jit_cache["disk_writer"]
            write_futures = []
        # Scalars change rarely: cache their device buffers (same rationale as the
        # fused step's _scalar_bufs — no per-step H2D for constants).
        skey = (float(inv_scale), float(lr_override) if with_lr else 0.0)
        if skey != self._jit_cache.get("chunk_scalar_key"):
            self._jit_cache["chunk_scalar_key"] = skey
            self._jit_cache["chunk_scalar_bufs"] = tuple(jnp.asarray(v, jnp.float32) for v in skey)
        inv_buf, lr_val = self._jit_cache["chunk_scalar_bufs"]
        try:
            new_params, finite = self._chunked_group_loop(
                groups,
                slice_state,
                group_state,
                absorb,
                recompose,
                disk_state=disk_state,
                flat_params=flat_params,
                flat_grads=flat_grads,
                params_offloaded=params_offloaded,
                p_compute_flat=p_compute_flat,
                p_storage_flat=p_storage_flat,
                inv_buf=inv_buf,
                lr_val=lr_val,
                finite=finite,
                with_lr=with_lr,
                use_scaler=use_scaler,
                new_flat=new_flat,
                state_slots=None if disk_state is not None else state_slots,
                state_def=None if disk_state is not None else state_def,
                out_slots=None if disk_state is not None else out_slots,
                scalar_snapshot=None if disk_state is None else scalar_snapshot,
                writer=None if disk_state is None else writer,
                write_futures=None if disk_state is None else write_futures,
                params_treedef=params_treedef,
                param_paths=param_paths,
            )
        except BaseException:
            # Group programs donate the grad buffers, so whatever accumulation
            # produced them is dead — drop it so the next backward starts fresh.
            self._grads = None
            self._accum_count = 0
            self._grads_unscaled = False
            if disk_state is not None:
                # Some groups' moment write-backs may already have landed while
                # the params were never assigned — the blob is now ahead of the
                # params. Poison so a blind retry fails loudly (load_state clears).
                for fut in write_futures:
                    try:
                        fut.result()
                    except Exception:
                        pass
                disk_state.poisoned = True
            raise
        return new_params, finite

    def _chunked_group_loop(
        self,
        groups,
        slice_state,
        group_state,
        absorb,
        recompose,
        *,
        disk_state,
        flat_params,
        flat_grads,
        params_offloaded,
        p_compute_flat,
        p_storage_flat,
        inv_buf,
        lr_val,
        finite,
        with_lr,
        use_scaler,
        new_flat,
        state_slots,
        state_def,
        out_slots,
        scalar_snapshot,
        writer,
        write_futures,
        params_treedef,
        param_paths,
    ):
        import jax
        import jax.numpy as jnp

        if disk_state is not None:
            state_def = disk_state.state_def
        for gi, paths in enumerate(groups):
            key = ("chunk_update", gi, with_lr)
            if key not in self._jit_cache:
                compute_shardings = slice_state(self._opt_compute_sharding, paths)
                p_compute = {p: p_compute_flat[p] for p in paths} if params_offloaded else None
                tx = self.tx

                def _group_update(p_g, s_g, g_g, inv, lr, finite, _sh=compute_shardings, _psh=p_compute):
                    s_g = jax.device_put(s_g, _sh)
                    if _psh is not None:
                        p_g = jax.device_put(p_g, _psh)
                    # Match the param dtype (same two hazards as _update_fn /
                    # unscale_and_clip): the fp32 `inv` scalar would promote bf16
                    # grads, and a reduce_dtype fp32 accumulation buffer must not
                    # leak fp32 moments into the (offload-halved) opt state.
                    g_g = jax.tree_util.tree_map(
                        lambda g, p: (g * inv).astype(p.dtype), g_g, p_g
                    )
                    return update_and_revert(
                        tx, p_g, s_g, g_g, lr if with_lr else None, finite, use_scaler
                    )

                # Disk tier: keep the caller's param buffers alive through the
                # step — a failed blob write-back must leave params usable for
                # the poison -> load_state recovery path (only grads donate).
                donate = (2,) if disk_state is not None else (0, 2)
                # tpu-lint: disable=jit-in-loop (memoized in _jit_cache per group key)
                self._jit_cache[key] = jax.jit(_group_update, donate_argnums=donate)
                self._jit_cache[("chunk_store_shard", gi)] = slice_state(self.opt_state_sharding, paths)
                self._jit_cache[("chunk_param_store", gi)] = (
                    {p: p_storage_flat[p] for p in paths} if params_offloaded else None
                )
            p_g = {p: flat_params[p] for p in paths}
            g_g = {p: flat_grads[p] for p in paths}
            if disk_state is not None:
                # Disk tier: async-prefetch the NEXT group's blob reads, consume
                # this group's (pre-step scalars from the snapshot), and hand the
                # write-back to the background thread so D2H + pwrite overlap the
                # next group's program.
                if gi + 1 < len(groups):
                    disk_state.prefetch_group(groups[gi + 1])
                s_g = disk_state.read_group(paths, scalars=scalar_snapshot)
                p_new, s_new = self._jit_cache[key](p_g, s_g, g_g, inv_buf, lr_val, finite)
                write_futures.append(writer.submit(disk_state.write_group, paths, s_new))
            else:
                s_g = group_state(state_slots, state_def, paths)
                p_new, s_new = self._jit_cache[key](p_g, s_g, g_g, inv_buf, lr_val, finite)
                # Write the group state straight back to its pinned-host tier (the
                # D2H overlaps the next group program) and absorb into the slots.
                s_new = jax.device_put(s_new, self._jit_cache[("chunk_store_shard", gi)])
                absorb(out_slots, state_def, s_new)
            if params_offloaded:
                p_new = jax.device_put(p_new, self._jit_cache[("chunk_param_store", gi)])
            new_flat.update(p_new)

        if disk_state is not None:
            for fut in write_futures:
                fut.result()  # surface write errors; state stays disk-resident
        else:
            self.opt_state = recompose(out_slots, state_def)
        new_params = jax.tree_util.tree_unflatten(params_treedef, [new_flat[p] for p in param_paths])
        return new_params, finite

    # ---- gradient intake -------------------------------------------------------------
    def _accumulate_fn(self):
        import jax

        if "acc" not in self._jit_cache:

            def _add(acc, new):
                return jax.tree_util.tree_map(lambda a, b: a + b.astype(a.dtype), acc, new)

            self._jit_cache["acc"] = jax.jit(_add, donate_argnums=(0,))
        return self._jit_cache["acc"]

    def accumulate_grads(self, grads):
        """Add a microbatch's gradients into the accumulation buffer (held in the
        model's reduce_dtype when set — FSDP MixedPrecision parity; cast back to
        the param dtype at step time by _update's grads.astype)."""
        self._reject_mpmd("accumulate_grads()")
        if self._grads is None:
            reduce_dtype = getattr(self.model, "reduce_dtype", None)
            if reduce_dtype is not None:
                import jax

                grads = jax.tree_util.tree_map(lambda g: g.astype(reduce_dtype), grads)
            self._grads = grads
            self._grads_unscaled = False
        else:
            self._grads = self._accumulate_fn()(self._grads, grads)
        self._accum_count += 1

    @property
    def grads(self):
        return self._grads

    # ---- clipping --------------------------------------------------------------------
    def _unscale_factor(self) -> float:
        """1/loss_scale the first time grads are touched pre-step; 1.0 after
        (the reference's unscale_gradients-once contract, accelerator.py:2186)."""
        if self.scaler is not None and self.scaler.enabled and not self._grads_unscaled:
            self._grads_unscaled = True
            return 1.0 / self.scaler.scale
        return 1.0

    def clip_grad_norm_(self, max_norm: float):
        """Unscale then clip accumulated grads by global norm; returns the pre-clip
        (unscaled) norm (reference accelerator.py:2221-2269, which unscales first)."""
        import jax
        import jax.numpy as jnp

        self._reject_mpmd("clip_grad_norm_()")
        if self._grads is None:
            return None
        inv_scale = self._unscale_factor()
        key = ("clip", float(max_norm))
        if key not in self._jit_cache:

            def _clip(grads, inv):
                grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
                norm = jnp.sqrt(
                    sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree_util.tree_leaves(grads))
                )
                factor = jnp.minimum(1.0, max_norm / (norm + 1e-6))
                return jax.tree_util.tree_map(lambda g: (g * factor).astype(g.dtype), grads), norm

            self._jit_cache[key] = jax.jit(_clip, donate_argnums=(0,))
        self._grads, norm = self._jit_cache[key](self._grads, jnp.asarray(inv_scale, jnp.float32))
        return norm

    def clip_grad_value_(self, clip_value: float):
        import jax
        import jax.numpy as jnp

        self._reject_mpmd("clip_grad_value_()")
        if self._grads is None:
            return
        inv_scale = self._unscale_factor()
        key = ("clipv", float(clip_value))
        if key not in self._jit_cache:

            def _clip(grads, inv):
                return jax.tree_util.tree_map(lambda g: (g * inv).clip(-clip_value, clip_value), grads)

            self._jit_cache[key] = jax.jit(_clip, donate_argnums=(0,))
        self._grads = self._jit_cache[key](self._grads, jnp.asarray(inv_scale, jnp.float32))

    # ---- the update ------------------------------------------------------------------
    def _update_fn(self):
        import jax

        if "update" not in self._jit_cache:
            use_scaler = self.scaler is not None and self.scaler.enabled
            to_compute = getattr(self.model, "to_compute_memory", lambda p: p)

            param_out = getattr(self.model, "param_compute_sharding", None)
            opt_out = self._opt_compute_sharding or self.opt_state_sharding

            def _update(params, opt_state, grads, inv_scale, lr_override):
                # Host-offloaded tiers stream into device memory for the update;
                # the caller writes the results back to pinned host.
                opt_state = self.opt_to_compute_memory(opt_state)
                params = to_compute(params)
                # The accumulation buffer may be in reduce_dtype (fp32 over bf16
                # params); the optimizer state mirrors the params, so bring the
                # grads back to the param dtype for the update arithmetic.
                grads = jax.tree_util.tree_map(lambda g, p: g.astype(p.dtype), grads, params)
                new_params, new_opt_state, finite = apply_update_core(
                    self.tx, params, opt_state, grads, inv_scale, lr_override, use_scaler=use_scaler
                )
                # Pin outputs to the derived shardings — an unconstrained donated
                # jit lets XLA re-layout params after the first step (sharding
                # drift away from the configured wrap policy).
                if param_out is not None:
                    new_params = jax.lax.with_sharding_constraint(new_params, param_out)
                if opt_out is not None:
                    new_opt_state = jax.lax.with_sharding_constraint(new_opt_state, opt_out)
                return new_params, new_opt_state, finite

            # XLA:CPU-only: donating (params, opt_state, grads) into the fused
            # update crashes the host runtime when the operands are sharded
            # across forced host-platform devices (SIGSEGV/SIGABRT inside the
            # aliased executable — the multi-device pipeline tests hit it
            # deterministically). Donation is a memory optimization, not a
            # semantics change, so drop it on CPU; TPU/GPU keep the aliasing.
            # The grads are NOT donated: no output aliases them, and on the chip
            # XLA answers "Some donated buffers were not usable" for every leaf.
            donate = () if jax.default_backend() == "cpu" else (0, 1)
            self._jit_cache["update"] = jax.jit(_update, donate_argnums=donate)
        return self._jit_cache["update"]

    def step(self):
        """Apply the update if at a sync boundary; no-op otherwise (reference
        optimizer.py:125-152)."""
        import jax
        import jax.numpy as jnp

        self._reject_mpmd("step()")
        if not self.gradient_state.sync_gradients:
            self.step_was_skipped = True
            return
        if self._grads is None:
            self.step_was_skipped = True
            return
        inv_scale = self._unscale_factor()
        lr = self._lr_override
        if self.offload_opt_state:
            # Chunked path: one small program per param group keeps peak HBM at
            # one group's params+grads+state (see apply_chunked_update); it also
            # places params/state back on their storage tiers itself.
            new_params, finite = self.apply_chunked_update(
                self.model.params, self._grads, inv_scale, lr
            )
        else:
            new_params, new_opt_state, finite = self._update_fn()(
                self.model.params, self.opt_state, self._grads, jnp.asarray(inv_scale, jnp.float32), lr
            )
            if hasattr(self.model, "to_storage_memory"):
                new_params = self.model.to_storage_memory(new_params)
            self.opt_state = self.opt_to_storage_memory(new_opt_state)
        self._grads = None
        self._accum_count = 0
        self._grads_unscaled = False
        if self.scaler is not None and self.scaler.enabled:
            found_inf = not bool(finite)
            self.scaler.update(found_inf)
            self.step_was_skipped = found_inf
            if found_inf:
                logger.warning("Skipping optimizer step: non-finite gradients (loss scale -> %s)", self.scaler.scale)
        else:
            self.step_was_skipped = False
        self.model.params = new_params
        # XLA:CPU-only deadlock guard (no-op on TPU/GPU) — see fence_if_cpu.
        fence_if_cpu(new_params)

    def zero_grad(self, set_to_none: bool = True):
        """Clear accumulated grads; no-op mid-accumulation (reference optimizer.py:112)."""
        if self.gradient_state.sync_gradients:
            self._grads = None
            self._accum_count = 0
            self._grads_unscaled = False

    # ---- scheduler hook --------------------------------------------------------------
    def set_learning_rate(self, lr: float):
        """Override the learning rate for subsequent steps (requires the tx to be built
        with `optax.inject_hyperparams`, else schedules inside the tx govern)."""
        self._reject_mpmd("set_learning_rate()")
        self._lr_override = lr

    @property
    def learning_rate(self):
        if self._lr_override is not None:
            return self._lr_override
        if hasattr(self.opt_state, "hyperparams"):
            lr = self.opt_state.hyperparams.get("learning_rate")
            return None if lr is None else float(np.asarray(lr))
        return None

    # ---- checkpoint view -------------------------------------------------------------
    def state_dict(self):
        self._reject_mpmd("state_dict()")
        opt_state = self.opt_state
        if isinstance(opt_state, DiskOptState):
            # Checkpointing sees an ordinary pytree (one pass over the blob).
            opt_state = opt_state.materialize()
        return {"opt_state": opt_state, "scaler": self.scaler.state_dict() if self.scaler else None}

    def load_state_dict(self, state):
        from .parallel.sharding import place_params

        self._reject_mpmd("load_state_dict()")
        if isinstance(self.opt_state, DiskOptState):
            self.opt_state.load(state["opt_state"])
        else:
            # place_params (not device_put): device_put aliases buffers already placed
            # correctly, and the donated update would delete the caller's arrays through
            # that alias on the next step.
            self.opt_state = place_params(state["opt_state"], self.opt_state_sharding)
        if self.scaler is not None and state.get("scaler") is not None:
            self.scaler.load_state_dict(state["scaler"])
