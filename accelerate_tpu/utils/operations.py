"""Pytree-recursive collectives and tensor utilities (L2).

TPU-native redesign of reference utils/operations.py. Two planes:

  - **Data plane** (arrays): across *hosts* via `jax.experimental.multihost_utils`
    (which compiles to XLA collectives over ICI/DCN — the NCCL replacement,
    reference operations.py:308-358,727-765). Inside jit, sharded global arrays make
    most per-rank collectives unnecessary: a "gathered" metric is just the global
    array fetched to host.
  - **Object plane** (arbitrary picklables): pickle → uint8 arrays → XLA broadcast /
    allgather. Notably `gather_object` works here; the reference raises
    NotImplementedError on XLA (operations.py:462-463).

Debug mode (`ACCELERATE_TPU_DEBUG_MODE=1`) wraps every collective in a cross-process
shape/dtype verification (parity: reference `verify_operation` operations.py:361-421),
which catches the classic mismatched-shape distributed hang before it happens.
"""

from __future__ import annotations

import functools
import pickle
from typing import Any, Callable, Mapping

import numpy as np


class DistributedOperationException(Exception):
    """Raised when ranks call a collective with mismatched shapes (reference
    operations.py:30)."""


def is_jax_array(x) -> bool:
    import jax

    return isinstance(x, jax.Array)


def is_array_like(x) -> bool:
    return is_jax_array(x) or isinstance(x, (np.ndarray, np.generic))


def honor_type(obj, generator):
    """Rebuild `obj`'s container type from `generator` (reference operations.py:73)."""
    try:
        if isinstance(obj, tuple) and hasattr(obj, "_fields"):  # namedtuple
            return type(obj)(*list(generator))
        return type(obj)(generator)
    except TypeError:
        # Some objects (e.g. flax structs) may not accept a generator; fall back to list.
        return list(generator)


def recursively_apply(
    func: Callable,
    data: Any,
    *args,
    test_type: Callable = is_array_like,
    error_on_other_type: bool = False,
    **kwargs,
):
    """Apply `func` to every array leaf of a nested list/tuple/namedtuple/Mapping
    (reference operations.py:84)."""
    if isinstance(data, (tuple, list)):
        return honor_type(
            data,
            (
                recursively_apply(
                    func, o, *args, test_type=test_type, error_on_other_type=error_on_other_type, **kwargs
                )
                for o in data
            ),
        )
    elif isinstance(data, Mapping):
        return type(data)(
            {
                k: recursively_apply(
                    func, v, *args, test_type=test_type, error_on_other_type=error_on_other_type, **kwargs
                )
                for k, v in data.items()
            }
        )
    elif test_type(data):
        return func(data, *args, **kwargs)
    elif error_on_other_type:
        raise TypeError(
            f"Unsupported type {type(data)} passed to collective: only nested "
            "list/tuple/dicts of arrays are supported."
        )
    return data


def send_to_device(tensor, device=None, non_blocking: bool = True, skip_keys=None):
    """Recursive host→device transfer (reference operations.py:135).

    `device` may be a jax.Device, a Sharding, or None (default device). Torch tensors
    are converted through numpy so torch dataloaders feed TPU arrays transparently.
    """
    import jax

    if skip_keys is None:
        skip_keys = []
    elif isinstance(skip_keys, str):
        skip_keys = [skip_keys]

    def _to_numpy(t):
        if hasattr(t, "detach") and hasattr(t, "numpy"):  # torch tensor
            return t.detach().cpu().numpy()
        return t

    def _send(t):
        t = _to_numpy(t)
        if not is_array_like(t):
            return t
        return jax.device_put(t, device)

    if isinstance(tensor, Mapping):
        return type(tensor)(
            {k: (v if k in skip_keys else send_to_device(v, device, non_blocking, skip_keys)) for k, v in tensor.items()}
        )
    if isinstance(tensor, (tuple, list)):
        # Recurse through ourselves so skip_keys is honored at any Mapping depth
        # (reference operations.py:135 recurses the same way).
        return honor_type(tensor, (send_to_device(t, device, non_blocking, skip_keys) for t in tensor))

    def _test(t):
        return is_array_like(t) or (hasattr(t, "detach") and hasattr(t, "numpy"))

    return recursively_apply(_send, tensor, test_type=_test)


def get_data_structure(data):
    """Shape/dtype skeleton of a pytree (reference operations.py:174)."""

    def _info(t):
        return {"shape": tuple(np.shape(t)), "dtype": str(np.asarray(t).dtype) if not is_jax_array(t) else str(t.dtype)}

    return recursively_apply(_info, data)


def find_batch_size(data) -> int | None:
    """First dimension of the first array leaf (reference operations.py:240)."""
    if isinstance(data, (tuple, list)):
        for d in data:
            result = find_batch_size(d)
            if result is not None:
                return result
        return None
    elif isinstance(data, Mapping):
        for v in data.values():
            result = find_batch_size(v)
            if result is not None:
                return result
        return None
    elif is_array_like(data) and np.ndim(data) > 0:
        return np.shape(data)[0]
    return None


def listify(data):
    """Arrays → nested python lists (reference operations.py:257)."""

    def _listify(t):
        return np.asarray(t).tolist()

    return recursively_apply(_listify, data)


def slice_tensors(data, tensor_slice, process_index=None, num_processes=None):
    """Slice every array leaf (reference operations.py:272)."""

    def _slice(t, s):
        return t[s]

    return recursively_apply(_slice, data, tensor_slice)


def concatenate(data, dim: int = 0):
    """Concatenate a list of same-structure pytrees leafwise (reference operations.py:600)."""
    import jax.numpy as jnp

    if isinstance(data[0], (tuple, list)):
        return honor_type(data[0], (concatenate([d[i] for d in data], dim=dim) for i in range(len(data[0]))))
    elif isinstance(data[0], Mapping):
        return type(data[0])({k: concatenate([d[k] for d in data], dim=dim) for k in data[0].keys()})
    elif not is_array_like(data[0]):
        raise TypeError(f"Can only concatenate arrays but got {type(data[0])}")
    if isinstance(data[0], np.ndarray):
        return np.concatenate(data, axis=dim)
    return jnp.concatenate(data, axis=dim)


# --------------------------------------------------------------------------------------
# Debug-mode operation verification (reference operations.py:361-421)
# --------------------------------------------------------------------------------------


def verify_operation(function):
    """Cross-process shape check before a collective when debug mode is on."""

    @functools.wraps(function)
    def wrapper(*args, **kwargs):
        from ..state import PartialState

        state = PartialState()
        if not state.debug or state.num_processes == 1:
            return function(*args, **kwargs)
        operation = f"{function.__module__}.{function.__name__}"
        tensor = kwargs.get("tensor", args[0] if args else None)
        shapes = get_data_structure(tensor)
        output = gather_object([shapes])
        if output[0] is not None and not all(x == output[0] for x in output):
            process_shape_str = "\n  - ".join([f"Process {i}: {s}" for i, s in enumerate(output)])
            raise DistributedOperationException(
                f"Cannot apply desired operation due to shape mismatches. All shapes across devices must be valid.\n\n"
                f"Operation: `{operation}`\nInput shapes:\n  - {process_shape_str}"
            )
        return function(*args, **kwargs)

    return wrapper


def chained_operation(function):
    """Re-raise collective errors with context (reference operations.py:405)."""

    @functools.wraps(function)
    def wrapper(*args, **kwargs):
        try:
            return function(*args, **kwargs)
        except DistributedOperationException as e:
            operation = f"{function.__module__}.{function.__name__}"
            raise DistributedOperationException(
                f"Error found while calling `{operation}`. Please see the earlier error for more details."
            ) from e

    return wrapper


# --------------------------------------------------------------------------------------
# Data-plane collectives
# --------------------------------------------------------------------------------------


def _num_processes() -> int:
    import jax

    return jax.process_count()


def _fetch_global(t):
    """Materialize a (possibly sharded) jax.Array on host as numpy.

    For fully-addressable arrays this is a device_get; for multi-host global arrays the
    non-addressable shards are fetched via an allgather.
    """
    import jax

    if is_jax_array(t):
        if t.is_fully_addressable:
            return np.asarray(jax.device_get(t))
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(t, tiled=True))
    return np.asarray(t)


def fetch_global(t):
    """Public alias of `_fetch_global`: materialize a (possibly multi-host sharded)
    jax.Array on host as numpy — the portable way to read a global batch/output."""
    return _fetch_global(t)


@verify_operation
def gather(tensor):
    """All-gather along dim 0 across processes (reference operations.py:425).

    Host-local arrays: every process contributes its array; all receive the dim-0
    concatenation (reference `_tpu_gather`/`_gpu_gather` semantics). Global sharded
    arrays: returns the full global value (the SPMD equivalent — the array already *is*
    the gathered batch).
    """

    def _gather_one(t):
        if is_jax_array(t) and not t.is_fully_addressable:
            return _fetch_global(t)
        if _num_processes() == 1:
            return _fetch_global(t)
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(np.asarray(t), tiled=True))

    return recursively_apply(_gather_one, tensor, error_on_other_type=True)


@chained_operation
def gather_object(object: Any):
    """Gather arbitrary picklables from all processes into a list (reference
    operations.py:451 — which is NotImplemented on XLA; supported here via the
    byte-array object plane)."""
    if _num_processes() == 1:
        return list(object) if isinstance(object, list) else [object]
    from jax.experimental import multihost_utils

    payload = np.frombuffer(pickle.dumps(object), dtype=np.uint8)
    sizes = multihost_utils.process_allgather(np.array([payload.size], dtype=np.int64))
    sizes = np.asarray(sizes).reshape(-1)
    max_size = int(sizes.max())
    padded = np.zeros((max_size,), dtype=np.uint8)
    padded[: payload.size] = payload
    gathered = np.asarray(multihost_utils.process_allgather(padded))
    out = []
    for i, size in enumerate(sizes):
        obj = pickle.loads(gathered[i, :size].tobytes())
        if isinstance(obj, list):
            out.extend(obj)
        else:
            out.append(obj)
    return out


@verify_operation
def broadcast(tensor, from_process: int = 0):
    """Broadcast array pytree from one process (reference operations.py:545).

    XLA's broadcast_one_to_all always sources process 0; for other sources we route
    through the object plane."""

    def _broadcast_one(t):
        t = np.asarray(_fetch_global(t))
        if _num_processes() == 1:
            return t
        from jax.experimental import multihost_utils

        if from_process == 0:
            return np.asarray(multihost_utils.broadcast_one_to_all(t))
        # Rare path: non-zero source. Object-plane relay via process 0.
        gathered = gather_object([t])
        return np.asarray(gathered[from_process])

    return recursively_apply(_broadcast_one, tensor, error_on_other_type=True)


@chained_operation
def broadcast_object_list(object_list: list, from_process: int = 0):
    """Broadcast a list of picklables from `from_process` (reference operations.py:566)."""
    if _num_processes() == 1:
        return object_list
    from jax.experimental import multihost_utils

    import jax

    if from_process != 0:
        # gather_object extends lists, so wrap each process's list once more: the result
        # is one sublist per process, indexed directly by rank.
        gathered = gather_object([[list(object_list)]])
        src = gathered[from_process]
        for i in range(len(object_list)):
            object_list[i] = src[i]
        return object_list

    payload = np.frombuffer(pickle.dumps(list(object_list)), dtype=np.uint8)
    size = multihost_utils.broadcast_one_to_all(np.array([payload.size], dtype=np.int64))
    buf = np.zeros((int(size[0]),), dtype=np.uint8)
    if jax.process_index() == from_process:
        buf[:] = payload
    buf = np.asarray(multihost_utils.broadcast_one_to_all(buf))
    result = pickle.loads(buf.tobytes())
    for i in range(len(object_list)):
        object_list[i] = result[i]
    return object_list


@verify_operation
def reduce(tensor, reduction: str = "mean", scale: float = 1.0):
    """Cross-process reduce (reference operations.py:727-765 with its XLA `scale` arg)."""

    def _reduce_one(t):
        # A non-addressable global array is already a single cross-host value; summing
        # per-host copies would over-count by num_processes (gather() has the same branch).
        if is_jax_array(t) and not t.is_fully_addressable:
            return _fetch_global(t) * scale
        arr = _fetch_global(t)
        if _num_processes() > 1:
            from jax.experimental import multihost_utils

            stacked = np.asarray(multihost_utils.process_allgather(np.asarray(arr)))
            arr = stacked.sum(axis=0)
            if reduction == "mean":
                arr = arr / _num_processes()
        arr = arr * scale
        return arr

    return recursively_apply(_reduce_one, tensor, error_on_other_type=True)


@verify_operation
def pad_across_processes(tensor, dim: int = 0, pad_index: int = 0, pad_first: bool = False):
    """Pad each process's array to the max size along `dim` (reference operations.py:634)."""

    def _pad_one(t):
        arr = np.asarray(_fetch_global(t))
        if arr.ndim == 0 or dim >= arr.ndim:
            return arr
        size = np.array(arr.shape, dtype=np.int64)
        if _num_processes() == 1:
            return arr
        from jax.experimental import multihost_utils

        sizes = np.asarray(multihost_utils.process_allgather(size))
        max_size = int(sizes[:, dim].max())
        if max_size == arr.shape[dim]:
            return arr
        old_size = arr.shape
        new_size = list(old_size)
        new_size[dim] = max_size
        new_tensor = np.full(new_size, pad_index, dtype=arr.dtype)
        if pad_first:
            indices = tuple(
                slice(max_size - old_size[dim], max_size) if i == dim else slice(None) for i in range(arr.ndim)
            )
        else:
            indices = tuple(slice(0, old_size[dim]) if i == dim else slice(None) for i in range(arr.ndim))
        new_tensor[indices] = arr
        return new_tensor

    return recursively_apply(_pad_one, tensor, error_on_other_type=True)


def pad_input_tensors(tensor, batch_size: int, num_processes: int, dim: int = 0):
    """Pad dim 0 so it divides num_processes (reference operations.py:686, used by the
    batch dispatcher and pipeline inference)."""

    def _pad_one(t):
        arr = np.asarray(t)
        remainder = arr.shape[dim] % num_processes
        if remainder == 0:
            return arr
        pad_count = num_processes - remainder
        pad_block = np.repeat(np.take(arr, [-1], axis=dim), pad_count, axis=dim)
        return np.concatenate([arr, pad_block], axis=dim)

    return recursively_apply(_pad_one, tensor, error_on_other_type=True)


# --------------------------------------------------------------------------------------
# Page-pool gather/scatter over cache pytrees (serving.py's KV page pool)
# --------------------------------------------------------------------------------------

# K/V leaves of the slot cache are pool-shaped: [..., num_pages, page_size,
# heads, head_dim] — the page axis sits where the dense cache's batch axis sits
# (4 from the back), so the same rule covers plain stacks and nn.scan-stacked
# layers ([layers, num_pages, page_size, h, d]). A latent family's one pool of
# rows has no head axis: [..., num_pages, page_size, row].
_PAGE_AXIS_FROM_BACK = {"cached_key": 4, "cached_value": 4, "cached_latent": 3}

# BY-SLOT leaves: what a layer with a recurrence keeps a request whatever its
# length (models/olmo_hybrid.py) — `recurrent_state` [..., slots, key_dim,
# heads * value_dim] and `conv_state` [..., slots, taps - 1, channels], the
# slot axis 3 from the back. They live in the same cache tree as the page
# pools and are found by name as those are; a batch-1 dense cache holds them
# with one row.
_SLOT_AXIS_FROM_BACK = {"recurrent_state": 3, "conv_state": 3}


def tree_slot_state_nbytes(cache) -> int:
    """Stored bytes ONE slot holds in the by-slot leaves of a slot cache, all
    layers; 0 for a cache of page pools alone."""
    import jax

    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        axis_back = _SLOT_AXIS_FROM_BACK.get(_leaf_name(path))
        if axis_back is not None:
            slots = leaf.shape[leaf.ndim - axis_back]
            total += int(np.prod(leaf.shape)) // slots * np.dtype(leaf.dtype).itemsize
    return total

# Per-page-per-head scale pools of a QUANTIZED paged cache
# (ops/quantization.py): [..., num_pages, heads] f32, page axis 2 from the
# back. `_SCALE_OF` maps a K/V pool leaf to its sibling scale leaf; the
# gather/scatter below dequantize/quantize through it so the insert path and
# the decode write path can never disagree about a page's scale.
_SCALE_AXIS_FROM_BACK = {"key_scale": 2, "value_scale": 2}
_SCALE_OF = {"cached_key": "key_scale", "cached_value": "value_scale"}
_KV_OF = {v: k for k, v in _SCALE_OF.items()}


def _key_name(entry) -> str:
    """DictKey/GetAttrKey/SequenceKey path entry -> plain string."""
    return str(getattr(entry, "key", getattr(entry, "name", entry)))


def _leaf_name(path) -> str:
    return _key_name(path[-1])


def _path_names(path):
    return tuple(_key_name(p) for p in path)


def tree_gather_pages(pool, dense_struct, page_ids, cache_index):
    """Materialize a batch-1 DENSE decode cache from pool pages: for every
    `cached_key`/`cached_value` leaf, gather `pool_leaf[page_ids]`
    ([P, page_size, h, d]) and merge the page axes into one contiguous
    [1, P*page_size, h, d] row; fill `cache_index` leaves with the traced
    `cache_index` scalar (the number of tokens already valid in the gathered
    prefix). `dense_struct` is the eval_shape pytree of the dense prefill
    module's cache — it fixes the output tree layout and shapes. A BY-SLOT leaf
    (`recurrent_state`, `conv_state`) comes out as zeros: pages of tokens do
    not say what state a prefix left behind, so an admission starts its
    recurrence from nothing (the engine serves such a family with the prefix
    cache off, `cache_index` 0).

    jit-traceable (`page_ids` [P] int32 and `cache_index` may be traced
    operands); the serving engine's paged insert uses this to give a suffix
    prefill an attention view over shared prefix pages without ever owning a
    dense per-slot cache."""
    import jax
    import jax.numpy as jnp

    pool_leaves = {
        _path_names(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]
    }

    def _build(path, struct):
        names = _path_names(path)
        axis_back = _PAGE_AXIS_FROM_BACK.get(names[-1])
        if axis_back is None:
            if names[-1] == "cache_index":
                return jnp.full(struct.shape, jnp.asarray(cache_index, struct.dtype))
            if names[-1] == "pad_mask":
                return jnp.ones(struct.shape, struct.dtype)
            return jnp.zeros(struct.shape, struct.dtype)
        leaf = pool_leaves.get(names)
        if leaf is None:
            raise ValueError(f"pool cache has no leaf at {'/'.join(names)}")
        axis = leaf.ndim - axis_back
        # mode="clip": the default "fill" passes every gathered page through a
        # select against NaN; the engine's ids are the pool's own (PagePool).
        pages = jnp.take(leaf, jnp.asarray(page_ids, jnp.int32), axis=axis, mode="clip")
        scale_leaf = pool_leaves.get(names[:-1] + (_SCALE_OF.get(names[-1], ""),))
        if scale_leaf is not None:
            # Quantized pool: dequantize the gathered pages with their
            # per-page-per-head scales so the dense prefill sees real values.
            scale_axis = scale_leaf.ndim - _SCALE_AXIS_FROM_BACK[_SCALE_OF[names[-1]]]
            pages_scale = jnp.take(
                scale_leaf, jnp.asarray(page_ids, jnp.int32), axis=scale_axis, mode="clip"
            )
            # Insert the page_size axis after the page axis and the head_dim
            # axis at the end, then broadcast-multiply in fp32.
            # The barrier keeps the quantized -> f32 convert in this fusion: hoisted
            # to the gather it writes the pages in f32 (PERF.md §6, PR 25).
            scale_b = jnp.expand_dims(pages_scale, axis + 1)[..., None]
            pages = jax.lax.optimization_barrier(pages).astype(jnp.float32) * scale_b
        merged = pages.reshape(
            pages.shape[:axis]
            + (pages.shape[axis] * pages.shape[axis + 1],)
            + pages.shape[axis + 2 :]
        )
        dense = jnp.expand_dims(merged, axis)  # the batch-1 slot axis
        if dense.shape != struct.shape:
            raise ValueError(
                f"gathered pages for {'/'.join(names)} have shape {dense.shape}, "
                f"dense prefill cache expects {struct.shape} — page count x page "
                "size must equal the dense cache length"
            )
        return dense.astype(struct.dtype)

    return jax.tree_util.tree_map_with_path(_build, dense_struct)


def tree_zero_cache_tail(dense, valid_len):
    """Zero every `cached_key`/`cached_value` row of a dense cache at
    positions >= `valid_len` (a traced scalar). The paged insert runs this
    before `tree_scatter_pages`: the gathered dense cache carries STALE
    dequantized content from each private page's previous occupant beyond the
    prompt, and while the position mask keeps it unattended, a QUANTIZED
    scatter would fold it into the boundary page's amax scale — a prior
    occupant with larger K/V magnitudes would silently coarsen the new
    request's real rows past the half-step round-trip bound (and decode's
    scatter-max would keep the inflated scale alive). Zeros contribute
    nothing to amax, restoring the bound; on unquantized pools this is pure
    hygiene."""
    import jax
    import jax.numpy as jnp

    def _zero(path, leaf):
        name = _leaf_name(path)
        if name not in _PAGE_AXIS_FROM_BACK:  # the page-pool leaves only
            return leaf
        seq_axis = leaf.ndim - _PAGE_AXIS_FROM_BACK[name] + 1  # [..., batch, L, heads, head_dim]
        cols = jnp.arange(leaf.shape[seq_axis])
        keep = (cols < jnp.asarray(valid_len, jnp.int32)).reshape(
            (leaf.shape[seq_axis],) + (1,) * (leaf.ndim - seq_axis - 1)
        )
        return jnp.where(keep, leaf, jnp.zeros((), leaf.dtype))

    return jax.tree_util.tree_map_with_path(_zero, dense)


def tree_scatter_pages(pool, dense, page_ids, slot=None):
    """Write a batch-1 dense cache back into pool pages (the inverse of
    `tree_gather_pages`): every `cached_key`/`cached_value` leaf is split into
    [P, page_size] blocks and scattered to `pool_leaf[page_ids[j]]`. Leaves the
    pool has no entry for in `dense` (the dense path's `cache_index` scalar,
    meaningless pool-side) keep the pool's value. A BY-SLOT leaf
    (`recurrent_state`, `conv_state`) is written whole at row `slot` (a traced
    scalar): the slot's last tenant leaves nothing behind.

    Callers that must not rewrite shared read-only prefix pages redirect those
    entries of `page_ids` to the reserved scratch page before calling (the
    serving engine's insert does exactly that), so a registered prefix page is
    written exactly once — at creation — for its whole lifetime.

    QUANTIZED pools (int8/fp8 K/V leaves with sibling `key_scale`/
    `value_scale` pool arrays): the dense float blocks are quantized whole-page
    (per-page-per-head amax scales, `ops.quantization.quantize_kv_pages`) and
    the scale leaves are scattered at the same `page_ids` — so an
    insert-written page round-trips within half a quantization step and the
    decode write path (`quantized_pool_write`) can grow its scale from there."""
    import jax
    import jax.numpy as jnp

    from ..ops.quantization import kv_spec_for_dtype, quantize_kv_pages

    dense_leaves = {
        _path_names(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(dense)[0]
    }
    pool_leaves = {
        _path_names(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]
    }
    ids = jnp.asarray(page_ids, jnp.int32)

    def _kv_blocks_front(names, kv_leaf):
        """Dense K/V leaf -> page blocks with the page axis at the FRONT
        ([P, ..., page_size, h, head_dim]), or None when absent in `dense`."""
        d = dense_leaves.get(names)
        if d is None:
            return None
        axis = kv_leaf.ndim - _PAGE_AXIS_FROM_BACK[names[-1]]
        d = jnp.squeeze(d, axis=axis)  # drop the batch-1 slot axis
        page_size = kv_leaf.shape[axis + 1]
        num = ids.shape[0]
        blocks = d.reshape(d.shape[:axis] + (num, page_size) + d.shape[axis + 1 :])
        return jnp.moveaxis(blocks, axis, 0)

    def _scatter(path, leaf):
        names = _path_names(path)
        name = names[-1]
        if name in _SCALE_AXIS_FROM_BACK:
            # Scale pool leaf: recompute the written pages' per-head scales
            # from the dense sibling K/V and scatter them alongside.
            kv_names = names[:-1] + (_KV_OF[name],)
            kv_leaf = pool_leaves.get(kv_names)
            spec = kv_spec_for_dtype(kv_leaf.dtype) if kv_leaf is not None else None
            blocks = _kv_blocks_front(kv_names, kv_leaf) if spec is not None else None
            if blocks is None:
                return leaf
            _, scales = quantize_kv_pages(blocks, spec)
            axis = leaf.ndim - _SCALE_AXIS_FROM_BACK[name]
            front = jnp.moveaxis(leaf, axis, 0)
            return jnp.moveaxis(front.at[ids].set(scales.astype(leaf.dtype)), 0, axis)
        if name in _SLOT_AXIS_FROM_BACK and names in dense_leaves:
            if slot is None:
                raise ValueError(f"by-slot leaf {'/'.join(names)} needs the slot it is written at")
            start = [jnp.int32(0)] * leaf.ndim
            start[leaf.ndim - _SLOT_AXIS_FROM_BACK[name]] = jnp.asarray(slot, jnp.int32)
            return jax.lax.dynamic_update_slice(leaf, dense_leaves[names].astype(leaf.dtype), start)
        axis_back = _PAGE_AXIS_FROM_BACK.get(name)
        if axis_back is None or names not in dense_leaves:
            return leaf
        axis = leaf.ndim - axis_back
        blocks_front = _kv_blocks_front(names, leaf)
        spec = (
            kv_spec_for_dtype(leaf.dtype)
            if names[:-1] + (_SCALE_OF.get(name, ""),) in pool_leaves
            else None
        )
        if spec is not None:
            blocks_front, _ = quantize_kv_pages(blocks_front, spec)
        pool_front = jnp.moveaxis(leaf, axis, 0)
        out = pool_front.at[ids].set(blocks_front.astype(leaf.dtype))
        return jnp.moveaxis(out, 0, axis)

    return jax.tree_util.tree_map_with_path(_scatter, pool)


# --------------------------------------------------------------------------------------
# fp32 output conversion (reference operations.py:768-827)
# --------------------------------------------------------------------------------------


def convert_to_fp32(tensor):
    """Upcast float16/bfloat16 leaves to float32 (reference operations.py:768)."""
    import jax.numpy as jnp

    def _convert(t):
        return t.astype(jnp.float32) if is_jax_array(t) else np.asarray(t, dtype=np.float32)

    def _is_half(t):
        dt = t.dtype if hasattr(t, "dtype") else np.asarray(t).dtype
        return str(dt) in ("float16", "bfloat16")

    return recursively_apply(_convert, tensor, test_type=lambda t: is_array_like(t) and _is_half(t))


class ConvertOutputsToFp32:
    """Picklable forward-output fp32 converter (reference operations.py:802)."""

    def __init__(self, model_forward):
        self.model_forward = model_forward
        functools.update_wrapper(self, model_forward)

    def __call__(self, *args, **kwargs):
        return convert_to_fp32(self.model_forward(*args, **kwargs))

    def __getstate__(self):
        raise pickle.PicklingError(
            "Cannot pickle a prepared model with automatic mixed precision; unwrap it first with "
            "`extract_model_from_parallel`."
        )


convert_outputs_to_fp32 = ConvertOutputsToFp32
