"""Sharded data pipeline (L3).

TPU-native redesign of reference data_loader.py (1149 LoC). The pipeline has three
stages, mirroring the reference's contracts but producing **global jax.Arrays** instead
of per-rank torch tensors:

  1. *Index plane* — `BatchSamplerShard` / `IterableDatasetShard` split the global batch
     stream across **host processes** (reference data_loader.py:100,256). All the
     even_batches / split_batches semantics live here, in pure python, exhaustively
     unit-testable without devices.
  2. *Host plane* — `DataLoaderShard` (reference :391) iterates per-host batches (from a
     torch DataLoader, our built-in loader, or any iterable), synchronizes host RNG at
     epoch start, and runs the one-batch lookahead that drives
     `GradientState.end_of_dataloader` / `remainder` (reference :445-476,377-384).
  3. *Device plane* — each host batch becomes a global array via
     `jax.make_array_from_process_local_data` with the batch axis sharded over
     ("data","fsdp"), double-buffered by a background prefetch thread — the
     MpDeviceLoader replacement (reference :518-559): jit consumes step N while step N+1
     is transferring.

`DataLoaderDispatcher` (reference :562) keeps the rank-0-reads-all mode: process 0
fetches the global batch and broadcasts; other hosts slice their shard.
"""

from __future__ import annotations

import functools
import itertools
import math
import queue
import threading
from typing import Any, Callable, Iterable, Iterator, List, Optional

import numpy as np

from .logging import get_logger
from .state import AcceleratorState, GradientState, PartialState
from .telemetry.tracing import default_tracer
from .utils.imports import is_torch_available
from .utils.operations import recursively_apply, send_to_device
from .utils.random import synchronize_rng_states

logger = get_logger(__name__)


class SeedableRandomSampler:
    """Deterministic shuffle keyed on `seed + epoch` (reference data_loader.py:67-97).

    Every host constructs the same permutation (numpy Philox keyed on the shared seed),
    which is what makes host-sharded loading consistent without a broadcast.
    """

    def __init__(self, data_source=None, num_samples: Optional[int] = None, seed: int = 0, epoch: int = 0):
        if num_samples is None:
            num_samples = len(data_source)
        self.num_samples = num_samples
        self.seed = seed
        self.epoch = epoch

    def __len__(self):
        return self.num_samples

    def set_epoch(self, epoch: int):
        self.epoch = epoch

    def state_dict(self) -> dict:
        return {"seed": self.seed, "epoch": self.epoch}

    def load_state_dict(self, state: dict):
        self.seed = state["seed"]
        self.epoch = state["epoch"]

    def __iter__(self):
        # The epoch is advanced externally: DataLoaderShard calls `set_epoch(iteration)`
        # at the start of each pass (reference data_loader.py:450), so standalone use
        # repeats the same order — same contract as a torch sampler.
        rng = np.random.default_rng(self.seed + self.epoch)
        yield from rng.permutation(self.num_samples).tolist()


class BatchSampler:
    """Minimal batch sampler over an index sampler (torch-free building block)."""

    def __init__(self, sampler, batch_size: int, drop_last: bool = False):
        self.sampler = sampler
        self.batch_size = batch_size
        self.drop_last = drop_last

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return math.ceil(n / self.batch_size)


class BatchSamplerShard:
    """Shard a stream of index batches across host processes
    (reference data_loader.py:100-253; the shard math is the most test-enumerated
    surface in the reference suite, tests/test_data_loader.py).

    Two modes:
      - `split_batches=False` (default): the inner sampler yields *process-level*
        batches; consecutive groups of `num_processes` batches form one global step, and
        this process takes the `process_index`-th batch of each group.
      - `split_batches=True`: the inner sampler yields *global* batches of size
        `batch_size`; this process takes its contiguous `batch_size/num_processes` slice
        of every batch.

    `even_batches=True` pads the tail by cycling samples from the start of the epoch so
    every process sees the same number of equally-sized batches (jit-stable shapes); the
    duplicated count is exposed through `GradientState.remainder` for
    `gather_for_metrics` truncation.
    """

    def __init__(
        self,
        batch_sampler,
        num_processes: int = 1,
        process_index: int = 0,
        split_batches: bool = False,
        even_batches: bool = True,
    ):
        if split_batches and getattr(batch_sampler, "batch_size", None) is not None:
            if batch_sampler.batch_size % num_processes != 0:
                raise ValueError(
                    f"To use `split_batches=True`, the batch size ({batch_sampler.batch_size}) "
                    f"must be a round multiple of the number of processes ({num_processes})."
                )
        self.batch_sampler = batch_sampler
        self.num_processes = num_processes
        self.process_index = process_index
        self.split_batches = split_batches
        self.even_batches = even_batches
        self.batch_size = getattr(batch_sampler, "batch_size", None)
        self.drop_last = getattr(batch_sampler, "drop_last", False)

    @property
    def total_length(self):
        return len(self.batch_sampler)

    def __len__(self):
        if self.split_batches:
            return len(self.batch_sampler)
        length = len(self.batch_sampler)
        if length % self.num_processes == 0:
            return length // self.num_processes
        elif self.even_batches and not self.drop_last:
            return math.ceil(length / self.num_processes)
        elif self.drop_last:
            return length // self.num_processes
        else:
            # Uneven: this process may get one more batch than others.
            return length // self.num_processes + (1 if self.process_index < length % self.num_processes else 0)

    def __iter__(self):
        return self._iter_with_split() if self.split_batches else self._iter_with_no_split()

    def _iter_with_split(self):
        initial_data = []
        batch_length = None
        full_size = None
        for idx, batch in enumerate(self.batch_sampler):
            if idx == 0:
                initial_data = list(batch)
                # Slice size comes from the declared batch_size, not the observed batch —
                # a short *first* batch must not shrink every process's shard.
                full_size = self.batch_size or len(batch)
                batch_length = full_size // self.num_processes
            start = batch_length * self.process_index
            end = batch_length * (self.process_index + 1)
            if len(batch) == full_size:
                yield batch[start:end]
            elif self.drop_last:
                continue
            elif not self.even_batches:
                chunk = batch[start:end]
                if len(chunk) > 0:
                    yield chunk
            else:
                # Cycle from the epoch's first samples to refill to full size
                # (reference _iter_with_split data_loader.py:186-205).
                batch = list(batch)
                while len(batch) < full_size:
                    batch += initial_data[: full_size - len(batch)]
                yield batch[start:end]

    def _iter_with_no_split(self):
        initial_data = []
        group = []
        batch_size_seen = None
        for idx, batch in enumerate(self.batch_sampler):
            if idx < self.num_processes:
                initial_data += list(batch)
            if batch_size_seen is None:
                batch_size_seen = len(batch)
            group.append(list(batch))
            if len(group) == self.num_processes:
                # Only a full-sized final batch may pass through unchecked; a short one
                # is handled in the tail logic below.
                if len(group[-1]) == batch_size_seen or not self.even_batches:
                    yield group[self.process_index]
                    group = []
                    continue
                group_tail = group
                group = []
                yield from self._finish_tail(group_tail, initial_data, batch_size_seen)
                return
        if len(group) > 0:
            yield from self._finish_tail(group, initial_data, batch_size_seen)

    def _finish_tail(self, group, initial_data, batch_size_seen):
        if self.drop_last:
            # Drop incomplete global step entirely only if short; a complete group of
            # full batches was already yielded above.
            full = [b for b in group if len(b) == batch_size_seen]
            if len(full) == self.num_processes:
                yield full[self.process_index]
            return
        if not self.even_batches:
            if self.process_index < len(group):
                yield group[self.process_index]
            return
        # Pad: top up the short batch, then append cycled batches until the group is full.
        cycle = itertools.cycle(initial_data)
        for b in group:
            while len(b) < batch_size_seen:
                b.append(next(cycle))
        while len(group) < self.num_processes:
            group.append([next(cycle) for _ in range(batch_size_seen)])
        yield group[self.process_index]


class IterableDatasetShard:
    """Shard an iterable dataset by slicing each global batch
    (reference data_loader.py:256-352).

    Collects `batch_size * num_processes` samples (or `batch_size` when
    `split_batches=True`) and yields this process's contiguous slice. The tail is padded
    by cycling the first collected samples when `even_batches=True`.
    """

    def __init__(
        self,
        dataset: Iterable,
        batch_size: int = 1,
        drop_last: bool = False,
        num_processes: int = 1,
        process_index: int = 0,
        split_batches: bool = False,
        even_batches: bool = True,
    ):
        if split_batches and batch_size % num_processes != 0:
            raise ValueError(
                f"To use `split_batches=True`, the batch size ({batch_size}) must be a round "
                f"multiple of the number of processes ({num_processes})."
            )
        self.dataset = dataset
        self.batch_size = batch_size
        self.drop_last = drop_last
        self.num_processes = num_processes
        self.process_index = process_index
        self.split_batches = split_batches
        self.even_batches = even_batches

    def set_epoch(self, epoch: int):
        if hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    def __len__(self):
        n = len(self.dataset)
        real_batch = self.batch_size if self.split_batches else self.batch_size * self.num_processes
        per_proc = real_batch // self.num_processes
        full_batches = n // real_batch
        tail = n % real_batch
        if self.drop_last or tail == 0:
            return full_batches * per_proc
        if self.even_batches:
            return (full_batches + 1) * per_proc
        # Uneven tail: this process gets its surviving slice of the short batch.
        start = self.process_index * per_proc
        end = start + per_proc
        return full_batches * per_proc + max(0, min(end, tail) - start)

    def __iter__(self):
        real_batch_size = self.batch_size if self.split_batches else self.batch_size * self.num_processes
        process_slice_size = real_batch_size // self.num_processes
        start = self.process_index * process_slice_size
        end = start + process_slice_size

        first_batch = None
        current_batch = []
        for element in self.dataset:
            current_batch.append(element)
            if len(current_batch) == real_batch_size:
                yield from current_batch[start:end]
                if first_batch is None:
                    first_batch = current_batch.copy()
                current_batch = []
        if not self.drop_last and len(current_batch) > 0:
            if not self.even_batches:
                yield from current_batch[start:min(end, len(current_batch))]
                return
            if first_batch is None:
                first_batch = current_batch.copy()
            cycle = itertools.cycle(first_batch)
            while len(current_batch) < real_batch_size:
                current_batch.append(next(cycle))
            yield from current_batch[start:end]


def _default_collate(samples: List[Any]):
    """numpy-stacking collate for the built-in loader (torch-free default_collate)."""
    first = samples[0]
    if isinstance(first, dict):
        return {k: _default_collate([s[k] for s in samples]) for k in first}
    if isinstance(first, (tuple, list)):
        return type(first)(_default_collate([s[i] for s in samples]) for i in range(len(first)))
    return np.stack([np.asarray(s) for s in samples])


class SimpleDataLoader:
    """Built-in map-style loader: dataset + batch_sampler → collated host batches.

    The torch-free backend for `prepare_data_loader`; torch DataLoaders are instead
    rebuilt with a sharded batch sampler (keeping their worker pool / collate_fn).

    When the dataset is columnar (`native.loader.ArrayDataset`) and the collate is
    the default, batches are assembled by the native gather pool — the sampled rows
    of every column copied into preallocated batch buffers on C++ threads, one batch
    ahead (the C++ analogue of torch's worker pool; results are bit-identical to the
    per-row Python path)."""

    def __init__(self, dataset, batch_sampler, collate_fn: Optional[Callable] = None):
        self.dataset = dataset
        self.batch_sampler = batch_sampler
        self.collate_fn = collate_fn or _default_collate
        self._gather_pool = None

    def __len__(self):
        return len(self.batch_sampler)

    def _columnar(self) -> bool:
        from .native.loader import ArrayDataset

        return isinstance(self.dataset, ArrayDataset) and self.collate_fn is _default_collate

    def __iter__(self):
        if self._columnar():
            yield from self._native_iter()
            return
        for batch_indices in self.batch_sampler:
            yield self.collate_fn([self.dataset[i] for i in batch_indices])

    def _native_iter(self):
        from .native.loader import NativeGatherPool, iter_gather_batches

        if self._gather_pool is None:
            self._gather_pool = NativeGatherPool()
        yield from iter_gather_batches(self._gather_pool, self.dataset.columns, self.batch_sampler)


class _IterableAsLoader:
    """Adapter: an (already-sharded) iterable dataset + batch size → collated batches."""

    def __init__(self, dataset, batch_size: int, collate_fn: Optional[Callable] = None, drop_last: bool = False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.collate_fn = collate_fn or _default_collate
        self.drop_last = drop_last

    def __len__(self):
        return math.ceil(len(self.dataset) / self.batch_size)

    def __iter__(self):
        batch = []
        for sample in self.dataset:
            batch.append(sample)
            if len(batch) == self.batch_size:
                yield self.collate_fn(batch)
                batch = []
        if batch and not self.drop_last:
            yield self.collate_fn(batch)


def _to_numpy_batch(batch):
    """Torch tensors / lists → numpy leaves (host plane is numpy everywhere)."""

    def _conv(t):
        if hasattr(t, "detach") and hasattr(t, "numpy"):
            return t.detach().cpu().numpy()
        return np.asarray(t)

    def _is_leaf(t):
        return (
            hasattr(t, "detach")
            and hasattr(t, "numpy")
            or isinstance(t, (np.ndarray, np.generic))
        )

    return recursively_apply(_conv, batch, test_type=_is_leaf)


def pad_batch_to_size(batch, target_size: int):
    """Pad every leaf's axis 0 up to `target_size` by cycling the batch's own samples.

    Keeps every step the same shape (one jit compilation, divisible device sharding);
    the duplicated tail is dropped again by `gather_for_metrics` via
    `GradientState.remainder` (reference pads at the sampler plane instead —
    data_loader.py:186-253 — because its batch is per-rank; ours is per-host and must
    also divide the local device count)."""

    def _pad(t):
        if t.ndim == 0 or t.shape[0] >= target_size:
            return t
        reps = int(np.ceil(target_size / t.shape[0]))
        return np.concatenate([t] * reps, axis=0)[:target_size]

    def _is_leaf(t):
        return isinstance(t, (np.ndarray, np.generic))

    return recursively_apply(_pad, batch, test_type=_is_leaf)


def batch_to_global_array(batch, sharding):
    """Host batch → global jax.Array with the given input sharding.

    The `MpDeviceLoader`/`send_to_device` replacement (reference data_loader.py:518-559):
    under SPMD each host contributes its local shard and the result is one logical array
    spanning the mesh. Non-array leaves pass through untouched.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    def _make(t):
        t = np.asarray(t)
        if t.ndim == 0:
            return jax.device_put(t)
        try:
            return jax.make_array_from_process_local_data(sharding, t)
        except ValueError:
            # Batch smaller than (or not divisible by) the data-axis device count —
            # legal for tiny single-host eval batches; replicate instead of sharding
            # dim 0. Multi-host must not take this path: each host holds *different*
            # local data, and a replicated global array would silently diverge.
            if jax.process_count() > 1:
                raise ValueError(
                    f"Per-host batch dim {t.shape[0]} does not match the data-axis sharding "
                    f"{sharding.spec} on a multi-host mesh. Use even_batches=True (pads to a "
                    "stable per-host batch) or make the batch divisible by the local "
                    "data-parallel device count."
                )
            logger.warning_once(
                "Batch dim %d is not divisible by the data-axis device count; replicating the batch. "
                "For full throughput make the per-host batch a multiple of the local data-parallel size.",
                t.shape[0],
            )
            replicated = NamedSharding(sharding.mesh, PartitionSpec())
            return jax.make_array_from_process_local_data(replicated, t)

    def _is_leaf(t):
        return isinstance(t, (np.ndarray, np.generic))

    return recursively_apply(_make, batch, test_type=_is_leaf)


def _stamps_wait(iter_fn):
    """A loader's `__iter__` whose every `next()` leaves in `self.data_wait_s`
    how long the consumer waited for that batch, under a `train.data_wait`
    annotation in a profiler capture. The loader keeps no timeline: whoever
    does takes the stamp from the loader whose pass it is in
    (`Accelerator.train_step`, after its dispatch)."""

    @functools.wraps(iter_fn)
    def __iter__(self):
        batches = iter_fn(self)
        tracer = default_tracer()
        try:
            while True:
                with tracer.span("train.data_wait", category="train", record=False) as wait:
                    try:
                        batch = next(batches)
                    except StopIteration:
                        return
                self.data_wait_s = wait.duration_s
                yield batch
        finally:
            batches.close()

    return __iter__


class DataLoaderStateMixin:
    """begin/end hooks registering with GradientState (reference data_loader.py:355-388)."""

    #: Seconds the consumer waited for the newest batch (see `_stamps_wait`).
    data_wait_s: Optional[float] = None

    def __init_subclass__(cls, **kwargs):
        cls.end_of_dataloader = False
        cls.remainder = -1

    def reset(self):
        self.end_of_dataloader = False
        self.remainder = -1

    def begin(self):
        self.reset()
        length = self.total_dataset_length
        if length is not None and self.total_batch_size:
            self.remainder = length % self.total_batch_size
        self.gradient_state._add_dataloader(self)

    def end(self):
        self.gradient_state._remove_dataloader(self)


class DataLoaderShard(DataLoaderStateMixin):
    """Per-host loader producing global device arrays (reference data_loader.py:391-515).

    Wraps a host-batch producer (rebuilt torch DataLoader / SimpleDataLoader / iterable):
      - epoch-start host RNG sync (reference :447)
      - one-batch lookahead setting `end_of_dataloader` on the final batch (:469-473)
      - device plane: global-array formation + background prefetch
    """

    def __init__(
        self,
        base_loader,
        sharding=None,
        device_placement: bool = True,
        rng_types: Optional[List[str]] = None,
        synchronized_generator=None,
        total_batch_size: Optional[int] = None,
        total_dataset_length: Optional[int] = None,
        prefetch_size: int = 2,
        skip_batches: int = 0,
        per_host_batch_size: Optional[int] = None,
        even_batches: bool = True,
        _non_blocking: bool = True,
    ):
        self.base_loader = base_loader
        self.sharding = sharding
        self.device_placement = device_placement
        self.rng_types = rng_types
        self.synchronized_generator = synchronized_generator
        self.gradient_state = GradientState()
        self._total_batch_size = total_batch_size
        self._total_dataset_length = total_dataset_length
        # prefetch_size=0 means SYNCHRONOUS: no producer thread, batches are
        # collated + transferred inline on the consumer — the debugging mode
        # (clean stack traces, no thread interleaving). >=1 sizes the background
        # prefetch queue. (0 used to be silently clamped to 1.)
        if prefetch_size < 0:
            raise ValueError(f"prefetch_size must be >= 0 (0 = synchronous), got {prefetch_size}")
        self.prefetch_size = prefetch_size
        self.skip_batches = skip_batches
        self.per_host_batch_size = per_host_batch_size
        self.even_batches = even_batches
        self.iteration = 0

    # -- reference-parity introspection (data_loader.py:497-515) -----------------------
    @property
    def total_batch_size(self):
        return self._total_batch_size

    @property
    def total_dataset_length(self):
        if self._total_dataset_length is not None:
            return self._total_dataset_length
        dataset = getattr(self.base_loader, "dataset", None)
        try:
            return len(dataset) if dataset is not None else None
        except TypeError:
            return None

    @property
    def dataset(self):
        return getattr(self.base_loader, "dataset", None)

    @property
    def batch_sampler(self):
        return getattr(self.base_loader, "batch_sampler", None)

    def _advance_linked_loader(self):
        """A `skip_first_batches` wrapper finishing its (partial) pass advances
        the loader it was built from, so the caller's NEXT full pass over the
        original loader draws a fresh permutation instead of replaying the
        resumed epoch's order."""
        linked = getattr(self, "_linked_loader", None)
        if linked is not None:
            linked.iteration = max(linked.iteration, self.iteration)

    def set_epoch(self, epoch: int):
        """Pin the shuffle epoch for the NEXT pass (public resume API: also
        realigns the loader's own pass counter, which `__iter__` would
        otherwise feed to the sampler — so an explicit `set_epoch(E)` wins
        over however many passes this loader object has or hasn't run)."""
        self.iteration = epoch
        if hasattr(self.batch_sampler, "sampler") and hasattr(self.batch_sampler.sampler, "set_epoch"):
            self.batch_sampler.sampler.set_epoch(epoch)
        elif hasattr(self.batch_sampler, "batch_sampler") and hasattr(
            getattr(self.batch_sampler.batch_sampler, "sampler", None), "set_epoch"
        ):
            self.batch_sampler.batch_sampler.sampler.set_epoch(epoch)
        elif hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    def __len__(self):
        return max(0, len(self.base_loader) - self.skip_batches)

    def _process_batch(self, batch):
        batch = _to_numpy_batch(batch)
        if self.even_batches and self.per_host_batch_size is not None:
            batch = pad_batch_to_size(batch, self.per_host_batch_size)
        if self.device_placement:
            if self.sharding is not None:
                return batch_to_global_array(batch, self.sharding)
            return send_to_device(batch)
        return batch

    def _raw_iter(self):
        for idx, batch in enumerate(self.base_loader):
            if idx < self.skip_batches:
                continue
            yield batch

    @_stamps_wait
    def __iter__(self):
        if self.rng_types is not None:
            synchronize_rng_states(self.rng_types, self.synchronized_generator)
        self.set_epoch(self.iteration)
        self.begin()
        if self.prefetch_size == 0:
            # Synchronous debug mode: no producer thread. Same one-batch
            # lookahead so `end_of_dataloader` is still set before the final
            # batch is yielded (the gradient-sync contract).
            try:
                held = None
                for raw in self._raw_iter():
                    batch = self._process_batch(raw)
                    if held is not None:
                        yield held
                    held = batch
                if held is not None:
                    self.end_of_dataloader = True
                    yield held
                self.iteration += 1
                self._advance_linked_loader()
            finally:
                self.end()
            return
        # Background prefetch: a producer thread collates + transfers up to
        # `prefetch_size` batches ahead so host work and host→HBM DMA overlap with the
        # consumer's jitted compute (the MpDeviceLoader replacement, reference
        # data_loader.py:518-559). One batch is held back so `end_of_dataloader` is set
        # *before* the final batch is yielded (lookahead contract, reference :469-473).
        stop = threading.Event()
        q: queue.Queue = queue.Queue(maxsize=self.prefetch_size)

        def _producer():
            try:
                for raw in self._raw_iter():
                    item = ("item", self._process_batch(raw))
                    while not stop.is_set():
                        try:
                            q.put(item, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        return
                q.put(("end", None))
            except BaseException as e:  # surfaced on the consumer thread
                q.put(("error", e))

        producer = threading.Thread(target=_producer, daemon=True)
        producer.start()
        try:
            held = None
            while True:
                kind, payload = q.get()
                if kind == "error":
                    raise payload
                if kind == "end":
                    if held is not None:
                        self.end_of_dataloader = True
                        yield held
                    break
                if held is not None:
                    yield held
                held = payload
            self.iteration += 1
            self._advance_linked_loader()
        finally:
            stop.set()
            # Drain so a producer blocked on q.put can observe `stop`, then wait for it
            # to leave any in-flight device transfer — a daemon thread inside XLA at
            # interpreter shutdown aborts the process.
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            producer.join(timeout=5.0)
            self.end()


class DataLoaderDispatcher(DataLoaderStateMixin):
    """Rank-0-reads-all loader (reference data_loader.py:562-795).

    Process 0 iterates the underlying loader over the *global* batch; the batch skeleton
    travels the object plane and arrays the data plane; every host slices its shard and
    forms the same global arrays. The default for IterableDatasets (reference :883-887).
    """

    def __init__(
        self,
        base_loader,
        sharding=None,
        device_placement: bool = True,
        split_batches: bool = False,
        total_batch_size: Optional[int] = None,
        total_dataset_length: Optional[int] = None,
        skip_batches: int = 0,
        slice_fn: Optional[Callable] = None,
        per_host_batch_size: Optional[int] = None,
        even_batches: bool = True,
    ):
        self.base_loader = base_loader
        self.sharding = sharding
        self.device_placement = device_placement
        self.split_batches = split_batches
        self.state = PartialState()
        self.gradient_state = GradientState()
        self._total_batch_size = total_batch_size
        self._total_dataset_length = total_dataset_length
        self.skip_batches = skip_batches
        self.slice_fn = slice_fn
        self.per_host_batch_size = per_host_batch_size
        self.even_batches = even_batches
        self.iteration = 0

    @property
    def total_batch_size(self):
        return self._total_batch_size

    @property
    def total_dataset_length(self):
        if self._total_dataset_length is not None:
            return self._total_dataset_length
        dataset = getattr(self.base_loader, "dataset", None)
        try:
            return len(dataset) if dataset is not None else None
        except TypeError:
            return None

    @property
    def dataset(self):
        return getattr(self.base_loader, "dataset", None)

    _advance_linked_loader = DataLoaderShard._advance_linked_loader

    def set_epoch(self, epoch: int):
        """Pin the shuffle epoch for the NEXT pass (see DataLoaderShard.set_epoch)."""
        self.iteration = epoch
        if hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    def __len__(self):
        whole_length = len(self.base_loader)
        if self.split_batches or self.state.num_processes == 1:
            return max(0, whole_length - self.skip_batches)
        return max(0, math.ceil(whole_length / self.state.num_processes) - self.skip_batches)

    def _read_global_batch(self, iterator):
        """Read one *global* batch from the base loader: with `split_batches` the loader
        already yields global batches; otherwise concatenate `num_processes` consecutive
        per-process batches (reference _fetch_batches data_loader.py:618-630)."""
        from .utils.operations import concatenate

        n = 1 if (self.split_batches or self.state.num_processes == 1) else self.state.num_processes
        parts = []
        for _ in range(n):
            try:
                parts.append(_to_numpy_batch(next(iterator)))
            except StopIteration:
                break
        if not parts:
            raise StopIteration
        return parts[0] if len(parts) == 1 else concatenate(parts, dim=0)

    def _fetch_batch(self, iterator):
        """Main process reads; everyone learns (has_more, batch) via the object/data
        planes (reference _fetch_batches data_loader.py:618-660)."""
        from .utils.operations import broadcast, broadcast_object_list

        if self.state.num_processes == 1:
            try:
                return True, self._read_global_batch(iterator)
            except StopIteration:
                return False, None

        info = [None, None]  # (has_more, structure)
        batch = None
        if self.state.is_main_process:
            try:
                batch = self._read_global_batch(iterator)
                from .utils.operations import get_data_structure

                info = [True, get_data_structure(batch)]
            except StopIteration:
                info = [False, None]
        info = broadcast_object_list(info, from_process=0)
        if not info[0]:
            return False, None
        if not self.state.is_main_process:
            # Materialize zero-filled buffers matching the structure, then receive.
            def _zeros(spec):
                if isinstance(spec, dict) and set(spec) == {"shape", "dtype"}:
                    return np.zeros(spec["shape"], dtype=np.dtype(spec["dtype"]))
                if isinstance(spec, dict):
                    return {k: _zeros(v) for k, v in spec.items()}
                if isinstance(spec, (list, tuple)):
                    return type(spec)(_zeros(s) for s in spec)
                return spec

            batch = _zeros(info[1])
        batch = broadcast(batch, from_process=0)
        return True, batch

    def _slice_for_process(self, batch):
        """Pad the global batch to its stable full size FIRST, then slice — a short
        final batch sliced by observed size would drop tail samples and desync the
        remainder bookkeeping (the reference pads in _fetch_batches, data_loader.py:645)."""
        from .utils.operations import find_batch_size, slice_tensors

        batch_size = find_batch_size(batch)
        if batch_size is None:
            return batch
        full = self._total_batch_size or batch_size
        if batch_size < full:
            batch = pad_batch_to_size(batch, full)
            batch_size = full
        per_proc = batch_size // self.state.num_processes
        start = self.state.process_index * per_proc
        if self.slice_fn is not None:
            return self.slice_fn(batch, slice(start, start + per_proc), self.state.process_index, self.state.num_processes)
        return slice_tensors(batch, slice(start, start + per_proc))

    @_stamps_wait
    def __iter__(self):
        self.set_epoch(self.iteration)
        self.begin()
        try:
            iterator = iter(self.base_loader)
            batch_index = 0
            has_more, current = self._fetch_batch(iterator)
            while has_more:
                has_more, nxt = self._fetch_batch(iterator)
                if batch_index >= self.skip_batches:
                    if not has_more:
                        self.end_of_dataloader = True
                        from .utils.operations import find_batch_size

                        observed = find_batch_size(current)
                        if observed is not None and self._total_batch_size:
                            self.remainder = observed % self._total_batch_size or -1
                    local = self._slice_for_process(current) if self.state.num_processes > 1 else current
                    if self.even_batches and self.per_host_batch_size is not None:
                        local = pad_batch_to_size(local, self.per_host_batch_size)
                    if self.device_placement:
                        if self.sharding is not None:
                            yield batch_to_global_array(local, self.sharding)
                        else:
                            yield send_to_device(local)
                    else:
                        yield local
                current = nxt
                batch_index += 1
            self.iteration += 1
            self._advance_linked_loader()
        finally:
            self.end()


class SkipBatchSampler:
    """Batch sampler skipping the first N batches (reference data_loader.py:1037)."""

    def __init__(self, batch_sampler, skip_batches: int = 0):
        self.batch_sampler = batch_sampler
        self.skip_batches = skip_batches
        self.batch_size = getattr(batch_sampler, "batch_size", None)
        self.drop_last = getattr(batch_sampler, "drop_last", False)

    def __iter__(self):
        for index, samples in enumerate(self.batch_sampler):
            if index >= self.skip_batches:
                yield samples

    @property
    def total_length(self):
        return len(self.batch_sampler)

    def __len__(self):
        return len(self.batch_sampler) - self.skip_batches


def skip_first_batches(dataloader, num_batches: int = 0):
    """Mid-epoch resume: a loader that skips its first `num_batches`
    (reference data_loader.py:1082-1149).

    When the base loader exposes a batch sampler, skipping happens at the *index plane*
    (`SkipBatchSampler`) so skipped batches are never loaded or collated; otherwise the
    wrapper skips already-collated batches."""
    if isinstance(dataloader, DataLoaderShard):
        base = dataloader.base_loader
        batch_sampler = getattr(base, "batch_sampler", None)
        new_base = None
        if batch_sampler is not None:
            skip_sampler = SkipBatchSampler(batch_sampler, num_batches)
            if _is_torch_loader(base):
                new_base = _rebuild_torch_loader(base, skip_sampler)
            elif isinstance(base, SimpleDataLoader):
                new_base = SimpleDataLoader(base.dataset, skip_sampler, base.collate_fn)
        if new_base is not None:
            skipped = DataLoaderShard(
                new_base,
                sharding=dataloader.sharding,
                device_placement=dataloader.device_placement,
                rng_types=dataloader.rng_types,
                synchronized_generator=dataloader.synchronized_generator,
                total_batch_size=dataloader._total_batch_size,
                total_dataset_length=dataloader._total_dataset_length,
                prefetch_size=dataloader.prefetch_size,
                per_host_batch_size=dataloader.per_host_batch_size,
                even_batches=dataloader.even_batches,
            )
        else:
            skipped = DataLoaderShard(
                dataloader.base_loader,
                sharding=dataloader.sharding,
                device_placement=dataloader.device_placement,
                rng_types=dataloader.rng_types,
                synchronized_generator=dataloader.synchronized_generator,
                total_batch_size=dataloader._total_batch_size,
                total_dataset_length=dataloader._total_dataset_length,
                prefetch_size=dataloader.prefetch_size,
                skip_batches=dataloader.skip_batches + num_batches,
                per_host_batch_size=dataloader.per_host_batch_size,
                even_batches=dataloader.even_batches,
            )
    elif isinstance(dataloader, DataLoaderDispatcher):
        skipped = DataLoaderDispatcher(
            dataloader.base_loader,
            sharding=dataloader.sharding,
            device_placement=dataloader.device_placement,
            split_batches=dataloader.split_batches,
            total_batch_size=dataloader._total_batch_size,
            total_dataset_length=dataloader._total_dataset_length,
            skip_batches=dataloader.skip_batches + num_batches,
            slice_fn=dataloader.slice_fn,
            per_host_batch_size=dataloader.per_host_batch_size,
            even_batches=dataloader.even_batches,
        )
    else:
        skipped = None
    if skipped is not None:
        # The resumed partial pass must shuffle with the interrupted epoch's
        # permutation, not a fresh wrapper's pass 0 — carry the source
        # loader's pass counter across (it was itself realigned by
        # load_state when resuming in a fresh process), and link back so the
        # wrapper's completed pass advances the source: the caller's next
        # full pass over the ORIGINAL loader must draw the following epoch's
        # permutation, not replay the resumed one.
        skipped.iteration = dataloader.iteration
        skipped._linked_loader = dataloader
        return skipped

    # Raw iterable / torch loader: generic skipping wrapper.
    class _Skipper:
        def __init__(self, dl, n):
            self.dl = dl
            self.n = n
            self.dataset = getattr(dl, "dataset", None)

        def __iter__(self):
            for i, b in enumerate(self.dl):
                if i >= self.n:
                    yield b

        def __len__(self):
            return max(0, len(self.dl) - self.n)

    return _Skipper(dataloader, num_batches)


def _is_torch_loader(dataloader) -> bool:
    if not is_torch_available():
        return False
    import torch.utils.data

    return isinstance(dataloader, torch.utils.data.DataLoader)


def _rebuild_torch_loader(dataloader, new_batch_sampler):
    """Rebuild a torch DataLoader around a sharded batch sampler, keeping its worker
    pool and collate_fn (the reference does the same surgery, data_loader.py:905-1010)."""
    import torch.utils.data

    kwargs = {
        "num_workers": dataloader.num_workers,
        "collate_fn": dataloader.collate_fn,
        "pin_memory": False,  # jax owns the host→device path
        "timeout": dataloader.timeout,
        "worker_init_fn": dataloader.worker_init_fn,
        "prefetch_factor": dataloader.prefetch_factor if dataloader.num_workers > 0 else None,
        "persistent_workers": dataloader.persistent_workers,
    }
    kwargs = {k: v for k, v in kwargs.items() if v is not None or k == "collate_fn"}
    return torch.utils.data.DataLoader(dataloader.dataset, batch_sampler=new_batch_sampler, **kwargs)


def default_data_sharding(mesh=None):
    """NamedSharding putting axis 0 on ("data","fsdp") — the canonical input sharding."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is None:
        mesh = AcceleratorState().mesh
    return NamedSharding(mesh, PartitionSpec(("data", "fsdp")))


def prepare_data_loader(
    dataloader,
    device=None,
    num_processes: Optional[int] = None,
    process_index: Optional[int] = None,
    split_batches: bool = False,
    put_on_device: bool = True,
    rng_types: Optional[List[str]] = None,
    dispatch_batches: Optional[bool] = None,
    even_batches: bool = True,
    slice_fn_for_dispatch: Optional[Callable] = None,
    use_seedable_sampler: bool = True,
    data_seed: int = 42,
    sharding=None,
    prefetch_size: int = 2,
) -> DataLoaderShard | DataLoaderDispatcher:
    """Factory combining sharded sampling + host loading + device plane (reference
    data_loader.py:797-1034).

    Accepts a torch DataLoader (rebuilt with a sharded batch sampler), a
    `SimpleDataLoader`, a map-style dataset paired with an existing batch_sampler, or
    any iterable of batches (treated as an already-per-host stream).

    `prefetch_size` sizes the background producer queue (host collation +
    host→HBM DMA overlap with jitted compute); **0 disables the producer thread
    entirely** — synchronous inline batches for debugging (clean stack traces,
    no thread interleaving), at the cost of the transfer/compute overlap.
    """
    state = PartialState()
    if num_processes is None:
        num_processes = state.num_processes
    if process_index is None:
        process_index = state.process_index

    if sharding is None and put_on_device:
        sharding = default_data_sharding()

    synchronized_generator = None

    # --- torch DataLoader path --------------------------------------------------------
    if _is_torch_loader(dataloader):
        import torch.utils.data

        dataset = dataloader.dataset
        is_iterable = isinstance(dataset, torch.utils.data.IterableDataset)
        if dispatch_batches is None:
            dispatch_batches = is_iterable and num_processes > 1
        batch_size = dataloader.batch_size if dataloader.batch_size is not None else getattr(
            dataloader.batch_sampler, "batch_size", 1
        )
        total_batch_size = batch_size * (1 if split_batches else num_processes)

        per_host_bs = batch_size // num_processes if split_batches else batch_size
        if dispatch_batches:
            return DataLoaderDispatcher(
                dataloader,
                sharding=sharding,
                device_placement=put_on_device,
                split_batches=split_batches,
                total_batch_size=total_batch_size,
                slice_fn=slice_fn_for_dispatch,
                per_host_batch_size=per_host_bs,
                even_batches=even_batches,
            )
        if is_iterable:
            shard = IterableDatasetShard(
                dataset,
                batch_size=batch_size,
                drop_last=dataloader.drop_last,
                num_processes=num_processes,
                process_index=process_index,
                split_batches=split_batches,
                even_batches=even_batches,
            )
            base = _IterableAsLoader(shard, per_host_bs, collate_fn=dataloader.collate_fn)
            return DataLoaderShard(
                base,
                sharding=sharding,
                device_placement=put_on_device,
                rng_types=rng_types,
                total_batch_size=total_batch_size,
                prefetch_size=prefetch_size,
                per_host_batch_size=per_host_bs,
                even_batches=even_batches,
            )
        # Map-style: swap the sampler if seedable shuffling requested, then shard batches.
        batch_sampler = dataloader.batch_sampler
        if use_seedable_sampler and isinstance(getattr(batch_sampler, "sampler", None), torch.utils.data.RandomSampler):
            seedable = SeedableRandomSampler(num_samples=len(dataset), seed=data_seed)
            synchronized_generator = seedable
            batch_sampler = BatchSampler(seedable, batch_size=batch_size, drop_last=dataloader.drop_last)
        new_batch_sampler = (
            batch_sampler
            if num_processes == 1
            else BatchSamplerShard(
                batch_sampler,
                num_processes=num_processes,
                process_index=process_index,
                split_batches=split_batches,
                even_batches=even_batches,
            )
        )
        base = _rebuild_torch_loader(dataloader, new_batch_sampler)
        return DataLoaderShard(
            base,
            sharding=sharding,
            device_placement=put_on_device,
            rng_types=rng_types,
            synchronized_generator=synchronized_generator,
            total_batch_size=total_batch_size,
            total_dataset_length=len(dataset),
            prefetch_size=prefetch_size,
            per_host_batch_size=per_host_bs,
            even_batches=even_batches,
        )

    # --- built-in loaders (SimpleDataLoader / native columnar) ------------------------
    # One contract for both: shard the batch sampler across processes and wrap
    # in the device plane, so either loader prepared through the Accelerator
    # gets sampler checkpointing (save_state's _find_seedable_sampler walks
    # batch_sampler.sampler), epoch-synced reshuffles, dispatch_batches, and
    # the end_of_dataloader boundary. Only the base rebuild differs.
    from .native.loader import NativeArrayLoader

    if isinstance(dataloader, (SimpleDataLoader, NativeArrayLoader)):
        batch_sampler = dataloader.batch_sampler
        batch_size = getattr(batch_sampler, "batch_size", 1)
        total_batch_size = batch_size * (1 if split_batches else num_processes)
        per_host_bs = batch_size // num_processes if split_batches else batch_size
        if dispatch_batches:
            return DataLoaderDispatcher(
                dataloader,
                sharding=sharding,
                device_placement=put_on_device,
                split_batches=split_batches,
                total_batch_size=total_batch_size,
                slice_fn=slice_fn_for_dispatch,
                per_host_batch_size=per_host_bs,
                even_batches=even_batches,
            )
        if use_seedable_sampler and isinstance(getattr(batch_sampler, "sampler", None), SeedableRandomSampler):
            synchronized_generator = batch_sampler.sampler
        new_batch_sampler = (
            batch_sampler
            if num_processes == 1
            else BatchSamplerShard(
                batch_sampler,
                num_processes=num_processes,
                process_index=process_index,
                split_batches=split_batches,
                even_batches=even_batches,
            )
        )
        if new_batch_sampler is batch_sampler:
            base = dataloader  # sampler unchanged: keep the loader (and any native gather pool)
        elif isinstance(dataloader, NativeArrayLoader):
            base = NativeArrayLoader(
                dataloader.dataset, new_batch_sampler, num_threads=dataloader.num_threads
            )
        else:
            base = SimpleDataLoader(
                dataloader.dataset, new_batch_sampler, collate_fn=dataloader.collate_fn
            )
        try:
            total_len = len(dataloader.dataset)
        except TypeError:
            total_len = None
        return DataLoaderShard(
            base,
            sharding=sharding,
            device_placement=put_on_device,
            rng_types=rng_types,
            synchronized_generator=synchronized_generator,
            total_batch_size=total_batch_size,
            total_dataset_length=total_len,
            prefetch_size=prefetch_size,
            per_host_batch_size=per_host_bs,
            even_batches=even_batches,
        )

    # Any iterable of batches: assume it already yields this host's batches.
    return DataLoaderShard(
        dataloader,
        sharding=sharding,
        device_placement=put_on_device,
        rng_types=rng_types,
        prefetch_size=prefetch_size,
    )
