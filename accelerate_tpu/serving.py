"""Continuous-batching serving engine: slot-based in-flight batching over the
fused decode loop.

The static `Generator` path runs one prefill + one fused decode to completion:
short requests wait for the longest row, finished rows burn MXU cycles on masked
work, and nothing new can join until the whole batch drains. `ContinuousBatcher`
keeps the GSPMD single-compiled-program discipline (one decode executable, ever)
but makes the BATCH dynamic at the host level:

  - A fixed-capacity **slot batch**: `num_slots` rows over one static KV cache.
    A slot is a logical cache row; requests come and go, the compiled program
    never changes shape. The cache is a POOL of fixed-size KV pages plus
    per-slot page tables riding as traced int32 operands
    (`ops/attention.slot_cache_attention`): admission reserves
    `ceil((prompt + max_new) / page_size)` pages — memory proportional to each
    request's ACTUAL footprint, not the engine-wide `max_length` worst case —
    and a page-granular prefix cache (`paging.PagePool`) maps shared prompt
    prefixes (system prompts) to shared read-only pages with refcounts, so a
    repeated prefix costs zero prefill FLOPs and zero duplicate HBM after its
    first request. (A dense one-row-per-slot read survives only as the
    tests' reference, in `tests/test_paging.py`.)
  - **insert** (one executable per power-of-two prompt bucket): gather the
    slot's pages into a batch-1 dense cache, prefill the new request's prompt
    (the part no shared prefix covers) through the ordinary decode-cache path,
    `tree_scatter_pages` the result back into the pool, read the logits at the
    prompt's REAL length (a traced scalar — bucket pads never recompile), and
    sample the first token. TTFT = one insert dispatch.
  - **decode_chunk** (ONE executable per engine): a `lax.scan` stepping ALL
    slots `chunk_size` tokens per dispatch through the models' per-row slot
    cache (`ops/attention.slot_cache_attention`). Per-slot position counters,
    per-slot GenerationConfig scalars (temperature / repetition penalty / EOS id
    / token budget ride as traced operands, the no-recompile discipline of
    generation.py's fused loop), EOS + budget masking, and a packed
    `(slot_id, token)` output buffer the host drains for streaming.

  - **speculative decode** (`speculative=True`): each chunk iteration becomes
    a draft-then-verify step — a host-free n-gram drafter
    (`speculative.propose_ngram_drafts`) proposes `draft_tokens` continuations
    from the slot's own observed context, ONE multi-token verify dispatch
    (`make_causal_programs(..., verify_block=True)` over
    `slot_cache_attention`'s multi-position path) scores all of them, and the
    longest greedily-confirmed prefix plus one bonus token is emitted — 1 to
    draft_tokens+1 tokens per dispatch instead of exactly 1, with greedy
    output token-identical to the plain path by construction. The accept/
    reject loop, EOS-in-block truncation, and history maintenance are all
    traced ops inside the one decode executable; the host only pushes its
    [S, max_length] context mirror as one more per-dispatch operand. Greedy
    engines only (sampling/repetition-penalty engines raise); admission
    reserves the draft window's pages alongside the request footprint.

Between chunks the host frees finished slots and admits queued requests — a
late-arriving request starts decoding while earlier long requests are still
mid-flight. Stale K/V from a slot's previous occupant is never visible: each row
attends only `cols <= its own position`, and insert overwrites the prompt rows.

**What may be in flight when `step()` returns.** Slot state is carried ON THE
DEVICE from chunk to chunk — a chunk is dispatched on its predecessor's
outputs, and the host pushes only the rows it changed (`_SlotMirror`) — so a
chunk can be enqueued before the one ahead of it has been read.
`step()` does that exactly when requests are LEFT IN THE QUEUE after admission
(a backlog: no arrival could have been admitted sooner anyway): it enqueues
its inserts and its decode chunk behind the chunk that is still running and
only then reads that older chunk back, so the device always holds its next
program. Such a step returns with ONE chunk in flight: `pending` stays True,
and the tokens of that chunk — and the first tokens of the requests admitted
with it — are handed out by the next `step()`. Whoever drives the engine
flushes it by stepping while `pending` (`run()`, `drain()`), or by `close()`,
which reads the chunk back before it cancels. With an EMPTY queue a step reads
back the chunk it dispatched itself and nothing is in flight when it returns,
so an arrival's insert never waits behind a chunk queued ahead of it. Running
ahead, the host works from a PREDICTED mirror: a request that ends by length is
known a chunk early, its slot is vacated at that dispatch and re-admitted
before its last tokens are drained; one that stops on its EOS is known at its
drain, one chunk late (`stats["slot_chunks_lost_to_eos"]`). Speculative engines,
whose blocks the host cannot predict, always read their own chunk
(`stats["run_ahead"]`).

Greedy outputs are token-identical to the static `Generator` path (pads
contribute exact zeros under the f32 softmax; rows are independent in every
layer), which is what `tests/test_serving.py` pins.

Fault isolation (the serving-runtime half of the resilience layer): the engine
degrades PER-REQUEST, never per-process. Admission failures (a transient device
error during an insert, a malformed prompt that slipped validation) mark only
that request `finish_reason="error"`; per-request wall-clock deadlines are
enforced at step boundaries (`finish_reason="timeout"`); `cancel()` frees an
in-flight slot immediately (a chunk in flight may still stream up to one chunk
of its tokens, which no step hands out); a bounded queue raises `QueueFull` so callers get
explicit backpressure instead of unbounded host memory growth; and
`drain()`/`close()` give the server a clean shutdown lifecycle. The one shared
decode executable is the blast-radius exception: if a chunk dispatch itself
dies, every in-flight request errors (the cache state is gone; a successor
chunk and the inserts already enqueued behind it are condemned with it) but the engine
stays up and keeps admitting — the slot cache is rebuilt from zeros, since the
failed dispatch may already have consumed the donated buffers. An insert
failure that consumed ITS donated operands (accelerators only) widens to the
same blast-radius recovery; otherwise admission failures stay per-request.
"""

from __future__ import annotations

import dataclasses
import inspect
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .generation import (
    GenerationConfig,
    _apply_repetition_penalty,
    _bucket_for,
    _operand,
    _params_resolver,
    _sample,
    make_cached_prefill_program,
    make_causal_programs,
)
from .logging import get_logger
from .ops import attention as attention_ops
from .ops.quantization import KV_CACHE_DTYPES, WEIGHT_DTYPES, weight_autocast
from .paging import SCRATCH_PAGE, PagePool, chain_hashes, pages_for
from .parallel.sharding import constrain_tp_cache, resolve_serving_sharding, tree_device_nbytes
from .speculative import (
    DEFAULT_DRAFT_NGRAM,
    DEFAULT_DRAFT_TOKENS,
    greedy_accept_length,
    propose_ngram_drafts,
)
from .telemetry import MetricsRegistry
from .telemetry.tracing import default_tracer
from .utils.operations import (
    _leaf_name,
    tree_gather_pages,
    tree_scatter_pages,
    tree_slot_state_nbytes,
    tree_zero_cache_tail,
)

logger = get_logger(__name__)


def _cached_key_leaf(cache):
    """One layer's `cached_key` leaf of a cache tree (every layer's has the
    same shape and dtype), [..., KV heads, head_dim] — or a latent family's
    `cached_latent`, [..., row], which keys and values are both read from."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if _leaf_name(path) in ("cached_key", "cached_latent"):
            return leaf
    raise ValueError("the cache holds no `cached_key` and no `cached_latent`")


def _expert_token_counts(cache):
    """`[expert layers, 2, experts]` int32 — the `expert_tokens` leaves a family
    with routed experts keeps in its slot cache (`models/latent_moe.py`: the
    tokens an expert was given, and the dispatches that gave it any) — or None
    where the cache holds none."""
    leaves = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
              if _leaf_name(path) == "expert_tokens"]
    return jnp.stack(leaves) if leaves else None


def _zero_expert_token_counts(cache):
    """The cache with its `expert_tokens` leaves at zero: a chunk counts its own."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.zeros_like(leaf) if _leaf_name(path) == "expert_tokens" else leaf,
        cache,
    )


def _merge_slot_updates(token, pos, active, rem, eos_ids, first_token, update):
    """The decode chunk's `(token, pos, active, rem)`: its predecessor's
    outputs, which the host may not have seen yet, with the rows the host
    changed since the last dispatch laid over them. `update` is
    `int32[5, num_slots]` — `changed`, `fresh`, then the host's `pos`,
    `active`, `rem` — and only the `changed` rows are taken from it: slots
    admitted (also `fresh`), vacated, cancelled or timed out. A `fresh` slot
    starts from the token its insert left on the device (`first_token`); the
    host has not seen that token, so the test it would make (a first token
    that is the request's EOS ends it) is made here. Returns `fresh` too."""
    changed, fresh = update[0] != 0, update[1] != 0
    pos = jnp.where(changed, update[2], pos)
    active = jnp.where(changed, update[3] != 0, active)
    rem = jnp.where(changed, update[4], rem)
    token = jnp.where(fresh, first_token, token)
    active = active & ~(fresh & (eos_ids >= 0) & (token == eos_ids))
    return token, pos, active, rem, fresh


@dataclass
class _Flight:
    """One dispatched decode chunk whose outputs the host has not read: what
    its drain needs of the host's state AS IT WAS WHEN THE CHUNK WAS
    DISPATCHED, since a step that runs ahead admits into slots and predicts
    past this chunk before it reads it back."""

    read: Dict[str, Any]  # the chunk's outputs the drain takes, on the device
    span: Any  # its open `serve.decode_chunk` span
    tenants: List[Optional["RequestResult"]]  # slot -> request
    fresh: List[Tuple[int, float, int]]  # slots whose first token rides it (`_fresh`), in admission order
    was_active: np.ndarray  # bool[num_slots]: decoding in this chunk
    ends: np.ndarray  # bool[num_slots]: vacated at its dispatch; the drain finishes the result
    eos: np.ndarray  # int32[num_slots]: the tenants' EOS ids
    pos_before: np.ndarray  # speculative: where each slot's drained tokens append


def _pool_token_sizes(cache) -> Tuple[int, int]:
    """`(stored bytes one token holds in the pool over all layers, values one
    layer holds of it)`: keys and values of full heads ([..., pages,
    page_size, heads, head_dim]) or a latent family's one row a layer ([...,
    pages, page_size, row]); nn.scan puts its layers in front. A layer's values
    are 2 x KV heads x head_dim, or the latent row."""
    held = {"cached_key": 2, "cached_value": 2, "cached_latent": 1}
    layers = nbytes = row_values = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        trailing = held.get(_leaf_name(path))
        if trailing is None:
            continue
        stacked = int(np.prod(leaf.shape[: leaf.ndim - trailing - 2]))
        values = stacked * int(np.prod(leaf.shape[leaf.ndim - trailing:]))
        row_values += values
        nbytes += values * np.dtype(leaf.dtype).itemsize
        layers += stacked * (_leaf_name(path) != "cached_value")
    return nbytes, row_values // layers


#: Config fields a family may carry, and what it serves by carrying them.
_OPTIONAL_FIELDS = {
    "weight_dtype": "int8 weight-only serving",
    "decode_tp_mesh": "tensor-parallel serving",
    "decode_kv_cache_dtype": "the quantized KV page pool",
}


@dataclass(frozen=True)
class _FamilyFacts:
    """What the engine knows of the served family: asked of the model's config
    once (`_family_facts`), read from here ever after."""

    name: str  # the flax module's class name, as refusals spell the family
    config: Any  # the module's config: `dataclasses.replace`d for the engine's modules, asked for span counts
    vocab_size: int
    max_positions: int
    heads: int
    kv_heads: int
    latent: bool  # the cache holds one latent row a token a layer (MLA), not keys and values by head
    residual_streams: int  # 1: the plain residual; more: hyper-connections mix them
    optional: frozenset  # the `_OPTIONAL_FIELDS` its config carries

    def fields(self, **wanted) -> Dict[str, Any]:
        """`wanted` (optional config fields and their values) for
        `dataclasses.replace`; a field the family's config lacks is refused."""
        for name in wanted:
            if name not in self.optional:
                raise ValueError(
                    f"{self.name}'s config has no `{name}` field — this model family "
                    f"doesn't support {_OPTIONAL_FIELDS[name]} yet"
                )
        return wanted


def _family_facts(model, kv_cache_dtype: str, tp: int) -> _FamilyFacts:
    """The ONE place the engine probes a model's config (`models.llama.ServedConfig`
    says what a family declares): what it needs of every family is refused here
    where missing, what a family may carry is recorded (`_FamilyFacts.optional`)
    and refused where the engine is asked for it."""
    if getattr(model, "module", None) is None or not hasattr(model.module, "config"):
        raise ValueError("ContinuousBatcher needs a Model bundle built from an in-tree flax module")
    base, family = model.module.config, type(model.module).__name__
    if not hasattr(base, "decode_page_size"):
        raise ValueError(
            f"{family}'s config has no `decode_page_size` "
            "field — this model family doesn't support slot-batched serving "
            "yet (the slot cache is a page pool)"
        )
    if "logits_at" not in inspect.signature(type(model.module).__call__).parameters:
        raise ValueError(
            f"{family}.__call__ takes no `logits_at` — an insert "
            "computes the head for the one row it samples (`models.llama.rows_for_head`), "
            "and the engine has no full-logits insert to fall back to"
        )
    # A latent cache (MLA: one `[c | k_pe]` row a token a layer, which the
    # config says by `decode_kv_row_values`) is read on one device,
    # unquantized — by the page-walk kernel where it reads the pool of rows
    # in place, else by the XLA loop (`ops.attention.slot_attention_impl`:
    # latent rows are never staged). The other combinations name what is
    # missing.
    latent = getattr(base, "decode_kv_row_values", None) is not None
    if latent:
        if kv_cache_dtype != "bf16":
            raise ValueError(
                f"kv_cache_dtype={kv_cache_dtype!r} with {family}: the quantized pool "
                "keeps one scale a page a KV head, and a latent row has no heads — a "
                "quantized pool for latent rows is not built; use kv_cache_dtype=\"bf16\""
            )
        if tp > 1:
            raise ValueError(
                f"tp={tp} with {family}: every head reads the same latent row, so a "
                "tensor-parallel engine needs the row replicated and the absorbed "
                "projections split by head (and the experts an \"expert\" axis) — that "
                "layout is not built; use tp=1"
            )
    return _FamilyFacts(
        name=family, config=base, vocab_size=int(base.vocab_size),
        max_positions=int(base.max_position_embeddings), heads=int(base.num_attention_heads),
        kv_heads=int(getattr(base, "num_key_value_heads", base.num_attention_heads)), latent=latent,
        residual_streams=int(getattr(base, "hc_mult", 1)),
        optional=frozenset(name for name in _OPTIONAL_FIELDS if hasattr(base, name)),
    )


class _SlotMirror:
    """The host's side of the slot state, and the one place it is pushed from.

    Slot state (`token`, `pos`, `active`, `rem`) lives ON THE DEVICE from
    chunk to chunk (`ContinuousBatcher._carry`). The host keeps a PREDICTED
    mirror of `pos`, `active`, `rem`: what they will be once every dispatched
    chunk has run (`dispatched`), put right at the drain where the device
    alone could know (`adopt`). Only the rows the host itself changed since
    the last dispatch — `changed`; admissions also `from_buffer`: their token
    is the one their insert left on the device — are pushed, as the chunk's
    `update` operand (`_merge_slot_updates`); a step that changed none pushes
    one array of zeros, made once.

    What only the host writes (`eos`, `temp`, `pen`, `page_table`) has a
    mirror each, its copy on the device, and its name among the stale while
    the mirror has changed since that copy. All-zeros table rows point at the
    scratch page, so a freed or idle slot's discarded decode writes can never
    land in a live request's pages. A speculative engine also mirrors each
    slot's observed context (`history`: prompt + generated, packed from index
    0) and pushes it with every chunk: the device updates its copy inside the
    scan (drafts must see tokens emitted earlier in the SAME chunk), the host
    re-derives identical content from the drained stream (`saw`), so nothing
    is ever read back.

    Every push goes through `_push`, which COPIES: on a CPU the device array
    may alias the numpy buffer it was made from, and the host writes its
    mirrors in place while a chunk that reads the pushed array is still
    running."""

    def __init__(self, num_slots: int, pages_per_slot: int, history_length: Optional[int] = None):
        S = num_slots
        self.pos = np.zeros(S, np.int32)
        self.active = np.zeros(S, bool)
        self.rem = np.zeros(S, np.int32)
        self.changed = np.zeros(S, bool)
        self.from_buffer = np.zeros(S, bool)
        self.eos = np.full(S, -1, np.int32)
        self.temp = np.ones(S, np.float32)
        self.pen = np.ones(S, np.float32)
        self.page_table = np.zeros((S, pages_per_slot), np.int32)
        self.history = None if history_length is None else np.zeros((S, history_length), np.int32)
        self._host_only = {"eos": self.eos, "temp": self.temp, "pen": self.pen, "table": self.page_table}
        self._pushed: Dict[str, Any] = {}
        self._stale = set(self._host_only)
        self._no_update = jnp.zeros((5, S), jnp.int32)

    @staticmethod
    def _push(host: np.ndarray):
        return jnp.asarray(host.copy())

    def set_row(self, name: str, slot: int, value):
        """Write one slot's entry of a host-only operand's mirror; the device's
        copy goes stale only where the value is new."""
        mirror = self._host_only[name]
        value = mirror.dtype.type(value)
        if mirror[slot] != value:
            mirror[slot] = value
            self._stale.add(name)

    def admit(self, slot: int, prompt: np.ndarray, budget: int, eos: int, temperature: float,
              penalty: float, page_row: np.ndarray):
        """A request takes `slot`: the device is told with the next dispatch,
        its state from the host, its token from the buffer. `budget` is what it
        may decode after the token its insert samples; 0 is a one-token
        request, which the chunk sees as an idle slot (position 0, the scratch
        row)."""
        self.changed[slot] = self.from_buffer[slot] = True
        self.rem[slot] = budget
        self.set_row("eos", slot, eos)
        if budget > 0:
            p = int(prompt.size)
            self.pos[slot] = p  # the first generated token's write position
            self.active[slot] = True
            self.set_row("temp", slot, temperature)
            self.set_row("pen", slot, penalty)
            if self.history is not None:
                # The drafter's context: the full prompt (prefix-cache hits
                # included — the host has the whole prompt even when the insert
                # only saw the suffix). The chunk puts the first token at
                # [slot, p] on the device, the drain here (`saw`).
                self.history[slot, :p] = prompt
                self.history[slot, p:] = 0
            self.page_table[slot] = page_row
            self._stale.add("table")

    def vacate(self, slot: int, held_pages: bool):
        """`slot` is idle from the next dispatch on. An idle slot sits at
        position 0: the XLA read takes a row's live pages from its position,
        and a released slot left at its last one would pass for that many pages
        of scratch. The row of a slot that `held_pages` points at the scratch
        page again, so any residual write for it is discarded."""
        self.active[slot] = False
        self.pos[slot] = self.rem[slot] = 0
        self.changed[slot], self.from_buffer[slot] = True, False
        if held_pages:
            self.page_table[slot] = SCRATCH_PAGE
            self._stale.add("table")

    def dispatched(self, steps: Optional[int]):
        """A chunk went out with `operands()`: the device has every change, and
        the prediction moves past the chunk's `steps` decode steps — an active
        slot streams one token a step until its budget ends, unless it stops on
        its EOS, which only the drain sees. None: a speculative engine predicts
        nothing (a verified block's length is the device's) and adopts the
        readback."""
        self.changed[:] = self.from_buffer[:] = False
        if steps is not None:
            took = np.where(self.active, np.minimum(self.rem, steps), 0)
            self.pos += took
            self.rem -= took
            self.active &= self.rem > 0

    def saw(self, slot: int, start: int, tokens):
        """The drain saw `tokens` of `slot`, which the chunk put at
        `history[start:]` on the device: the next push holds the same context."""
        self.history[slot, start : start + len(tokens)] = tokens

    def adopt(self, slots: np.ndarray, pos: np.ndarray, rem: np.ndarray):
        """Where the device left `slots` (a mask), off a readback."""
        self.pos[slots], self.rem[slots] = pos[slots], rem[slots]

    def reset(self):
        """Every slot idle, as on a device whose state was rebuilt from zeros:
        nothing of the host's is ahead of it."""
        self.pos[:] = self.rem[:] = 0
        self.active[:] = self.changed[:] = self.from_buffer[:] = False
        if self.history is not None:
            self.history[:] = 0
        self.page_table[:] = SCRATCH_PAGE
        self._stale.add("table")

    def operands(self) -> Tuple[List[Any], Any, Optional[Any]]:
        """`([eos, temp, pen, page table], update, history)` on the device for
        the next chunk: the stale host-only operands pushed again, the others
        as they were; the changed slots' rows (`changed`, `from_buffer`, `pos`,
        `active`, `rem`) or the array of zeros; a speculative engine's context
        (else None)."""
        for name in self._stale:
            self._pushed[name] = self._push(self._host_only[name])
        self._stale.clear()
        update = self._no_update
        if self.changed.any():
            update = self._push(np.stack(
                [self.changed, self.from_buffer, self.pos, self.active, self.rem]
            ).astype(np.int32, copy=False))
        history = None if self.history is None else self._push(self.history)
        return [self._pushed[name] for name in ("eos", "temp", "pen", "table")], update, history


class _StarvedAccount:
    """The account of a starved device.

    The engine knows, without the device, when nothing it enqueued is still
    unread: no chunk in flight and no insert dispatched since the last
    readback returned. The wall time it spends in that state goes to the cause
    that let it happen (`serving_device_starved_seconds_total{cause}`,
    `stats["device_starved"]`), at the boundaries of `step()`: `admit` (step
    start → the first insert's dispatch call returns, or the end of an
    admission that admits nothing), `push` then `dispatch` (→ the chunk's
    launch returns, where no insert went out before it), `drain` (the
    readback's return → step() returns, where it left nothing in flight) —
    `starved_admit_s` / `_push_s` / `_dispatch_s` / `_drain_s` on `serve.step`,
    summed in `starved_s`, at most `host_s` — and between two steps `client`
    (work was pending when the step before returned) or `no_work` (nothing
    was: the offered load's idle, not the host's): `gap_s`, the whole gap
    before a step, and `gap_cause`, who had it — or `"covered"`: a chunk was in
    flight through it, nothing starved, nothing charged. A gap is its cause's
    from its first instant: a request submitted into an engine that had nothing
    pending waits in a `no_work` gap until the next step(). Time inside the
    step's wait is never charged — the host cannot see when the device
    finished — so the account is a LOWER BOUND of the device's idle time, short
    by the readback's latency and the launch's tail. A step that runs ahead
    charges nothing after its first dispatch, and one that finds a chunk in
    flight and leaves one charges nothing at all: in a steady backlog every
    cause reads 0. The step that ENDS a backlog (it reads the last chunk in
    flight back and dispatches none) charges its drain.

    One mark, `_empty_since`: since when the engine has known the device
    EMPTY, or None while something it enqueued is unread; every charge moves
    it on."""

    def __init__(self, counters: Dict[str, Any]):
        self._counters = counters  # by cause (`STARVED_CAUSES`)
        self._empty_since: Optional[float] = None
        self._first_step_at: Optional[float] = None
        self._step_returned_at: Optional[float] = None
        self._gap_cause = "no_work"  # whose the gap after the last step() is, where nothing covers it
        self._in_step: Dict[str, float] = {}  # the running step's own charges
        self._read_back_at: Optional[float] = None  # when the last readback returned

    def charge(self, cause: str, now: Optional[float] = None):
        """Charge `cause` the wall time since the mark, where the device was
        EMPTY through it, and move the mark to `now`."""
        if self._empty_since is None:
            return
        now = time.perf_counter() if now is None else now
        seconds = now - self._empty_since
        self._empty_since = now
        self._counters[cause].inc(seconds)
        self._in_step[cause] = self._in_step.get(cause, 0.0) + seconds

    def enqueued(self, cause: str, now: Optional[float] = None):
        """A program's dispatch call returned: what the device sat empty until
        `now` is `cause`'s, and it has work from here on."""
        self.charge(cause, now)
        self._empty_since = None

    def read_returned(self) -> Optional[float]:
        """The step's readback returned, now: seconds since the one before it
        did (None for the first)."""
        previous, self._read_back_at = self._read_back_at, time.perf_counter()
        return None if previous is None else self._read_back_at - previous

    def all_read(self, at: Optional[float] = None):
        """Nothing the engine enqueued is left to read, since `at` (the last
        readback's return where none is given)."""
        self._empty_since = self._read_back_at if at is None else at

    def begin_step(self, now: float) -> Tuple[float, str]:
        """`(gap_s, gap_cause)` of the gap since the last step() returned:
        covered by a chunk left in flight, or the device sat empty through it —
        the client's, or nobody's where nothing was pending. Charged; the
        step's own parts count from here."""
        if self._first_step_at is None:
            self._first_step_at = self._step_returned_at = self._empty_since = now
        gap_s = now - self._step_returned_at
        gap_cause = "covered" if self._empty_since is None else self._gap_cause
        self.charge(gap_cause, now)  # nothing where a chunk covers the gap
        self._in_step = {}
        return gap_s, gap_cause

    def end_step(self, pending: bool) -> Dict[str, float]:
        """step() returns, now: what it left empty is its drain's, and the gap
        that starts is the client's where work is `pending`. Returns the step's
        own parts, for its span."""
        self._step_returned_at = time.perf_counter()
        if self._empty_since is not None:
            self.charge("drain", self._step_returned_at)
            self._gap_cause = "client" if pending else "no_work"
        parts = {cause: round(self._in_step.get(cause, 0.0), 6) for cause in ("admit", "push", "dispatch", "drain")}
        return {"starved_s": round(sum(parts.values()), 6),
                **{f"starved_{cause}_s": seconds for cause, seconds in parts.items()}}

    def view(self) -> Dict[str, Optional[float]]:
        """`stats["device_starved"]`: the seconds by cause, and `share`: the
        host's part of them — every cause but `no_work` — over the wall since
        the first step(). A lower bound of the device's idle share."""
        view: Dict[str, Optional[float]] = {cause: c.value for cause, c in self._counters.items()}
        wall = time.perf_counter() - self._first_step_at if self._first_step_at is not None else 0.0
        view["share"] = (sum(view.values()) - view["no_work"]) / wall if wall > 0 else None
        return view


class QueueFull(RuntimeError):
    """Bounded-queue backpressure: the engine's wait queue is at `max_queue`.
    Callers shed load (HTTP 429 / retry-after) instead of growing host memory."""


class EngineClosed(RuntimeError):
    """The engine was `close()`d (or is mid-`drain()`) and takes no new work."""


#: Every value `RequestResult.finish_reason` can take.
FINISH_REASONS = ("eos", "length", "timeout", "error", "cancelled")

#: What the host was doing while the device had nothing enqueued (`step()`): the
#: first four inside a step, the last two between two steps.
STARVED_CAUSES = ("admit", "push", "dispatch", "drain", "client", "no_work")


#: The engine's instruments, one row each: the attribute it is kept as, its kind
#: (`MetricsRegistry.counter` / `.gauge` / `.histogram`), its name, its help and
#: — where it is one instrument a label value, kept as a dict by value — the
#: label and its values. This table is the source; docs/observability.md
#: describes it.
_INSTRUMENTS = (
    ("_m_submitted", "counter", "serving_requests_submitted_total",
     "requests accepted by submit()"),
    ("_m_inserts", "counter", "serving_inserts_total",
     "successful insert (prefill+admit) dispatches"),
    ("_m_chunks", "counter", "serving_chunks_total",
     "decode-chunk dispatches"),
    ("_m_decode_steps", "counter", "serving_decode_steps_total",
     "decode loop iterations (chunks * chunk_size)"),
    ("_m_finish", "counter", "serving_requests_finished_total",
     "finished requests by finish_reason", ("reason", FINISH_REASONS)),
    ("_m_queue_depth", "gauge", "serving_queue_depth",
     "requests waiting for a slot"),
    ("_m_queue_peak", "gauge", "serving_queue_peak",
     "queue-depth high-water mark (sized against max_queue)"),
    ("_m_slots_in_use", "gauge", "serving_slots_in_use",
     "slots occupied by in-flight requests"),
    ("_m_slot_utilization", "gauge", "serving_slot_utilization",
     "slots_in_use / num_slots"),
    ("_m_ttft", "histogram", "serving_ttft_seconds",
     "submit() -> the step() that carries the first token returns (host wall clock)"),
    ("_m_inter_token", "histogram", "serving_inter_token_seconds",
     "per-token gap between stream drains for an in-flight slot"),
    ("_m_chunk_latency", "histogram", "serving_chunk_seconds",
     "one decode chunk's cadence (`serve.decode_chunk.cadence_s`): since the previous readback "
     "returned, at most operand push to readback — one chunk and the inserts enqueued ahead of it, "
     "whether or not it was dispatched while its predecessor ran"),
    ("_m_device_waits", "counter", "serving_device_waits_total",
     "blocking device reads: one a step() that had a dispatched program to read back"),
    ("_m_dispatching_steps", "counter", "serving_dispatching_steps_total",
     "step() calls that had a program to read back: their own inserts and chunk, or the chunk the "
     "step before left in flight (not the step that only starts running ahead)"),
    ("_m_chunks_ahead", "counter", "serving_chunks_ahead_total",
     "decode chunks dispatched while their predecessor was still running"),
    ("_m_chunks_ahead_share", "gauge", "serving_chunks_ahead_share",
     "serving_chunks_ahead_total over serving_chunks_total: ~1 under a backlog, ~0 with an empty "
     "queue"),
    ("_m_lost_to_eos", "counter", "serving_slot_chunks_lost_to_eos_total",
     "chunks a slot sat inactive because its request stopped on an EOS the host had not yet seen "
     "when it dispatched the next chunk"),
    ("_m_starved", "counter", "serving_device_starved_seconds_total",
     "wall time the engine knew the device had nothing enqueued, by what the host was doing (a "
     "lower bound of the device's idle time: the readback's latency and a launch's tail are not in "
     "it); `no_work` is the offered load's idle, the rest the host's", ("cause", STARVED_CAUSES)),
    ("_m_pages_total", "gauge", "serving_pages_total",
     "usable KV pool pages (excludes the scratch page)"),
    ("_m_pages_in_use", "gauge", "serving_pages_in_use",
     "pool pages referenced by in-flight requests"),
    ("_m_kv_live_page_share", "gauge", "serving_kv_live_page_share",
     "live pages of the active slots over num_slots * pages_per_slot, as the last decode chunk was "
     "dispatched: the share of the window the paged XLA read visits"),
    ("_m_kv_bytes_per_token", "gauge", "serving_kv_bytes_per_token",
     "stored bytes one token holds in the page pool, all layers: keys and values of full heads, or "
     "a latent family's one row a layer"),
    ("_m_state_bytes_per_slot", "gauge", "serving_state_bytes_per_slot",
     "stored bytes a slot holds in by-slot leaves (recurrent and convolution state of layers with "
     "a recurrence), all layers, whatever the request's length (0 for a family that keeps pages "
     "alone)"),
    ("_m_state_share", "gauge", "serving_state_share_of_cache",
     "by-slot state of the active slots over that state plus their live pages' bytes, as the last "
     "decode chunk was dispatched"),
    ("_m_expert_load", "gauge", "serving_expert_load_max_over_mean",
     "the last decode chunk's tokens of the busiest routed expert over the mean expert's, layers "
     "averaged: what dropless routing pays under imbalance (0 for a family without routed experts)"),
    ("_m_residual_streams", "gauge", "serving_residual_streams",
     "streams of the served family's residual path (1: the plain residual; more: hyper-connections "
     "mix them around every sub-layer, `serve.insert.hc_rows`)"),
    ("_m_prefix_hits", "counter", "serving_prefix_cache_hits_total",
     "prompt pages served from the shared-prefix cache"),
    ("_m_prefix_misses", "counter", "serving_prefix_cache_misses_total",
     "full prompt pages that had to be prefilled (no cached prefix)"),
    ("_m_prefix_evictions", "counter", "serving_prefix_cache_evictions_total",
     "unreferenced cached prefix pages reclaimed by the allocator"),
    ("_m_prefill_saved", "counter", "prefill_tokens_saved_total",
     "prompt tokens whose prefill FLOPs the prefix cache skipped"),
)

#: Those of a speculative engine (host-scalar arithmetic over the chunk
#: readback). The headline derived number — accepted_tokens_per_step — is
#: (verify_steps + accepted) / verify_steps, surfaced in `stats`.
_SPECULATIVE_INSTRUMENTS = (
    ("_m_spec_steps", "counter", "serving_spec_verify_steps_total",
     "verify-block loop iterations with an active slot (each emits >= 1 token)"),
    ("_m_spec_drafted", "counter", "serving_spec_draft_tokens_total",
     "draft tokens proposed by the n-gram drafter (valid proposals only)"),
    ("_m_spec_accepted", "counter", "serving_spec_accepted_draft_tokens_total",
     "draft tokens confirmed by verification and emitted"),
    ("_m_spec_rejected", "counter", "serving_spec_rejected_draft_tokens_total",
     "draft tokens the verify step discarded"),
    ("_m_spec_hist", "histogram", "serving_spec_accepted_tokens",
     "tokens emitted per verify step (accepted drafts + 1 bonus)"),
)


@dataclass
class Request:
    """One serving request. `eos_token_id`, `max_new_tokens`, `temperature` and
    `repetition_penalty` are PER-REQUEST (traced operands of the shared decode
    program); `do_sample`/`top_k`/`top_p` are engine-level (they shape the
    compiled sampler, exactly as in `Generator._decode_fn`).

    `deadline_s` is a wall-clock budget in seconds measured from `submit()`;
    enforced at step boundaries, so a request can overrun by at most one chunk
    before finishing with `finish_reason="timeout"` (partial tokens kept).

    `tenant` and `priority` are ROUTER-level admission-control fields
    (`router.Router(tenant_queue_limit=...)`): the engine itself ignores them —
    a single engine is one queue — but carries them so requests survive
    `dataclasses.replace` round trips through the fleet layers."""

    request_id: int
    input_ids: Any  # [prompt_len] int sequence
    max_new_tokens: int = 32
    temperature: float = 1.0
    repetition_penalty: float = 1.0
    eos_token_id: Optional[int] = None
    arrival_time: float = 0.0  # caller-defined clock, echoed into the result
    deadline_s: Optional[float] = None  # wall-clock budget from submit; None = no deadline
    tenant: Optional[str] = None  # admission-control class (router fair share)
    priority: int = 0  # higher dispatches first across tenant queues (router)


@dataclass
class RequestResult:
    request_id: int
    tokens: List[int] = field(default_factory=list)
    arrival_time: float = 0.0
    # Host perf_counter when the first token was ON THE HOST (the one readback
    # of the step() that admitted it returned). The client gets it when that
    # step() returns, once the drain is done: the request span's `handed_back`
    # event carries the difference.
    first_token_time: Optional[float] = None
    # Host perf_counter when the call that dispatched its insert returned: the
    # request's time on the device (`handed_back.on_device_s`) starts here.
    insert_dispatched_time: Optional[float] = None
    finish_time: Optional[float] = None
    finished: bool = False
    finish_reason: Optional[str] = None  # one of FINISH_REASONS
    error: Optional[str] = None  # repr of the exception when finish_reason == "error"
    submit_time: Optional[float] = None  # host perf_counter when submit() accepted it


class ContinuousBatcher:
    """Slot-based in-flight batching over the fused decode loop.

    Typical driving loop::

        engine = ContinuousBatcher(model, num_slots=8, chunk_size=16)
        for r in requests:
            engine.submit(r)
        while engine.pending:
            for request_id, new_tokens in engine.step():
                stream(request_id, new_tokens)   # incremental drain

    `step()` = admit-into-free-slots, dispatch ONE decode chunk, read ONE chunk
    back and drain its packed stream buffer: its own with an empty queue, the
    one the step before left running while requests wait in the queue — so
    keep stepping while `pending`: a step may return with a chunk in flight
    whose tokens only the next step (or `close()`) hands out. The decode
    executable is compiled exactly once per (num_slots, chunk_size, sampler
    shape); admission compiles one insert executable per power-of-two prompt
    bucket and never touches the decode program (`trace_counts` proves it).

    What it is made of: the served family's facts, asked of the model's config
    once (`_family_facts`); the host's side of the slot state (`_SlotMirror`);
    the account of a starved device (`_StarvedAccount`); its instruments
    (`_INSTRUMENTS`); the mesh and the rules its weights are placed by
    (`parallel.sharding.resolve_serving_sharding`). The engine itself keeps the
    device values its programs donate, the queue and the slots' tenants, and
    `step()`'s control flow.
    """

    def __init__(
        self,
        model,
        num_slots: int = 4,
        max_length: Optional[int] = None,
        chunk_size: int = 8,
        do_sample: bool = False,
        top_k: int = 0,
        top_p: float = 1.0,
        use_repetition_penalty: bool = False,
        rng=None,
        max_queue: Optional[int] = None,
        trace_guard=None,
        registry: Optional[MetricsRegistry] = None,
        tracer=None,
        paged: bool = True,  # accepts only True; kept for the benchmark's pins (ROADMAP B0)
        page_size: int = 16,
        num_pages: Optional[int] = None,
        prefix_cache: bool = True,
        speculative: bool = False,
        draft_tokens: int = DEFAULT_DRAFT_TOKENS,
        draft_ngram: int = DEFAULT_DRAFT_NGRAM,
        attention_impl: Optional[str] = None,
        weight_dtype: str = "bf16",
        kv_cache_dtype: str = "bf16",
        tp: int = 1,
        tp_devices=None,
        tp_group: int = 0,
        sharding_rules: Any = None,
        sharding_refine_top_k: int = 0,
    ):
        if paged is not True:
            raise ValueError(
                f"paged={paged!r}: the contiguous per-slot KV layout is gone — the "
                "page pool is the engine's only KV store; drop the argument"
            )
        self.paged = True
        # Static config, both — dtypes never retrace: int8 weights are quantized ONCE at
        # load/swap time (the `params` setter); a quantized pool adds scale operands.
        self.weight_dtype = str(weight_dtype)
        if self.weight_dtype not in WEIGHT_DTYPES:
            raise ValueError(
                f"unknown weight_dtype {weight_dtype!r}; expected one of {WEIGHT_DTYPES}"
            )
        self.kv_cache_dtype = str(kv_cache_dtype)
        if self.kv_cache_dtype not in KV_CACHE_DTYPES:
            raise ValueError(
                f"unknown kv_cache_dtype {kv_cache_dtype!r}; expected one of {KV_CACHE_DTYPES}"
            )
        self.tp = int(tp)
        family = self._family = _family_facts(model, self.kv_cache_dtype, self.tp)
        self.base_config = family.config

        self.num_slots = int(num_slots)
        self.max_length = int(max_length or family.max_positions)
        self.chunk_size = int(chunk_size)
        self.do_sample = do_sample
        self.top_k = top_k
        self.top_p = top_p
        self.use_repetition_penalty = use_repetition_penalty
        if self.num_slots < 1 or self.chunk_size < 1:
            raise ValueError("num_slots and chunk_size must be >= 1")
        self.speculative = bool(speculative)
        self.draft_tokens = int(draft_tokens)
        self.draft_ngram = int(draft_ngram)
        # Why this engine never runs a chunk ahead (step()), or None where it
        # does under a backlog; `stats["run_ahead"]` says it.
        self.run_ahead_disabled_reason: Optional[str] = None
        if self.speculative:
            self.run_ahead_disabled_reason = (
                "speculative=True: the host cannot predict a verified block's length, and "
                "pushes the drafter's context (`_history`), which it rebuilds from the "
                "drained stream, with every chunk"
            )
            if self.draft_tokens < 1 or self.draft_ngram < 1:
                raise ValueError("speculative decode needs draft_tokens >= 1 and draft_ngram >= 1")
            if do_sample:
                raise ValueError(
                    "speculative decode is greedy-only: draft verification accepts "
                    "argmax matches, which is not distribution-preserving under "
                    "sampling — pass do_sample=False or speculative=False"
                )
            if use_repetition_penalty:
                raise ValueError(
                    "speculative decode does not compose with use_repetition_penalty "
                    "(the presence update is order-dependent across a verified "
                    "block); disable one of the two"
                )
        self.page_size = int(page_size)
        if self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.pages_per_slot = -(-self.max_length // self.page_size)
        # A slot's capacity in whole pages; columns past max_length stay masked
        # (exact zeros under the f32 softmax): token-identical to a dense row.
        self._padded_length = self.pages_per_slot * self.page_size
        # Default pool: the worst case (every slot at max_length) plus the scratch
        # page, so admission never waits on pages. Size it DOWN to save HBM.
        self.num_pages = (
            int(num_pages) if num_pages is not None
            else self.num_slots * self.pages_per_slot + 1
        )
        # A suffix-only insert cannot seed presence from the WHOLE prompt:
        # repetition-penalty engines run without prefix reuse.
        self.use_prefix_cache = bool(prefix_cache)
        self.prefix_cache_disabled_reason: Optional[str] = None
        if not prefix_cache:
            self.prefix_cache_disabled_reason = "prefix_cache=False"
        elif use_repetition_penalty:
            self._disable_prefix_cache(
                "use_repetition_penalty needs whole-prompt presence seeding, which "
                "shared-prefix inserts cannot provide"
            )

        # tp=1 on the first device group is byte-for-byte the single-device
        # engine (mesh is None); `sharding_plan` is the planner's, under "auto".
        params_tree = model.params if "params" in model.params else {"params": model.params}
        self.sharding_refine_top_k = int(sharding_refine_top_k)
        self.mesh, self._tp_rules, self.sharding_plan, self.sharding_mode = resolve_serving_sharding(
            model, params_tree, tp=self.tp, tp_devices=tp_devices, tp_group=tp_group,
            sharding_rules=sharding_rules, sharding_refine_top_k=self.sharding_refine_top_k,
            kv_heads=family.kv_heads, num_slots=self.num_slots, page_size=self.page_size,
            num_pages=self.num_pages, kv_cache_dtype=self.kv_cache_dtype, weight_dtype=self.weight_dtype,
        )
        self._param_shardings = self._cache_shardings = None
        self.params = params_tree

        # Counted at TRACE time: compiles ("decode compiled once across mixed admissions").
        self.trace_counts: Dict[str, int] = {"insert": 0, "decode_chunk": 0}
        #: `ops.attention.LAST_DISPATCH` as the decode chunk was traced (`serve.decode_chunk.read_impl`).
        self.read_impl: Optional[str] = None
        self._sample_config = GenerationConfig(do_sample=do_sample, top_k=top_k, top_p=top_p)
        self._insert_fns: Dict[int, Any] = {}
        self._build_programs(model, attention_impl)
        self._cache = self._init_cache()
        self._kv_bytes_per_token, self.kv_row_values = _pool_token_sizes(self._cache)
        counts = _expert_token_counts(self._cache)
        self._expert_layers = 0 if counts is None else int(counts.shape[0])
        # Device values that programs donate. `_first_token`: where an insert leaves
        # its sampled token, by slot — the next chunk starts from it and hands it back
        # (`_Flight.read["first"]`). `_carry`: `(token, pos, active, rem)` as the last
        # dispatched chunk returned them — maybe not computed yet.
        self._rng = self._carried(rng if rng is not None else jax.random.key(0))
        self._presence = self._new_presence()
        self._first_token = self._new_first_token()
        self._carry = self._new_carry()
        self._slots = _SlotMirror(
            self.num_slots, self.pages_per_slot, self.max_length if self.speculative else None
        )
        self._slot_pages: List[List[int]] = [[] for _ in range(self.num_slots)]
        self._slot_request: List[Optional[RequestResult]] = [None] * self.num_slots
        self._queue: deque = deque()
        self.results: Dict[int, RequestResult] = {}
        self.max_queue = None if max_queue is None else int(max_queue)
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None for unbounded)")
        self._deadlines: Dict[int, float] = {}  # request_id -> absolute perf_counter deadline
        self._closed = False
        self._draining = False
        # Optional analysis.TraceGuard (assignable later too): fault isolation
        # swallows a step's exceptions, so guarded transfer violations are
        # `observe()`d first — the analysis ledger sees them while serving goes on.
        self.trace_guard = trace_guard
        # Every health counter lives in a MetricsRegistry (shareable, exportable);
        # `stats` is a read-only VIEW over it. Host-scalar arithmetic, no device sync.
        self.metrics = registry if registry is not None else MetricsRegistry()
        self._register_instruments(_INSTRUMENTS)
        if self.speculative:
            self._register_instruments(
                _SPECULATIVE_INSTRUMENTS, buckets=[float(i) for i in range(1, self.draft_tokens + 2)]
            )
        self._m_kv_bytes_per_token.set(self._kv_bytes_per_token)
        self._m_state_bytes_per_slot.set(self._state_bytes_per_slot)
        self._m_residual_streams.set(family.residual_streams)
        self._starved = _StarvedAccount(self._m_starved)
        self.pool = PagePool(
            self.num_pages, self.page_size,
            on_evict=self._m_prefix_evictions.inc,
            kv_cache_dtype=self.kv_cache_dtype,
        )
        self._m_pages_total.set(self.pool.pages_total)

        # One `serve.request` span a request, submit() to its finish_reason, and one
        # `serve.step` tree a step(): host-clock arithmetic (TPU112 lints the annotations).
        self.tracer = tracer if tracer is not None else default_tracer()
        self._request_spans: Dict[int, Any] = {}
        # Slots admitted since the last chunk's dispatch, in order, whose first tokens
        # are still on the device: the next `_Flight` takes the list (a step with no
        # chunk reads the buffer itself). With when each was admitted and how many
        # admissions of its step came before it, for `handed_back`.
        self._fresh: List[Tuple[int, float, int]] = []
        # Decode chunks dispatched and not yet read back, oldest first: two at
        # most inside a step() that runs ahead, one at most when it returns.
        self._flights: deque = deque()
        # When a request's tokens last reached the host, by request id.
        self._last_event: Dict[int, float] = {}
        # Requests whose first token reached the host in the step() now running, with
        # their `_fresh` entry and whether a chunk brought it (_hand_back).
        self._first_tokens: List[Tuple[RequestResult, float, int, bool]] = []

    # ------------------------------------------------------------------ programs

    def _register_instruments(self, table, buckets=None):
        """Create (or find, in a shared registry) every instrument of `table`
        and keep it as the attribute the table names: one instrument, or one a
        label value in a dict. `buckets` are the table's histograms'."""
        for attr, kind, name, help_text, *by in table:
            make = getattr(self.metrics, kind)
            extra = {} if buckets is None or kind != "histogram" else {"buckets": buckets}
            if by:
                label, values = by[0]
                made = {v: make(name, help=help_text, labels={label: v}, **extra) for v in values}
            else:
                made = make(name, help=help_text, **extra)
            setattr(self, attr, made)

    def _build_programs(self, model, attention_impl: Optional[str]):
        """The engine's modules and their programs' raw functions, from the
        model's own module class under two configs: prefill rides the ORDINARY
        decode-cache path on a batch-1 cache (shared scalar cache_index);
        decode steps ride the per-row slot cache. Same logical cache capacity,
        so the prefilled rows line up for the scatter into pool pages. What a
        family's cache tree shows and the engine has not built is refused
        here, by name."""
        family, base = self._family, self.base_config
        resolve = self._resolve = _params_resolver(model)
        cache_len = self._padded_length
        fields = family.fields(weight_dtype=self.weight_dtype) if self.weight_dtype != "bf16" else {}
        prefill_module = type(model.module)(dataclasses.replace(base, decode_cache_length=cache_len, **fields))
        self._cached_prefill_raw = make_cached_prefill_program(prefill_module, resolve)
        # The dense batch-1 cache STRUCTURE the insert materializes by
        # gathering pool pages (zero compute/compile: eval_shape only). The
        # weight_autocast wrap matters even for eval_shape: int8 engines
        # hold quantized kernel entries the raw Dense can't consume.
        dummy = jnp.zeros((1, 1), jnp.int32)
        with weight_autocast(self.weight_dtype):
            self._dense_cache_struct = jax.eval_shape(
                lambda p: prefill_module.apply(resolve(p), dummy, None, dummy, mutable=["cache"])[1]["cache"],
                self.params,
            )
        # What a family keeps a request is read off its cache tree: page
        # leaves (`cached_key` / `cached_value` / `cached_latent`) and, for a
        # layer with a recurrence, BY-SLOT leaves (`recurrent_state`,
        # `conv_state`: utils/operations) — a fixed state a slot that the
        # insert writes whole and the decode chunk carries and updates.
        self._state_bytes_per_slot = tree_slot_state_nbytes(self._dense_cache_struct)
        if self._state_bytes_per_slot:
            if self.speculative:
                raise ValueError(
                    f"speculative=True with {family.name}: its cache holds recurrent state by slot, "
                    "which a verify block advances past drafts it may reject — speculative "
                    "verify with a state roll-back (speculative.py) is not built; use "
                    "speculative=False"
                )
            if self.tp > 1:
                raise ValueError(
                    f"tp={self.tp} with {family.name}: its cache holds recurrent state by slot, and "
                    "the cache shardings (parallel/sharding.derive_tp_cache_shardings) place "
                    "page pools by KV head only — a tensor-parallel layout for by-slot state is "
                    "not built; use tp=1"
                )
            if self.use_prefix_cache:
                self._disable_prefix_cache(
                    f"{family.name} keeps recurrent state by slot, and a shared prefix hands back "
                    "pages of tokens but not the state at that boundary (state snapshots are "
                    "not built)"
                )
        if self.mesh is not None:
            # The slot-decode modules carry the submesh so the Pallas page-walk
            # kernels can shard_map over the KV-head grid; prefill stays
            # mesh-free in config (its XLA paths partition off the sharded
            # operands alone).
            fields.update(family.fields(decode_tp_mesh=self.mesh))
        if self.kv_cache_dtype != "bf16":
            fields.update(family.fields(decode_kv_cache_dtype=self.kv_cache_dtype))
        # What the paged read is sized from (`_live_page_counts`) and chosen by:
        # the prefill cache is K as the model computes it — [1, length, KV
        # heads, head_dim] in the compute dtype ([1, length, row] for a latent
        # family), the very operands the read takes its block and its run from.
        # "xla" is the loop over blocks of live pages (the parity oracle),
        # "pallas_paged" the ops/paged_attention page-walk kernel, None the
        # engine's choice between them (`ops.attention.slot_attention_impl`).
        # Either way the ONE decode executable and the traced-operand page
        # tables are unchanged — the impl only swaps the attention read inside
        # the compiled program.
        key = _cached_key_leaf(self._dense_cache_struct)
        kv_heads = key.shape[2] if key.ndim == 4 else 1  # latent rows [1, length, row]: no head axis
        self.attention_impl = attention_ops.slot_attention_impl(
            None if attention_impl is None else str(attention_impl), platform=self._home_device.platform,
            latent=family.latent, tp=self.tp, slots=self.num_slots,
            pages_per_slot=self.pages_per_slot, page_size=self.page_size,
            block=self.draft_tokens + 1 if self.speculative else 1,
            heads=family.heads // self.tp, kv_heads=max(1, kv_heads // self.tp),
            head_dim=key.shape[-1], itemsize=np.dtype(key.dtype).itemsize, kv_cache_dtype=self.kv_cache_dtype,
        )
        self._read_shape = (self.pages_per_slot, self.page_size, kv_heads, key.shape[-1],
                            np.dtype(key.dtype).itemsize, family.heads // kv_heads,
                            self.attention_impl, family.latent)
        self._step_module = type(model.module)(dataclasses.replace(
            base, decode_cache_length=cache_len, decode_slot_cache=True,
            decode_page_size=self.page_size, decode_num_pages=self.num_pages,
            decode_attention_impl=self.attention_impl, **fields,
        ))
        _, self._step_raw, self._verify_raw = make_causal_programs(
            self._step_module, resolve, step_mask_operand=True, verify_block=True
        )
        self._chunk_fn = self._build_spec_chunk() if self.speculative else self._build_chunk()

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, value):
        """The weight-load seam: construction, the router's rolling
        `swap_weights`, the ReplicaSet rebuild path, and the worker's
        `set_params` op all assign here. int8 engines quantize per-output-
        channel scales ONCE per assignment (`quantize_params_int8` —
        idempotent, so an already-quantized tree passes through), which is
        exactly the "scales computed at weight-load/swap time" contract: the
        compiled programs only ever see int8 kernels + scale operands.

        Tensor-parallel engines RE-SHARD here too: the (possibly quantized)
        tree is `device_put` onto the submesh with the model family's
        Megatron rules (`derive_tp_param_shardings` — quantized {"q",
        "scale"} entries ride their kernel's rule), so a rolling
        `swap_weights` lands already-sharded weights with zero recompiles
        and an already-placed tree passes through as the same buffers.

        HOST leaves (a checkpoint's numpy arrays) are placed here, once: left
        as they come, every dispatch would transfer them again. An int8
        engine quantizes them a leaf at a time on the way, so it never holds
        the floating tree on the device — what lets int8 weights serve a model
        whose bf16 weights would not fit beside them."""
        if self.weight_dtype == "int8":
            from .ops.quantization import quantize_params_int8

            value = quantize_params_int8(value)
        if self.mesh is not None:
            from .parallel.sharding import derive_tp_param_shardings

            self._param_shardings = derive_tp_param_shardings(
                value, self.mesh, self._tp_rules
            )
        # One chip: no shardings, the default device. Placed leaves pass through as the same buffers.
        self._params = jax.device_put(value, self._param_shardings)

    def _disable_prefix_cache(self, reason: str):
        """Serve without prefix reuse, and say why once (`stats["prefix_cache"]`
        keeps the reason)."""
        self.use_prefix_cache = False
        self.prefix_cache_disabled_reason = reason
        logger.info("prefix cache disabled: %s", reason)

    def _carried(self, value):
        """Device state that threads through every dispatch (rng, presence,
        the first-token buffer), committed REPLICATED on a tensor-parallel
        engine's submesh up front: an uncommitted first-call signature
        followed by a committed second-call one would recompile the one
        decode executable the engine promises never to."""
        if self.mesh is None:
            return value
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(value, NamedSharding(self.mesh, PartitionSpec()))

    def _new_presence(self):
        """Zeroed `bool[num_slots, vocab]` seen-token rows of a penalty engine."""
        if not self.use_repetition_penalty:
            return None
        return self._carried(jnp.zeros((self.num_slots, self._family.vocab_size), bool))

    def _new_first_token(self):
        """The zeroed `int32[num_slots]` first-token buffer."""
        return self._carried(jnp.zeros((self.num_slots,), jnp.int32))

    def _new_carry(self):
        """`(token, pos, active, rem)` of an engine with every slot idle."""
        idle = jnp.zeros((self.num_slots,), jnp.int32)
        return tuple(self._carried(x) for x in (idle, idle, idle.astype(bool), idle))

    def _init_cache(self):
        """Create the slot cache — the [num_pages, page_size] pool (quantized
        dtypes add the per-page-per-head scale pools): `eval_shape` the
        slot-mode module's cache variables (zero compute, zero compile — no
        throwaway executable at engine construction) and materialize them as
        zeros. Correct because every slot's pages are overwritten by insert
        before they're ever attended."""
        S = self.num_slots
        module, resolve = self._step_module, self._resolve
        dummy = jnp.zeros((S, 1), jnp.int32)
        pos = jnp.zeros((S, 1), jnp.int32)
        mask = jnp.zeros((S, self.pages_per_slot), jnp.int32)
        with weight_autocast(self.weight_dtype):
            shapes = jax.eval_shape(
                lambda p: module.apply(resolve(p), dummy, mask, pos, mutable=["cache"])[1]["cache"],
                self.params,
            )
        cache = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        if self.mesh is not None:
            # Place the pools SHARDED by KV head over the submesh (scale
            # pools by head, scalars replicated) — blast-radius rebuilds come
            # through here too, so recovery reconstructs the sharded layout.
            from .parallel.sharding import derive_tp_cache_shardings

            self._cache_shardings = derive_tp_cache_shardings(cache, self.mesh)
            cache = jax.device_put(cache, self._cache_shardings)
        return cache

    @staticmethod
    def plan_admission_bucket(
        p: int, matched_pages: int, page_size: int, padded_length: int
    ) -> Tuple[int, int]:
        """Pure admission planner: (insert bucket, matched pages to KEEP) for a
        `p`-token prompt with `matched_pages` prefix-cache hits.

        The bucket set this can return is CLOSED — powers of two (prefix-hit
        suffixes floored at the page-size bucket, so a deepening cache never
        mints ever-smaller buckets) plus the single capped value
        `padded_length` (a full-window prompt with no prefix hit). A pow2
        suffix bucket that would overflow the cache window (`matched_len +
        bucket > padded_length`) DROPS trailing matched pages until it fits
        instead of shrinking the bucket to a matched_len-dependent remainder:
        an open set of remainder-sized buckets is exactly what used to compile
        a fresh insert executable on the first deep prefix hit of a timed run.
        `warm_inserts` precompiles the whole closed set."""
        floor_bucket = _bucket_for(page_size)
        matched_len = matched_pages * page_size
        while matched_pages and (
            matched_len + max(_bucket_for(p - matched_len), floor_bucket) > padded_length
        ):
            matched_pages -= 1
            matched_len -= page_size
        bucket = _bucket_for(p - matched_len)
        if matched_pages:
            bucket = max(bucket, floor_bucket)
        # Only binds when matched_pages == 0: the single fixed top bucket.
        bucket = min(bucket, padded_length - matched_len)
        return bucket, matched_pages

    def insert_bucket_ladder(self) -> List[int]:
        """Every insert bucket any admission of this engine can mint: the pow2
        ladder below the cache window plus the capped top value. Closed by
        `plan_admission_bucket`."""
        limit = self._padded_length
        ladder = []
        b = 1
        while b < limit:
            ladder.append(b)
            b <<= 1
        ladder.append(limit)
        return ladder

    def warm_inserts(self) -> List[int]:
        """Precompile the full insert-bucket ladder so NO admission — whatever
        prompt length or prefix-cache depth it arrives with — compiles at
        serving time. Each warm call donates a THROWAWAY zero cache (never the
        engine's), so engine state is untouched. Returns the buckets warmed.

        Cost: one small compile per ladder rung (log2 of the cache window), a
        few seconds at init; the payoff is a mechanical 0-recompile guarantee
        across the whole admission space instead of 'whatever the warmup
        traffic happened to mint'."""
        import jax

        warmed = []
        for bucket in self.insert_bucket_ladder():
            fn = self._insert_fn(bucket)
            dummy_cache = jax.tree_util.tree_map(jnp.zeros_like, self._cache)
            if self._cache_shardings is not None:
                # Warm with the REAL sharded signature: an unsharded dummy
                # would compile a throwaway executable and the first live
                # admission would still pay the sharded compile.
                dummy_cache = jax.device_put(dummy_cache, self._cache_shardings)
            dummy_presence = (
                jax.tree_util.tree_map(jnp.zeros_like, self._presence)
                if self._presence is not None
                else None
            )
            ids = jnp.zeros((1, bucket), jnp.int32)
            fn(
                self.params, dummy_cache, dummy_presence, ids,
                _operand(1, np.int32), _operand(0, np.int32), _operand(0, np.int32),
                jnp.asarray(np.zeros((self.pages_per_slot,), np.int32)),
                _operand(0, np.int32), _operand(1.0, np.float32),
                _operand(1.0, np.float32), self._rng, self._new_first_token(),
            )
            warmed.append(bucket)
        return warmed

    def _insert_fn(self, bucket: int):
        """One compiled insert per power-of-two SUFFIX bucket (the unmatched
        tail after prefix-cache hits). The real length, the slot index, page
        table row, matched prefix length, temperature/penalty and the rng all
        ride as traced operands — re-admission never recompiles anything.

        Gather the slot's (possibly shared-prefix) pages
        into a batch-1 dense cache positioned at `matched_len`, prefill ONLY the
        unmatched suffix through it, scatter the result back into pool pages —
        with every already-matched table entry redirected to the scratch page,
        so a shared read-only prefix page is never rewritten — and sample the
        first token from the suffix's real last logits, the ONE row of the
        bucket the final norm and the head are computed for (`head_rows` 1 on
        the `serve.insert` span). A full prefix hit still
        recomputes the prompt's final token (matching is capped below the whole
        prompt), so first-token logits always exist. The token is written into
        the donated `first_token` buffer at `slot` and stays on the device:
        nothing here is read back (step())."""
        fn = self._insert_fns.get(bucket)
        if fn is not None:
            return fn
        cached_prefill = self._cached_prefill_raw
        dense_struct = self._dense_cache_struct
        use_pen = self.use_repetition_penalty
        config = self._sample_config
        V = self._family.vocab_size
        P = self.pages_per_slot
        mesh = self.mesh
        recurrent = bool(self._state_bytes_per_slot)

        def insert(
            params, pool_cache, presence, suffix_ids, real_len, matched_len,
            matched_pages, page_row, slot, temperature, penalty, rng, first_token,
        ):
            self.trace_counts["insert"] += 1
            with jax.named_scope("kv_read"):
                dense = tree_gather_pages(pool_cache, dense_struct, page_row, matched_len)
            positions = matched_len + jnp.broadcast_to(jnp.arange(bucket)[None, :], (1, bucket))
            # A recurrence runs over the whole bucket: it is told which
            # positions are real, so that the padding leaves the slot's state
            # as the last real token left it. Attention needs no such mark.
            real = (jnp.arange(bucket) < real_len)[None, :] if recurrent else None
            # The head is computed for the one row that is sampled: the REAL
            # last suffix token (bucket pads sit above it and, being causal,
            # never influenced it).
            logits, dense = cached_prefill(
                params, dense, suffix_ids, positions, real, jnp.reshape(real_len - 1, (1,))
            )
            last = logits[:, 0, :]
            # Zero rows past the prompt before the write-back: the gather
            # resurrects a recycled page's stale content (never attended, but
            # a QUANTIZED scatter folds it into the boundary page's amax
            # scale, coarsening the real rows; tree_zero_cache_tail).
            with jax.named_scope("kv_write"):
                dense = tree_zero_cache_tail(dense, matched_len + real_len)
                write_row = jnp.where(
                    jnp.arange(P) < matched_pages, jnp.int32(SCRATCH_PAGE), page_row
                )
                pool_cache = constrain_tp_cache(
                    tree_scatter_pages(pool_cache, dense, write_row, slot), mesh
                )
            row = None
            with jax.named_scope("sample"):
                if use_pen:
                    # Penalty engines run with the prefix cache OFF (matched_len is
                    # always 0), so the "suffix" here is the whole prompt and the
                    # presence row is seeded from all of it.
                    valid = jnp.arange(bucket) < real_len
                    row = jnp.zeros((V,), bool).at[suffix_ids[0]].max(valid)
                    last = _apply_repetition_penalty(last, row[None, :], penalty)
                token, rng = _sample(last, config, rng, temperature)
                if use_pen:
                    row = row.at[token[0]].set(True)
                    presence = jax.lax.dynamic_update_slice(
                        presence, row[None, :], (jnp.asarray(slot, jnp.int32), jnp.int32(0))
                    )
            first_token = first_token.at[slot].set(token[0])
            return first_token, pool_cache, presence, rng

        donate = (1, 2, 12) if use_pen else (1, 12)
        fn = jax.jit(insert, donate_argnums=donate)
        self._insert_fns[bucket] = fn
        return fn

    def _build_chunk(self):
        """THE decode executable: `chunk_size` scan steps over all slots, per-slot
        operands, packed (slot, token) stream output. Compiled exactly once."""
        S, L, chunk = self.num_slots, self.max_length, self.chunk_size
        step_inner = self._step_raw
        use_pen = self.use_repetition_penalty
        config = self._sample_config
        mesh = self.mesh

        def decode_chunk(params, cache, presence, token, pos, active, rem, eos_ids, temperature, penalty, page_table, rng, first_token, update):
            self.trace_counts["decode_chunk"] += 1
            cache = _zero_expert_token_counts(cache)
            token, pos, active, rem, fresh = _merge_slot_updates(token, pos, active, rem, eos_ids, first_token, update)
            first = jnp.where(fresh, token, 0)

            def body(carry, _):
                cache, presence, token, pos, active, rem, rng = carry
                # The page table is loop-invariant: admission reserves a
                # request's whole worst-case footprint up front, so no page
                # boundary crossed mid-chunk ever needs a fresh page.
                logits, cache = step_inner(params, cache, token, pos, page_table)
                with jax.named_scope("sample"):
                    if use_pen:
                        logits = _apply_repetition_penalty(logits, presence, penalty[:, None])
                    nxt, rng = _sample(logits, config, rng, temperature[:, None])
                    nxt = jnp.where(active, nxt, jnp.int32(0))
                    if use_pen:
                        presence = presence.at[jnp.arange(S), nxt].max(active)
                emitted = active  # every active slot streams exactly one token
                new_rem = jnp.where(active, rem - 1, rem)
                hit_eos = (eos_ids >= 0) & (nxt == eos_ids)
                new_active = active & ~hit_eos & (new_rem > 0)
                new_pos = jnp.where(active, pos + 1, pos)
                return (cache, presence, nxt, new_pos, new_active, new_rem, rng), (nxt, emitted)

            carry = (cache, presence, token, pos, active, rem, rng)
            carry, (toks, valids) = jax.lax.scan(body, carry, None, length=chunk)
            self.read_impl = attention_ops.LAST_DISPATCH  # trace time: the read the program holds
            cache, presence, token, pos, active, rem, rng = carry
            cache = constrain_tp_cache(cache, mesh)
            # Pack the [chunk, S] stream TIME-major so each slot's tokens stay in
            # order, valid entries first: composite sort key = invalid*N + time.
            n = chunk * S
            with jax.named_scope("pack_stream"):
                flat_tok = toks.reshape(n)
                flat_valid = valids.reshape(n)
                flat_slot = jnp.broadcast_to(jnp.arange(S)[None, :], (chunk, S)).reshape(n)
                order = jnp.argsort(jnp.where(flat_valid, 0, n) + jnp.arange(n))
                packed = jnp.stack(
                    [
                        jnp.where(flat_valid[order], flat_slot[order], -1),
                        jnp.where(flat_valid[order], flat_tok[order], -1),
                    ],
                    axis=-1,
                ).astype(jnp.int32)
            # What the next chunk starts from, and what the host reads back:
            # `first` is the fresh slots' tokens as THIS chunk found them in
            # the buffer — the next step's inserts are donated the buffer
            # before this chunk is read.
            read = {"active": active, "packed": packed, "count": flat_valid.sum(), "first": first}
            # A family with routed experts: the chunk's tokens an expert a
            # layer ride the same readback.
            counts = _expert_token_counts(cache)
            if counts is not None:
                read["expert_tokens"] = counts
            return (cache, presence, token, pos, active, rem, rng), read

        donate = (1, 2) if use_pen else (1,)
        return jax.jit(decode_chunk, donate_argnums=donate)

    def _build_spec_chunk(self):
        """THE decode executable, speculative flavor: each of the `chunk_size`
        scan iterations drafts `draft_tokens` continuations per slot with the
        on-device n-gram drafter, scores the pending token plus every draft in
        ONE (draft_tokens+1)-position verify dispatch
        (`make_causal_programs(..., verify_block=True)` through
        `ops.attention.slot_cache_attention`'s multi-token path), and emits the
        longest greedily-confirmed draft prefix plus one bonus token — up to
        draft_tokens+1 tokens per slot for one dispatch's latency, 1..k+1
        always, so it can only match or beat the plain chunk. Accept/reject,
        EOS-in-block truncation, budget capping, and the history update all
        run as traced ops: steady state stays this one executable, zero
        recompiles, zero host reads.

        Rejected draft K/V needs no rollback: the slot's position simply
        doesn't advance past the accepted prefix, the per-query `cols <= pos`
        mask keeps stale rows invisible, and the next verify block overwrites
        them before anything can attend them. (Rejected writes land through
        the slot's OWN page table — the draft window is part of the admission
        reservation — or fall through to the scratch page past the table's
        last real entry.)

        An EOS inside the verified block terminates the request THERE: the
        block's tail is discarded (not emitted, not counted against the
        budget), pos stops at the EOS, and the drained result ends with the
        EOS token — exactly the one-token path's `_trim_at_eos` semantics.

        Beyond the plain chunk's outputs it returns two [chunk, S] int32
        matrices: tokens emitted per (iteration, slot) and valid drafts
        proposed — the host folds them into the spec counters/histogram."""
        S, chunk = self.num_slots, self.chunk_size
        H = self.max_length
        verify_inner = self._verify_raw
        k_draft, m_gram = self.draft_tokens, self.draft_ngram
        mesh = self.mesh

        def decode_chunk(params, cache, presence, token, pos, active, rem, eos_ids, temperature, penalty, page_table, rng, first_token, update, history):
            self.trace_counts["decode_chunk"] += 1
            cache = _zero_expert_token_counts(cache)
            token, pos, active, rem, fresh = _merge_slot_updates(token, pos, active, rem, eos_ids, first_token, update)
            first = jnp.where(fresh, token, 0)
            js = jnp.arange(k_draft + 1, dtype=jnp.int32)
            rows = jnp.arange(S)
            # A fresh slot's pending token belongs at history[pos]: the host
            # seeded the prompt and could not know the token.
            at_pos = jnp.clip(pos, 0, H - 1)
            history = history.at[rows, at_pos].set(jnp.where(fresh, token, history[rows, at_pos]))

            def body(carry, _):
                cache, token, pos, active, rem, history = carry
                hist_len = pos + 1  # the pending token sits at history[pos]
                drafts, valid_len = propose_ngram_drafts(history, hist_len, k_draft, m_gram)
                block = jnp.concatenate([token[:, None], drafts], axis=1)  # [S, k+1]
                positions = pos[:, None] + js[None, :]
                logits, cache = verify_inner(params, cache, block, positions, page_table)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, k+1]
                accept = greedy_accept_length(drafts, greedy[:, :k_draft], valid_len)
                # Budget cap: emit at most `rem` tokens (accept + 1 bonus).
                accept = jnp.clip(accept, 0, rem - 1)
                emit = active[:, None] & (js[None, :] <= accept[:, None])
                # EOS inside the block ends the request there: keep the EOS,
                # discard the tail.
                eos_hit = emit & (eos_ids[:, None] >= 0) & (greedy == eos_ids[:, None])
                first_eos = jnp.min(jnp.where(eos_hit, js[None, :], k_draft + 1), axis=1)
                emit &= js[None, :] <= first_eos[:, None]
                n_emit = emit.sum(axis=1).astype(jnp.int32)  # [S], 0 for inactive
                new_pos = pos + n_emit
                new_rem = rem - n_emit
                finished_eos = first_eos <= accept
                new_active = active & ~finished_eos & (new_rem > 0)
                last = jnp.take_along_axis(greedy, jnp.clip(n_emit - 1, 0, k_draft)[:, None], axis=1)[:, 0]
                new_token = jnp.where(active, last, token)
                # Append the emitted tokens to the history (the next iteration
                # drafts over them). Emitted index j lands at history[pos+1+j];
                # masked positions write back their own gathered values.
                idx = jnp.clip(pos[:, None] + 1 + js[None, :], 0, H - 1)
                old = jnp.take_along_axis(history, idx, axis=1)
                history = history.at[rows[:, None], idx].set(jnp.where(emit, greedy, old))
                out_tok = jnp.where(emit, greedy, jnp.int32(-1))
                proposed = jnp.where(active, valid_len, 0).astype(jnp.int32)
                carry = (cache, new_token, new_pos, new_active, new_rem, history)
                return carry, (out_tok, emit, n_emit, proposed)

            carry = (cache, token, pos, active, rem, history)
            carry, (toks, valids, emitted_mat, proposed_mat) = jax.lax.scan(body, carry, None, length=chunk)
            self.read_impl = attention_ops.LAST_DISPATCH  # trace time: the read the program holds
            cache, token, pos, active, rem, history = carry
            cache = constrain_tp_cache(cache, mesh)
            # Pack [chunk, S, k+1] -> (slot, token) stream, time-major per slot
            # (row-major flatten keeps (iteration, block-index) order within a
            # slot), valid entries first — same composite key as the plain chunk.
            n = chunk * S * (k_draft + 1)
            with jax.named_scope("pack_stream"):
                flat_tok = toks.reshape(n)
                flat_valid = valids.reshape(n)
                flat_slot = jnp.broadcast_to(rows[None, :, None], (chunk, S, k_draft + 1)).reshape(n)
                order = jnp.argsort(jnp.where(flat_valid, 0, n) + jnp.arange(n))
                packed = jnp.stack(
                    [
                        jnp.where(flat_valid[order], flat_slot[order], -1),
                        jnp.where(flat_valid[order], flat_tok[order], -1),
                    ],
                    axis=-1,
                ).astype(jnp.int32)
            # The host cannot predict a verified block's length: it adopts
            # `pos` and `rem` from the readback (these engines never run ahead).
            read = {
                "active": active, "packed": packed, "count": flat_valid.sum(), "first": first,
                "pos": pos, "rem": rem, "spec_emitted": emitted_mat, "spec_proposed": proposed_mat,
            }
            counts = _expert_token_counts(cache)
            if counts is not None:
                read["expert_tokens"] = counts
            return (cache, presence, token, pos, active, rem, rng), read

        return jax.jit(decode_chunk, donate_argnums=(1,))

    # ---------------------------------------------------------------- host plane

    @property
    def pending(self) -> bool:
        """Anything queued, holding a slot, or dispatched and not read back: a
        chunk left in flight by a step that ran ahead is pending work, whose
        tokens only another `step()` (or `close()`) hands out."""
        return bool(self._queue) or bool(self._flights) or any(
            r is not None for r in self._slot_request
        )

    @property
    def free_slots(self) -> int:
        return sum(r is None for r in self._slot_request)

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a slot (the routing/backpressure signal)."""
        return len(self._queue)

    @property
    def slots_in_use(self) -> int:
        return sum(r is not None for r in self._slot_request)

    @property
    def load(self) -> int:
        """Queued + in-flight request count — what least-loaded routing
        compares across replicas (`router.Router`)."""
        return len(self._queue) + sum(r is not None for r in self._slot_request)

    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def kv_pool_itemsize(self) -> int:
        """Stored bytes per cached K/V VALUE in the live cache (pool leaf
        itemsize) — the honest dtype figure for HBM-traffic estimates, which
        used to be (wrongly, under quantization) read off the params dtype."""
        return int(np.dtype(_cached_key_leaf(self._cache).dtype).itemsize)

    @property
    def kv_cache_nbytes(self) -> int:
        """Actual stored bytes of the whole slot cache (pools + scale pools
        for quantized dtypes) — the capacity half of the quantization story."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(self._cache):
            total += int(leaf.size) * int(np.dtype(leaf.dtype).itemsize)
        return total

    @property
    def _home_device(self):
        """The per-chip accounting device: the submesh's first device for a
        mesh-spanning engine, the default device otherwise."""
        if self.mesh is not None:
            return self.mesh.devices.flat[0]
        return jax.devices()[0]

    @property
    def per_device_weight_nbytes(self) -> int:
        """Weight bytes resident on ONE chip, read off the LIVE shardings —
        for a tp=N engine the Megatron-sharded kernels contribute ~1/N each,
        replicated leaves (norms, biases) their full size."""
        return tree_device_nbytes(self._params, self._home_device)

    @property
    def per_device_kv_cache_nbytes(self) -> int:
        """Slot-cache bytes resident on ONE chip (pools sharded by KV head
        contribute ~1/N under tp=N; scalars and pad masks replicate)."""
        return tree_device_nbytes(self._cache, self._home_device)

    def tp_sharding_report(self) -> Dict[str, Dict[str, str]]:
        """{'params': {path: spec}, 'cache': {path: spec}} from the LIVE
        arrays — the audit surface the tp tests and the serving bench read to
        prove nothing fell back to silent full replication (TPU118's runtime
        complement). Single-device engines report every leaf as
        'single-device'."""
        from .parallel.sharding import tree_paths_and_leaves

        def describe(tree):
            out = {}
            for path, leaf in tree_paths_and_leaves(tree)[0]:
                sharding = getattr(leaf, "sharding", None)
                spec = getattr(sharding, "spec", None)
                out[path] = str(spec) if spec is not None else "single-device"
            return out

        return {"params": describe(self._params), "cache": describe(self._cache)}

    @property
    def stats(self) -> Dict[str, Any]:
        """Back-compat health view, computed from the metrics registry (the
        source of truth since the telemetry PR). Same keys and meanings as the
        old ad-hoc dict; mutate nothing here — it is rebuilt per access."""
        view: Dict[str, Any] = {
            "attention_impl": self.attention_impl,
            "weight_dtype": self.weight_dtype,
            "kv_cache_dtype": self.kv_cache_dtype,
            "tp": self.tp,
            "inserts": int(self._m_inserts.value),
            "chunks": int(self._m_chunks.value),
            "decode_steps": int(self._m_decode_steps.value),
            "queue_peak": int(self._m_queue_peak.value),
            # Blocking device reads a step() that had anything to read back: 1.0.
            "waits_per_step": (
                self._m_device_waits.value / self._m_dispatching_steps.value
                if self._m_dispatching_steps.value else None
            ),
            # Chunks dispatched while their predecessor ran, over all chunks:
            # ~1 under a backlog, ~0 with an empty queue (step()).
            "chunks_ahead_share": float(self._m_chunks_ahead_share.value),
            "slot_chunks_lost_to_eos": int(self._m_lost_to_eos.value),
            "device_starved": self._starved.view(),
            "run_ahead": {
                "enabled": self.run_ahead_disabled_reason is None,
                "disabled_reason": self.run_ahead_disabled_reason,
            },
            "finish_reasons": {
                reason: int(counter.value) for reason, counter in self._m_finish.items()
            },
        }
        if self.speculative:
            steps = int(self._m_spec_steps.value)
            accepted = int(self._m_spec_accepted.value)
            view["speculative"] = {
                "draft_tokens": self.draft_tokens,
                "draft_ngram": self.draft_ngram,
                "verify_steps": steps,
                "drafted": int(self._m_spec_drafted.value),
                "accepted": accepted,
                "rejected": int(self._m_spec_rejected.value),
                # The headline: mean tokens emitted per verify step. 1.0 means
                # speculation never helped; k+1 is the ceiling.
                "accepted_tokens_per_step": round((steps + accepted) / steps, 4) if steps else None,
            }
        view["pages_total"] = self.pool.pages_total
        view["pages_in_use"] = self.pool.pages_in_use
        view["kv_live_page_share"] = float(self._m_kv_live_page_share.value)
        view["kv_bytes_per_token"] = self._kv_bytes_per_token
        if self._state_bytes_per_slot:
            view["state_bytes_per_slot"] = self._state_bytes_per_slot
            view["state_share_of_cache"] = float(self._m_state_share.value)
        if self._expert_layers:
            view["expert_load_max_over_mean"] = float(self._m_expert_load.value)
        if self._family.residual_streams > 1:
            view["residual_streams"] = self._family.residual_streams
        view["prefix_cache"] = {
            "enabled": self.use_prefix_cache,
            "disabled_reason": self.prefix_cache_disabled_reason,
            "hits": int(self._m_prefix_hits.value),
            "misses": int(self._m_prefix_misses.value),
            "evictions": int(self._m_prefix_evictions.value),
            "prefill_tokens_saved": int(self._m_prefill_saved.value),
            "entries": self.pool.prefix_entries,
            "cached_pages": self.pool.pages_cached,
        }
        return view

    def _update_occupancy_gauges(self):
        """Refresh the point-in-time gauges (queue depth, slot occupancy) —
        called wherever the queue or the slot map changes."""
        depth = len(self._queue)
        self._m_queue_depth.set(depth)
        self._m_queue_peak.set_max(depth)
        in_use = sum(r is not None for r in self._slot_request)
        self._m_slots_in_use.set(in_use)
        self._m_slot_utilization.set(in_use / self.num_slots)
        self._m_pages_in_use.set(self.pool.pages_in_use)

    def submit(self, request: Request) -> int:
        """Validate + enqueue. Raises `ValueError` for malformed requests (the
        caller's bug, reported synchronously), `QueueFull` for backpressure, and
        `EngineClosed` after `close()`/during `drain()` — none of which disturb
        requests already in flight."""
        if self._closed:
            raise EngineClosed("engine is closed")
        if self._draining:
            raise EngineClosed("engine is draining; resubmit after drain() returns")
        ids = np.asarray(request.input_ids, np.int32).reshape(-1)
        if ids.size == 0:
            raise ValueError("empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if ids.size + request.max_new_tokens > self.max_length:
            raise ValueError(
                f"prompt ({ids.size}) + max_new_tokens ({request.max_new_tokens}) "
                f"exceeds the {self.max_length}-token slot capacity"
            )
        need = self._pages_needed(int(ids.size), request.max_new_tokens)
        if need > self.pool.pages_total:
            raise ValueError(
                f"request needs {need} KV pages ({ids.size} prompt + "
                f"{request.max_new_tokens} new tokens"
                + (f" + {self.draft_tokens} draft-window" if self.speculative else "")
                + f" at page_size {self.page_size}) but the pool holds "
                f"{self.pool.pages_total}"
            )
        if request.request_id in self.results:
            raise ValueError(f"duplicate request_id {request.request_id}")
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            raise QueueFull(
                f"wait queue is at max_queue={self.max_queue}; shed load or retry later"
            )
        now = time.perf_counter()
        self.results[request.request_id] = RequestResult(
            request.request_id, arrival_time=request.arrival_time, submit_time=now
        )
        if request.deadline_s is not None:
            self._deadlines[request.request_id] = now + float(request.deadline_s)
        self._queue.append(dataclasses.replace(request, input_ids=ids))
        self._m_submitted.inc()
        span = self.tracer.start_span(
            "serve.request", category="serve",
            request_id=int(request.request_id), prompt_tokens=int(ids.size),
            max_new_tokens=int(request.max_new_tokens),
        )
        span.event("submitted", queue_depth=len(self._queue))
        self._request_spans[request.request_id] = span
        self._update_occupancy_gauges()
        return request.request_id

    def _pages_needed(self, prompt_tokens: int, max_new: int) -> int:
        """A request's page reservation: its worst-case token footprint, plus —
        speculative engines — the draft window, whose rejected verify writes
        land through the slot's own page table (capped at the table width; the
        cache clips overshoot to its never-attended last cell)."""
        window = self.draft_tokens if self.speculative else 0
        return min(pages_for(prompt_tokens + max_new + window, self.page_size), self.pages_per_slot)

    # ------------------------------------------------------------- fault isolation
    def _cache_consumed(self) -> bool:
        """True when a failed dispatch actually CONSUMED the donated slot cache
        (its buffers are deleted) — accelerators only; CPU ignores donation.
        Donation is all-or-nothing per dispatch, so the first leaf decides."""
        for leaf in jax.tree_util.tree_leaves(self._cache):
            is_deleted = getattr(leaf, "is_deleted", None)
            return bool(is_deleted()) if callable(is_deleted) else False
        return False

    def _abort_in_flight(self, exc: Exception, now: Optional[float] = None):
        """The shared-state blast radius: a dispatch failure that took the slot
        cache with it (the decode chunk and anything that surfaces at the
        step's one wait always; an insert's call only when its donated
        operands were consumed). Every in-flight request errors (partial tokens
        kept) — those holding a slot, and those of a chunk not yet read back
        whose slot was already vacated: a failure that surfaces at one chunk's
        readback condemns the chunk and the inserts dispatched behind it too,
        since they consumed the donated cache — and the cache is rebuilt from
        zeros: the donated buffers may
        already be invalidated, and keeping the references would poison every
        later insert with a deleted-buffer error, leaving the engine up but
        failing every future request. New admissions overwrite their own rows
        before they are ever attended, exactly as at engine construction."""
        now = time.perf_counter() if now is None else now
        condemned = [r for r in self._slot_request if r is not None]
        for flight in self._flights:
            condemned += [r for r in flight.tenants if r is not None and not r.finished]
            flight.span.annotate(error=repr(exc)).end()
        self._flights.clear()
        self._starved.all_read(now)
        self.tracer.event(
            "serve.blast_radius", category="serve",
            errored_requests=len({id(r) for r in condemned}),
            error=repr(exc),
        )
        for slot, result in enumerate(self._slot_request):
            if result is not None:
                self._vacate(slot)
        for result in condemned:
            if not result.finished:
                self._finish(result, "error", now=now, error=repr(exc))
        self._fresh.clear()  # they held slots: errored above, with no tokens
        self._cache = self._init_cache()
        self._first_token = self._new_first_token()
        self._presence = self._new_presence()
        # Every slot idle on the device too, and on the host: a speculative
        # engine's drafting contexts belonged to requests that just errored.
        self._carry = self._new_carry()
        self._slots.reset()
        # The pool CONTENT died with the donated buffers: every refcount,
        # page-table row and — critically — prefix registration goes with
        # it (a stale hash->page mapping would serve zeroed KV as a
        # "cached" prefix to the next shared-prompt request).
        self.pool.reset()
        self._slot_pages = [[] for _ in range(self.num_slots)]
        self._m_pages_in_use.set(0)

    def _slot_of(self, request_id: int) -> Optional[int]:
        for slot, result in enumerate(self._slot_request):
            if result is not None and result.request_id == request_id:
                return slot
        return None

    def _vacate(self, slot: int):
        """Free a slot, its pages and its table row for the next `_admit` —
        the half of a request's exit that may come BEFORE its last tokens:
        a slot whose request will have ended when the chunk just dispatched
        has run is vacated at that dispatch (_predict), and the insert of its
        next tenant is enqueued behind the chunk, so the device's program
        order keeps the old tenant's last writes ahead of the new one's. The
        next dispatch tells the device (`_SlotMirror.vacate`)."""
        self._slot_request[slot] = None
        self._slots.vacate(slot, held_pages=bool(self._slot_pages[slot]))
        # Release the slot's page references (a shared prefix page
        # drops to CACHED at refcount 0, private pages go free).
        if self._slot_pages[slot]:
            self.pool.release(self._slot_pages[slot])
            self._slot_pages[slot] = []

    def _finish(self, result: RequestResult, reason: str, now: Optional[float] = None,
                slot: Optional[int] = None, error: Optional[str] = None):
        """The single exit path for a request's RESULT: stamp it, bump the
        per-reason counter, drop its deadline — and `_vacate(slot)` where the
        request still holds one. A request that ends by length has been
        vacated one drain earlier (_predict): this runs at the drain that
        hands out its last tokens, and until then the result reads unfinished
        (`release()` refuses it). Tokens of a finished request that a chunk
        in flight still streams (a cancel, a deadline) are dropped by _drain."""
        result.finished = True
        result.finish_time = time.perf_counter() if now is None else now
        result.finish_reason = reason
        if error is not None:
            result.error = error
        span = self._request_spans.get(result.request_id)
        if span is not None:
            span.annotate(finish_reason=reason, tokens=len(result.tokens))
            if error is not None:
                span.annotate(error=error)
            # A request that ends in the step() that admitted it keeps its span
            # open for the `handed_back` event: _hand_back() ends it.
            if not any(entry[0] is result for entry in self._first_tokens):
                del self._request_spans[result.request_id]
                span.end()
        self._m_finish[reason].inc()
        self._deadlines.pop(result.request_id, None)
        self._last_event.pop(result.request_id, None)
        if slot is not None:
            self._vacate(slot)
        self._update_occupancy_gauges()

    def _drop_queued(self, request_id: int) -> bool:
        before = len(self._queue)
        self._queue = deque(r for r in self._queue if r.request_id != request_id)
        return len(self._queue) != before

    def _expire_deadlines(self):
        """Step-boundary deadline sweep: queued requests time out without ever
        occupying a slot; in-flight ones keep the tokens handed out so far and
        free the slot (what a chunk in flight still streams of them is dropped)."""
        if not self._deadlines:
            return
        now = time.perf_counter()
        for request_id in [rid for rid, t in self._deadlines.items() if now >= t]:
            result = self.results[request_id]
            if result.finished:
                self._deadlines.pop(request_id, None)
                continue
            self._drop_queued(request_id)
            self._finish(result, "timeout", now=now, slot=self._slot_of(request_id))

    def cancel(self, request_id: int) -> bool:
        """Cancel a queued or in-flight request: its result finishes with
        `finish_reason="cancelled"` (the tokens handed out so far kept) and its
        slot frees for the next admission. A chunk in flight may still stream
        up to one chunk of its tokens: no later step hands them out, and the
        next dispatch clears the slot on the device. Returns False if it
        already finished; raises KeyError for an unknown id."""
        result = self.results[request_id]
        if result.finished:
            return False
        self._drop_queued(request_id)
        self._finish(result, "cancelled", slot=self._slot_of(request_id))
        return True

    def _admit(self):
        """Fill free slots from the queue (FIFO). Each admission is one insert
        DISPATCH and nothing more: the first token stays on the device
        (`_first_token[slot]`), the slot joins `_fresh`, the decode chunk
        dispatched next starts from the token where it is and hands it back
        with its own outputs. So the host prepares the next admission, the
        operand push and the chunk's launch while the inserts run — and, when
        the step before left a chunk in flight, while that chunk runs: the
        inserts are enqueued BEHIND it. A free slot may be one `_vacate`d on a
        prediction — its last tenant ends in the chunk still running, whose
        writes the device's program order keeps ahead of this insert's. Every
        admission holds its slot until the next chunk is dispatched (or, in a
        step that dispatches none, until its drain) — a one-token request too,
        so that no second admission is given its entry of the buffer first.

        Admission is PAGE-based, not slot-based: the request reserves
        `ceil((prompt + max_new) / page_size)` pool pages minus whatever its
        prompt prefix already shares from the prefix cache — so a mix of small
        requests can occupy every slot even when the pool is far smaller than
        `num_slots * max_length` worst-case rows. When the pool (plus evictable
        cached prefix pages) cannot cover the next request, it returns to the
        FRONT of the queue and admission pauses until in-flight requests
        release pages — FIFO order and guaranteed progress, since reserve-on-
        admit means every admitted request runs to completion.

        Error isolation: an exception raised at ONE request's insert CALL (a
        bucket's executable dies, a prompt the compiled program rejects)
        finishes only that request with `finish_reason="error"` — the queue
        keeps draining and every other slot keeps serving. A failure of an
        insert ON THE DEVICE surfaces at the step's one wait, where it cannot
        be told from the chunk's: it takes the blast-radius path
        (`_abort_in_flight`) as a chunk failure does, and the requests admitted
        in that step error with no tokens."""
        fresh_before = len(self._fresh)
        while self._queue and self.free_slots:
            req = self._queue.popleft()
            slot = self._slot_request.index(None)
            ids = req.input_ids
            p = int(ids.size)
            result = self.results[req.request_id]
            hashes: List[str] = []
            total_pages = self._pages_needed(p, req.max_new_tokens)
            if self.use_prefix_cache:
                hashes = chain_hashes(ids, self.page_size)
                # Cap below the whole prompt: the last real token always
                # reruns so the insert has first-token logits to sample.
                shared = self.pool.match_prefix(hashes, min(len(hashes), (p - 1) // self.page_size))
            else:
                shared = []
            matched_pages = len(shared)
            # Closed-bucket planning: when the pow2 suffix bucket would
            # overflow the cache window (`matched_len + bucket >
            # _padded_length`), DROP trailing matched pages instead of
            # minting a matched_len-dependent capped bucket — an open set
            # of bucket sizes no warmup can enumerate, and the source of
            # the first-hit insert recompiles the bench's 0-recompile
            # assert used to trip at non-default --max-new-max sizes.
            bucket, keep_pages = self.plan_admission_bucket(
                p, matched_pages, self.page_size, self._padded_length
            )
            while matched_pages > keep_pages:
                self.pool.release([shared.pop()])
                matched_pages -= 1
            matched_len = matched_pages * self.page_size
            private = self.pool.reserve(total_pages - matched_pages)
            if private is None:
                if shared:
                    self.pool.release(shared)
                self._queue.appendleft(req)
                break
            pages = shared + private
            if self.use_prefix_cache:
                full_pages = p // self.page_size
                self._m_prefix_hits.inc(matched_pages)
                self._m_prefix_misses.inc(max(full_pages - matched_pages, 0))
                if matched_len:
                    self._m_prefill_saved.inc(matched_len)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, : p - matched_len] = ids[matched_len:]
            page_row = np.zeros((self.pages_per_slot,), np.int32)
            page_row[: len(pages)] = pages
            admitted_at = time.perf_counter()
            rspan = self._request_spans.get(req.request_id)
            if rspan is not None:
                rspan.event(
                    "admitted", slot=slot, bucket=int(bucket),
                    queue_wait_s=round(admitted_at - result.submit_time, 6),
                    prefix_hit_pages=int(matched_pages), pages_reserved=len(pages),
                )
            try:
                # A child of this step's `serve.step`; `request_id` ties it to
                # its `serve.request` span. A failure is on it as `error`.
                with self.tracer.span(
                    "serve.insert", category="serve",
                    request_id=int(req.request_id), slot=slot, bucket=int(bucket),
                    suffix_tokens=int(p - matched_len), prefix_hit_pages=int(matched_pages),
                    head_rows=1,
                    **self._family.config.insert_span_counts(
                        int(bucket), int(p - matched_len), int(matched_len), self._padded_length),
                ):
                    fn = self._insert_fn(bucket)
                    self._first_token, self._cache, self._presence, self._rng = fn(
                        self.params,
                        self._cache,
                        self._presence,
                        jnp.asarray(padded),
                        _operand(p - matched_len, np.int32),
                        _operand(matched_len, np.int32),
                        _operand(matched_pages, np.int32),
                        jnp.asarray(page_row),
                        _operand(slot, np.int32),
                        _operand(req.temperature, np.float32),
                        _operand(req.repetition_penalty, np.float32),
                        self._rng,
                        self._first_token,
                    )
                # What the device sat empty until now is the admission's (a
                # step's FIRST insert alone can charge).
                result.insert_dispatched_time = time.perf_counter()
                self._starved.enqueued("admit", result.insert_dispatched_time)
            except Exception as exc:  # noqa: BLE001 — isolate, report, keep serving
                self.pool.release(pages)
                if self.trace_guard is not None:
                    self.trace_guard.observe(exc)
                logger.warning(
                    "insert failed for request %s (isolated): %r", req.request_id, exc
                )
                self._finish(result, "error", error=repr(exc))
                # Per-request isolation holds only while the shared cache is
                # intact. The insert fn donates (cache, presence) too: if this
                # failed dispatch consumed them (chaos-surfaced hazard — the
                # same poisoning the chunk path guards against), the state is
                # gone for EVERY slot — widen to the blast-radius recovery.
                if self._cache_consumed():
                    logger.warning(
                        "failed insert consumed the donated slot cache; erroring "
                        "%d in-flight request(s) and rebuilding",
                        sum(r is not None for r in self._slot_request),
                    )
                    self._abort_in_flight(exc)
                continue
            if self.use_prefix_cache:
                # The insert just wrote this prompt's full pages: register them
                # so the NEXT request with the same prefix shares instead of
                # prefilling. Decode writes land at pos >= prompt_len, past
                # every full prompt page, so registered content stays frozen.
                self.pool.register_prefix(hashes[: p // self.page_size], pages, start=matched_pages)
            self._m_inserts.inc()
            self._fresh.append((slot, admitted_at, len(self._fresh) - fresh_before))
            self._slot_request[slot] = result
            self._slot_pages[slot] = pages
            # A one-token request (nothing left to decode) is vacated when the
            # next chunk is dispatched and finished when its token is drained —
            # a prefix it just registered stays CACHED for the next hit.
            self._slots.admit(
                slot, ids, req.max_new_tokens - 1, -1 if req.eos_token_id is None else int(req.eos_token_id),
                req.temperature, req.repetition_penalty, page_row,
            )
        self._update_occupancy_gauges()

    def _hand_back(self):
        """Runs as step() returns, which is when a client gets the first token
        of every request whose token the step drained: observes TTFT there,
        and marks the request's span with `handed_back`, which says where the
        request waited — `ttft_s`, what the client waited since submit(), in
        four phases that add up to it: `queue_wait_s` (submit → admission),
        `admit_host_s` (admission → its insert's dispatch call returned: the
        host part of the insert; the `inserts_ahead` admissions of its step
        before it were each dispatched before its own began), `on_device_s`
        (→ the token on the host: the insert, the chunk it rides — `rode_chunk`
        — under a backlog what was left of the chunk before that, and the
        readback) and `held_s` (→ step() returns: how long the engine sat on a
        token it had)."""
        if not self._first_tokens:
            return
        now = time.perf_counter()
        for result, admitted_at, inserts_ahead, rode_chunk in self._first_tokens:
            ttft_s = round(now - result.submit_time, 6)
            self._m_ttft.observe(ttft_s)
            span = self._request_spans.get(result.request_id)
            if span is not None:
                dispatched = result.insert_dispatched_time
                span.event("handed_back",
                           queue_wait_s=round(admitted_at - result.submit_time, 6),
                           admit_host_s=round(dispatched - admitted_at, 6),
                           inserts_ahead=inserts_ahead,
                           on_device_s=round(result.first_token_time - dispatched, 6),
                           held_s=round(now - result.first_token_time, 6),
                           ttft_s=ttft_s, rode_chunk=rode_chunk)
                if result.finished:  # _finish() left the span open for this event
                    del self._request_spans[result.request_id]
                    span.end()
        self._first_tokens.clear()

    def release(self, request_id: int) -> RequestResult:
        """Drop a FINISHED request's result and free its id for reuse. `results`
        is never evicted on its own — a long-running server must release each
        request once its consumer has drained it, or host memory grows linearly
        in total requests served."""
        result = self.results[request_id]
        if not result.finished:
            raise ValueError(f"request {request_id} is still in flight")
        del self.results[request_id]
        return result

    def _chunk_operands(self) -> List[Any]:
        """The decode-chunk dispatch's operand list. All of it is on the
        device already — params, the donated cache/presence, the rng, the
        inserts' first tokens, the slot state its predecessor returned
        (`_carry`), the host-only operands' copies — but what the host
        changed since the last dispatch (`_SlotMirror.operands`). A step that
        changed no slot pushes nothing."""
        host_only, update, history = self._slots.operands()
        args = [self.params, self._cache, self._presence, *self._carry, *host_only,
                self._rng, self._first_token, update]
        if history is not None:
            args.append(history)
        return args

    def lower_decode_chunk(self):
        """`jax.stages.Lowered` of THE decode executable at the engine's live
        operand signature, without dispatching it — how chip_smoke.py proves
        from the program text which attention read is inside it
        (a `tpu_custom_call` for the compiled Pallas kernels)."""
        return self._chunk_fn.lower(*self._chunk_operands())

    def step(self) -> List[Tuple[int, List[int]]]:
        """One serving cycle: expire deadlines → admit → dispatch one decode
        chunk → ONE wait → drain first tokens and a chunk's packed stream.
        Returns `(request_id, new_tokens)` events in stream order (admissions'
        first tokens included, each ahead of its request's chunk tokens).

        A step enqueues every device program it has — each admission's insert,
        then the decode chunk — before it blocks on anything, and blocks once.
        WHICH chunk it reads back depends on one observation of its own input,
        the queue after admission:

          - **Queue empty** (no request waits for a slot): the step reads back
            the chunk it just dispatched, which brings the inserts' first
            tokens too (the first-token buffer alone when nothing is left to
            decode). Nothing is in flight when it returns. An arrival's insert
            never waits out a chunk it did not have to.
          - **Requests left in the queue** (a backlog: no arrival could have
            been admitted sooner anyway): the step RUNS ONE CHUNK AHEAD. It
            enqueues its inserts and its chunk behind the chunk the step
            before left running, and only then reads that older chunk back;
            the device holds its next program through the drain, the client
            loop, the next admission, push and launch. The first such step has
            no older chunk: it returns no events and does not wait. ONE CHUNK
            IS IN FLIGHT WHEN SUCH A STEP RETURNS (`pending` stays True): its
            tokens, and the first tokens of the requests admitted with it, are
            handed out by the next `step()`; `drain()` and `run()` step until
            nothing is left, `close()` reads it back before it cancels.
          - A step that finds a chunk in flight and an empty queue reads it
            back, dispatches no chunk, and the engine is synchronous again.

        Speculative engines always take the first form (`stats["run_ahead"]`).

        Running ahead, the host works from a PREDICTED mirror (_predict): a
        request that ends by length is known a chunk early, its slot is
        vacated at that dispatch and re-admitted in the next step, before its
        last tokens are drained. A request that stops on its EOS is known only
        at its drain, one chunk late: the chunk already in flight carries its
        slot inactive (`stats["slot_chunks_lost_to_eos"]`).

        One span tree a step, each also a profiler annotation of its name but
        `serve.decode_chunk`:

            serve.step ⊃ serve.admit ⊃ serve.insert (one an admission: dispatch only)
                       ⊃ serve.decode_chunk (opened at its dispatch ⊃ serve.chunk.push, .dispatch;
                                             ended at its readback, in this step or the next)
                       ⊃ serve.chunk.wait (the step's one wait: for the chunk it reads back)
                       ⊃ serve.drain

        `serve.step`, `serve.insert` and `serve.decode_chunk` are recorded (the
        flight recorder's ring stays proportional to dispatches); the others
        are annotations whose seconds ride `serve.step` as `admit_s`, `push_s`,
        `dispatch_s`, `drain_s` and `device_wait_s` (the step's one wait).
        `host_s` is the rest of the step: its own time, with the device's
        taken out. `waits` counts the step's blocking device reads (1; 0 for an
        idle step and for the step that starts running ahead),
        `dispatched_ahead` the programs enqueued before it (inserts + chunk)
        and `in_flight_at_return` the chunks left for the next step (0 or 1).
        `serve.decode_chunk.ahead` says the chunk was dispatched while its
        predecessor ran; its `cadence_s` is the time since the previous
        readback returned, at most its own extent: one chunk and the inserts
        ahead of it either way (what `serving_chunk_seconds` observes).

        The account of a starved device (`_StarvedAccount`) is kept at these
        boundaries: `starved_s` and its four parts, `gap_s` and `gap_cause`."""
        if self._closed:
            return []
        tracer = self.tracer
        with tracer.span("serve.step", category="serve") as step_span:
            gap_s, gap_cause = self._starved.begin_step(time.perf_counter())
            waits_before = self._m_device_waits.value
            self._expire_deadlines()
            with tracer.span("serve.admit", category="serve", record=False) as admit_span:
                self._admit()
            self._starved.charge("admit")  # where it admitted nothing: else its first insert moved the mark
            inserts = len(self._fresh)
            step_span.annotate(inserts=inserts,
                               admit_s=round(admit_span.duration_s, 6), push_s=0.0, dispatch_s=0.0)
            events: List[Tuple[int, List[int]]] = []
            # The chunk the step before left running, if any; then whether this
            # step leaves one: only with a backlog, and only an engine that can
            # predict its slots.
            older = self._flights[0] if self._flights else None
            run_ahead = bool(self._queue) and self.run_ahead_disabled_reason is None
            decoding = bool(self._slots.active.any()) and (older is None or run_ahead)
            newer = self._dispatch_chunk(step_span, ahead=older is not None) if decoding else None
            if decoding and newer is None:  # the dispatch failed: everything in flight errored
                older = None
            flight = older if older is not None else (None if run_ahead else newer)
            device_wait_s = drain_s = 0.0
            if flight is not None or self._fresh:
                self._m_dispatching_steps.inc()
                drained, device_wait_s = self._read_back(flight)
                if drained is not None and not self._flights:
                    self._starved.all_read()
                with tracer.span("serve.drain", category="serve", record=False) as drain_span:
                    if drained is not None:
                        self._drain(events, flight, *drained)
                    self._hand_back()
                drain_s = drain_span.duration_s
            starved = self._starved.end_step(self.pending)
            step_span.annotate(
                drain_s=round(drain_s, 6),
                device_wait_s=round(device_wait_s, 6),
                host_s=round(step_span.duration_s - device_wait_s, 6),
                waits=int(self._m_device_waits.value - waits_before),
                dispatched_ahead=inserts + decoding,
                in_flight_at_return=len(self._flights),
                gap_s=round(gap_s, 6), gap_cause=gap_cause, **starved,
            )
        return events

    def _read_back(self, flight: Optional[_Flight]):
        """The step's ONE blocking device read: `flight`'s outputs (its fresh
        slots' first tokens among them only where it has any) and, for
        admissions that ride no chunk (`_fresh` still holds them: the step
        dispatched none), the first-token buffer itself. Returns what _drain()
        takes — None when the read failed: see _wait_failed() — and the
        seconds it waited. jax.device_get — np.asarray / int() on a device
        value are IMPLICIT reads, which an armed transfer guard rejects on a
        TPU."""
        read = None
        if flight is not None:
            read = {k: v for k, v in flight.read.items() if k != "first" or flight.fresh}
        values = (read, self._first_token if self._fresh else None)
        self._m_device_waits.inc()
        name = "serve.chunk.wait" if flight is not None else "serve.first_tokens.wait"
        try:
            with self.tracer.span(name, category="serve", record=False) as wait_span:
                host = jax.device_get(values)
        except Exception as exc:  # noqa: BLE001
            self._wait_failed(exc, "decode chunk readback" if flight is not None else "first-token readback")
            return None, 0.0
        since_previous = self._starved.read_returned()
        if flight is not None:
            self._flights.popleft()
            # One chunk's cadence: since the previous readback returned, at most
            # the span's own extent (operand push through readback) — the chunk
            # and the inserts ahead of it, where the span of a chunk dispatched
            # ahead also covers what was left of its predecessor.
            cadence_s = max(flight.span.duration_s, 0.0)
            if since_previous is not None:
                cadence_s = min(cadence_s, since_previous)
            flight.span.annotate(cadence_s=round(cadence_s, 6), **self._chunk_counts(host[0])).end()
            self._m_chunk_latency.observe(cadence_s)
        return host, wait_span.duration_s

    def _wait_failed(self, exc: Exception, what: str):
        """A failure at the step's dispatches or its wait: dispatch is async
        on accelerators, so an insert's or a chunk's device-side failure
        surfaces at a readback, and the programs share the donated cache —
        the in-flight state is unrecoverable, so every in-flight request
        errors (partial tokens kept; this step's admissions with none), the
        requests of a successor chunk already dispatched with them. The
        engine itself stays up: slots free, the queue keeps draining, new
        admissions rebuild their own cache rows from scratch."""
        if self.trace_guard is not None:
            self.trace_guard.observe(exc)
        in_flight = sum(r is not None for r in self._slot_request)
        logger.warning("%s failed; erroring %d in-flight request(s): %r", what, in_flight, exc)
        self._abort_in_flight(exc)

    def _dispatch_chunk(self, step_span, ahead: bool) -> Optional[_Flight]:
        """Push what the host changed, dispatch the decode chunk on its
        predecessor's outputs and move the host's mirror past it (_predict).
        `serve.decode_chunk` opens here and is ended by the readback of its
        `_Flight`, in this step() or the next; `push_s` and `dispatch_s` go on
        `step_span`. Returns the flight, appended to `_flights` — None when
        the dispatch failed (every in-flight request then errored)."""
        tracer = self.tracer
        # One batched span per chunk dispatch: every active request rides it
        # (not N per-request spans). The readers of `chipbench/chunk_counters.py`
        # take it by where it STARTS: at its dispatch.
        chunk_span = tracer.start_span(
            "serve.decode_chunk", category="serve",
            chunk_size=self.chunk_size,
            active_slots=int(self._slots.active.sum()),
            pages_in_use=self.pool.pages_in_use,
            ahead=bool(ahead),
            **self._live_page_counts(),
            **self._family.config.chunk_span_counts(int(self._slots.active.sum()) * self.chunk_size),
        )
        try:
            with tracer.span("serve.chunk.push", category="serve", record=False) as push_span:
                operands = self._chunk_operands()
            self._starved.charge("push")  # where no insert went out before it
            with tracer.span("serve.chunk.dispatch", category="serve", record=False) as dispatch_span:
                carry, read = self._chunk_fn(*operands)
            chunk_span.annotate(read_impl=self.read_impl)  # known once the first dispatch has traced it
            self._starved.enqueued("dispatch")
        except Exception as exc:  # noqa: BLE001
            chunk_span.annotate(error=repr(exc)).end()
            self._wait_failed(exc, "decode chunk dispatch")
            return None
        step_span.annotate(push_s=round(push_span.duration_s, 6),
                           dispatch_s=round(dispatch_span.duration_s, 6))
        self._cache, self._presence, *slot_state, self._rng = carry
        self._carry = tuple(slot_state)
        self._m_chunks.inc()
        self._m_chunks_ahead.inc(int(ahead))
        self._m_chunks_ahead_share.set(self._m_chunks_ahead.value / self._m_chunks.value)
        self._m_decode_steps.inc(self.chunk_size)
        # The host's state as the chunk was dispatched, before _predict() moves it on.
        slots = self._slots
        tenants, was_active, pos_before = list(self._slot_request), slots.active.copy(), slots.pos.copy()
        flight = _Flight(
            read=read, span=chunk_span, tenants=tenants, fresh=self._fresh, was_active=was_active,
            ends=self._predict(), eos=slots.eos.copy(), pos_before=pos_before,
        )
        self._fresh = []
        self._flights.append(flight)
        return flight

    def _predict(self) -> np.ndarray:
        """Move the host's mirror past the chunk just dispatched, without the
        device (`_SlotMirror.dispatched`). A slot whose request WILL have
        ended when the chunk has run (its budget is at most the chunk's steps;
        a one-token request, which never decodes) is vacated now: the returned
        `bool[num_slots]` marks them, for the drain that finishes their
        results."""
        self._slots.dispatched(None if self.speculative else self.chunk_size)
        ends = np.asarray([r is not None for r in self._slot_request]) & ~self._slots.active
        for slot in np.nonzero(ends)[0]:
            self._vacate(int(slot))
        if ends.any():
            self._update_occupancy_gauges()
        return ends

    def _live_page_counts(self) -> Dict[str, int]:
        """What the KV read is about to visit, from the host mirrors: the
        active slots' live pages (`pos // page_size + 1` each) beside the
        window's `num_slots * pages_per_slot`, for the chunk's span; their
        share goes to the `kv_live_page_share` gauge. `read_blocks` is the
        trip count of the XLA read's loop in the chunk's first step, as the
        read's own module counts it (`ops.attention.read_blocks`: an idle
        slot is the one scratch page the device visits). A slot's pages grow
        inside the chunk: that is not counted."""
        pos, active = self._slots.pos, self._slots.active
        live = int((pos[active] // self.page_size + 1).sum())
        window = self.num_slots * self.pages_per_slot
        self._m_kv_live_page_share.set(live / window)
        counts = {"live_pages": live, "window_pages": window,
                  "read_blocks": attention_ops.read_blocks(np.where(active, pos, 0), *self._read_shape),
                  "kv_row_values": self.kv_row_values}
        if self._state_bytes_per_slot:
            # A family with recurrent state: what the chunk's first step reads
            # and writes whatever the contexts, beside the pages it visits.
            slots = int(active.sum())
            state = slots * self._state_bytes_per_slot
            pages = live * self.page_size * self._kv_bytes_per_token
            self._m_state_share.set(state / (state + pages) if state else 0.0)
            counts.update(state_bytes_per_slot=self._state_bytes_per_slot, state_slots=slots,
                          kv_page_bytes=self.page_size * self._kv_bytes_per_token)
        return counts

    def _chunk_counts(self, host: Dict[str, Any]) -> Dict[str, int]:
        """What a chunk's readback counts, for its span: the tokens streamed,
        — a family with routed experts — `expert_tokens_max` / `_mean` and
        `experts_touched`, and
        — speculative engines — the fold of the per-(iteration, slot)
        emit/propose matrices into the spec ledger. Every count is a host
        scalar off the readback."""
        counts = {"tokens_streamed": int(host["count"])}
        if self._expert_layers:
            # Over the chunk's dispatches, every row the program ran, idle
            # slots' too — they are rows the experts multiply.
            tokens, dispatches = (np.asarray(host["expert_tokens"])[:, i] for i in (0, 1))
            busiest, mean = float(tokens.max(axis=1).mean()), float(tokens.mean())
            self._m_expert_load.set(busiest / mean if mean else 0.0)
            counts.update(
                expert_tokens_max=busiest, expert_tokens_mean=mean,
                # routed experts a layer a dispatch that were given any row
                experts_touched=float(dispatches.sum()) / (self._expert_layers * self.chunk_size),
            )
        if self.speculative:
            spec_emitted, spec_proposed = host["spec_emitted"], host["spec_proposed"]
            steps = int((spec_emitted > 0).sum())
            emitted_total = int(spec_emitted.sum())
            proposed_total = int(spec_proposed.sum())
            accepted = emitted_total - steps  # each step emits accepted + 1
            self._m_spec_steps.inc(steps)
            self._m_spec_drafted.inc(proposed_total)
            self._m_spec_accepted.inc(accepted)
            self._m_spec_rejected.inc(proposed_total - accepted)
            for v in spec_emitted[spec_emitted > 0]:
                self._m_spec_hist.observe(float(v))
            counts.update(
                spec_verify_steps=steps,
                spec_tokens_emitted=emitted_total,
                spec_drafts_accepted=accepted,
                spec_drafts_proposed=proposed_total,
            )
        return counts

    def _first_token_to(self, events, result: RequestResult, token: int, now: float,
                        admitted_at: float, inserts_ahead: int, rode_chunk: bool):
        """Hand `result` the token its insert sampled, on the host since `now`:
        with a chunk's readback (`rode_chunk`), or the first-token buffer's."""
        result.tokens.append(token)
        result.first_token_time = now
        self._first_tokens.append((result, admitted_at, inserts_ahead, rode_chunk))
        events.append((result.request_id, [token]))
        span = self._request_spans.get(result.request_id)
        if span is not None:
            span.event("first_token")

    def _drain(self, events: List[Tuple[int, List[int]]], flight: Optional[_Flight], read, first_token):
        """Hand out what the step's one readback brought: `flight`'s chunk —
        its admissions' first tokens, then its packed `(slot, token)` stream,
        routed by the slot → request map AS IT WAS WHEN THE CHUNK WAS
        DISPATCHED (a slot may have its next tenant by now) — then, from
        `first_token` (the buffer itself), the first tokens of admissions
        that ride no chunk. Requests that ended are finished: those the
        dispatch had predicted (vacated there), and those only the device
        could know — an EOS, a speculative block — which are vacated here.
        What a chunk streams of a request finished meanwhile is dropped."""
        now = time.perf_counter()
        self.tracer.recorder.poll()  # serve the `trace dump` touch file
        if flight is not None:
            self._drain_chunk(events, flight, read, now)
        # Admissions of a step that dispatched no chunk. A one-token request
        # ends here; any other keeps `from_buffer`: the next chunk starts it
        # from the buffer, where a first token that is its EOS ends it.
        for slot, *admission in self._fresh:
            result = self._slot_request[slot]
            if result is None:
                continue
            self._first_token_to(events, result, int(first_token[slot]), now, *admission, rode_chunk=False)
            if self._slots.rem[slot] == 0:
                self._finish(result, "eos" if result.tokens[-1] == self._slots.eos[slot] else "length",
                             now=now, slot=slot)
        self._fresh.clear()

    def _drain_chunk(self, events, flight: _Flight, read, now: float):
        tenants = flight.tenants
        for slot, *admission in flight.fresh:
            result = tenants[slot]
            if result.finished:  # cancelled or timed out since its dispatch
                continue
            token = int(read["first"][slot])
            self._first_token_to(events, result, token, now, *admission, rode_chunk=True)
            if self.speculative and flight.was_active[slot]:
                self._slots.saw(slot, flight.pos_before[slot], [token])  # as the chunk did on the device
        per_slot: Dict[int, List[int]] = {}
        for slot, tok in read["packed"][: int(read["count"])]:
            per_slot.setdefault(int(slot), []).append(int(tok))
        for slot, toks in per_slot.items():
            result = tenants[slot]
            if result is None or result.finished:
                continue
            result.tokens.extend(toks)
            if self.speculative:
                # Mirror the device-side history update (emitted token j of the
                # chunk landed at history[pos_before + 1 + j]) so the next
                # dispatch pushes an identical context.
                self._slots.saw(slot, int(flight.pos_before[slot]) + 1, toks)
            events.append((result.request_id, toks))
            # Inter-token latency: the host drains a request's tokens once per
            # chunk, so the per-token gap is the drain gap amortized over the
            # tokens it delivered, weighted by them.
            last = self._last_event.get(result.request_id)
            if last is not None:
                self._m_inter_token.observe(max(now - last, 0.0) / len(toks), count=len(toks))
            self._last_event[result.request_id] = now

        # Who ended in this chunk: the slots its dispatch vacated, and those the
        # device alone could know of — decoding as dispatched, inactive after.
        stopped = flight.was_active & ~np.asarray(read["active"])
        for slot in np.nonzero(flight.ends | stopped)[0]:
            result = tenants[slot]
            if result is None or result.finished:
                continue
            if self._slot_request[slot] is result:
                # Not predicted. Under a backlog the successor is in flight
                # with this slot inactive: a chunk of a slot's time lost.
                self._m_lost_to_eos.inc(sum(f.tenants[slot] is result for f in self._flights))
                self._vacate(int(slot))
            reason = "eos" if result.tokens and result.tokens[-1] == flight.eos[slot] else "length"
            self._finish(result, reason, now=now)
        if self.speculative:
            # Where the device left the slots still decoding: the next
            # dispatch's live pages and the drafter's context are read off it.
            still = np.asarray([r is not None and self._slot_request[i] is r
                                for i, r in enumerate(tenants)]) & np.asarray(read["active"])
            self._slots.adopt(still, read["pos"], read["rem"])

    def run(self, requests: Optional[List[Request]] = None) -> Dict[int, np.ndarray]:
        """Drive to completion: submit `requests` (if given), loop `step()` until
        the queue, every slot and the chunk in flight drain, return
        {request_id: generated tokens}."""
        for req in requests or ():
            self.submit(req)
        while self.pending:
            self.step()
        return {rid: np.asarray(r.tokens, np.int32) for rid, r in self.results.items()}

    # ------------------------------------------------------------------ lifecycle
    def drain(self) -> Dict[int, RequestResult]:
        """Flush: refuse new submissions while finishing everything queued and
        in flight (a chunk a step left running included), then reopen.
        Returns the full results map (the caller `release()`s what it has
        consumed)."""
        self._draining = True
        try:
            while self.pending:
                self.step()
        finally:
            self._draining = False
        return self.results

    def close(self) -> Dict[int, RequestResult]:
        """Terminal shutdown: a chunk still in flight is read back (its tokens
        go to their results; nobody is handed events), then everything still
        queued or in flight finishes with `finish_reason="cancelled"` (partial
        tokens kept), and the engine permanently refuses new work (`submit`
        raises `EngineClosed`, `step` no-ops). Idempotent."""
        if self._closed:
            return self.results
        self._queue.clear()
        if self._flights or self._fresh:
            flight = self._flights[0] if self._flights else None
            self._m_dispatching_steps.inc()  # the read a step() would have made
            drained, _ = self._read_back(flight)
            if drained is not None:
                self._drain([], flight, *drained)
            self._hand_back()
        now = time.perf_counter()
        for slot, result in enumerate(self._slot_request):
            if result is not None:
                self._finish(result, "cancelled", now=now, slot=slot)
        for result in self.results.values():
            if not result.finished:  # still queued (never admitted)
                self._finish(result, "cancelled", now=now)
        self._closed = True
        self._update_occupancy_gauges()
        return self.results
