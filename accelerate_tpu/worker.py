"""Out-of-process serving workers: one engine per OS process, coordinated over
an explicit IPC protocol.

PR 10's `router.Router` made the serving fleet replicated, but every replica
still shared one Python interpreter: a segfault, a GIL stall, or an OOM in any
engine took down ALL of them. This module moves the engine into a real process
fault domain — the serving analogue of the multi-controller discipline MPMD
training systems use: independent workers, an explicit wire protocol, and a
controller that can lose any worker without losing its own state.

Three layers, bottom up:

  - **Framing** (`send_frame` / `recv_frame`): length-prefixed JSON over a pair
    of pipe/socket file descriptors. A frame is a 4-byte big-endian payload
    length followed by UTF-8 JSON. `recv_frame` always takes a deadline — an
    IPC read with no timeout turns a hung peer into a hung caller, which is
    exactly the failure isolation this module exists to remove (analysis rule
    TPU116 lints that discipline). Torn frames (EOF mid-payload) raise
    `WorkerGone`; oversized or undecodable frames raise `FrameError`.

  - **Worker side** (`python -m accelerate_tpu.worker`): builds a model from a
    JSON spec (a named registry model, or a family+config dict with the params
    loaded from an `.npz` the controller saved — so worker params are
    bit-identical to the controller's, never re-derived), hosts ONE
    `ContinuousBatcher` behind `EngineHost`, optionally pre-warms the insert
    ladder before reporting ready (a restarted worker rejoins WARM: the fleet
    never pays a compile on the serving path), and runs `serve_worker` — a
    recv/dispatch/reply loop with a heartbeat deadline: a controller that goes
    silent past the deadline means the worker is orphaned and exits instead of
    leaking. Fault plans ride the PR 5 env protocol (`ACCELERATE_TPU_FAULT_PLAN`)
    and trace context rides the PR 7 one (`ACCELERATE_TPU_TRACE_DIR`), so chaos
    can SIGKILL a real worker mid-dispatch and the evidence survives.

  - **Controller side** (`SubprocessEngine`): a client proxy exposing the
    engine's EXACT surface (`submit`/`cancel`/`release`/`step`/`run`/`drain`/
    `close`, `results`/`pending`/`load`/`queue_depth`/`stats`/`warm_inserts`,
    assignable `params`), so `router.Router` routes over subprocess workers
    with ZERO routing changes — `make_subprocess_factory` plugs into
    `ReplicaSet.engine_factory` and the health machine's existing
    eject/rebuild/rejoin path becomes real process supervision: a SIGKILLed
    worker surfaces as `WorkerGone` from `step()`, the router ejects it, and
    the rebuild spawns a fresh warm process.

Everything on the wire is host scalars and token ids; params move by file
handoff (`save_pytree` -> path -> worker `load_pytree`, digest-verified
end-to-end), never through frames.

PR 20 lifts the same frame protocol onto TCP sockets (`SocketTransport` +
`python -m accelerate_tpu.worker --listen HOST:PORT`) and makes TRANSPORT
failure a first-class fault distinct from worker death: a torn frame or missed
deadline on a reconnectable transport parks the client proxy in a
`reconnecting` state (capped exponential backoff + jitter, budgeted by
`reconnect_deadline_s`), re-runs the registration handshake under a bumped
epoch, and reconciles in-flight streams against the worker's retained
per-request state — never-streamed requests re-dispatch, streamed requests
resume from the retained tail or surface `finish_reason=replica_lost`. Only an
exhausted reconnect budget escalates to the old behavior: `WorkerGone`, eject,
respawn.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import select
import signal
import socket
import struct
import subprocess
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .logging import get_logger

logger = get_logger(__name__)

#: Env var carrying the worker's fleet index to the subprocess (the chaos
#: `path_pattern: "worker_N"` targeting token derives from it).
WORKER_ID_ENV = "ACCELERATE_TPU_WORKER_ID"
#: Env var naming the shared append-only chaos journal file workers record
#: injections into BEFORE the damage lands (a SIGKILL must not erase the
#: evidence that it fired) — and read back on restart so a per-process
#: re-armed plan cannot livelock by re-killing at the same trigger.
CHAOS_JOURNAL_ENV = "ACCELERATE_TPU_CHAOS_JOURNAL"

#: Hard ceiling on one frame's payload. Tokens and host scalars only — params
#: move by file handoff — so anything near this is a protocol violation, not a
#: big message.
MAX_FRAME_BYTES = 64 << 20

#: Default worker-side heartbeat: a controller silent for this long means the
#: worker is orphaned (controller crashed without close()) and exits.
DEFAULT_HEARTBEAT_S = 120.0

#: Exit code a worker uses when it terminates itself (orphaned / torn pipe),
#: distinguishing self-termination from a crash in supervision logs.
ORPHANED_EXIT_CODE = 17

#: Frame-protocol version carried in the socket registration handshake; a
#: mismatched controller/worker pair is rejected before any op flows.
PROTOCOL_VERSION = 1


class FrameError(RuntimeError):
    """A malformed frame: oversized length prefix or undecodable payload (a
    protocol bug or corrupted stream, NOT a dead peer)."""


class FrameTimeout(RuntimeError):
    """No complete frame arrived inside the deadline: the peer is hung (or
    stalled past its budget) — the caller decides whether that is fatal."""


class WorkerGone(RuntimeError):
    """The peer's stream ended (EOF / broken pipe), cleanly or mid-frame: the
    process on the other side is dead. Escapes `SubprocessEngine.step()` so the
    router's replica-death handling (eject -> rebuild -> rejoin) takes over."""


def _fileno(stream) -> int:
    return stream if isinstance(stream, int) else stream.fileno()


def _frame_ctx(peer: Optional[str], op: Optional[str]) -> str:
    """Diagnostic suffix naming the peer and the op in flight — a partition
    post-mortem must say WHICH worker's WHICH request tore, not just that
    bytes stopped."""
    parts = []
    if peer:
        parts.append(f"peer={peer}")
    if op:
        parts.append(f"op={op}")
    return f" [{' '.join(parts)}]" if parts else ""


def _read_exact(fd: int, n: int, deadline: Optional[float], what: str,
                ctx: str = "") -> bytes:
    """Read exactly `n` bytes from `fd`, honoring an absolute monotonic
    deadline. EOF before `n` bytes is a dead peer (`WorkerGone`) — torn frames
    included; a deadline miss is `FrameTimeout`. Every message carries the
    bytes-read-so-far plus the peer/op context."""
    chunks: List[bytes] = []
    got = 0
    while got < n:
        if deadline is not None:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise FrameTimeout(
                    f"timed out waiting for {what} ({got}/{n} bytes){ctx}"
                )
            ready, _, _ = select.select([fd], [], [], remaining)
            if not ready:
                raise FrameTimeout(
                    f"timed out waiting for {what} ({got}/{n} bytes){ctx}"
                )
        chunk = os.read(fd, n - got)
        if not chunk:
            raise WorkerGone(
                f"peer closed the stream mid-{what} ({got}/{n} bytes){ctx}"
                if got else f"peer closed the stream{ctx}"
            )
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


def send_frame(stream, obj: Dict[str, Any], timeout_s: Optional[float] = None, *,
               peer: Optional[str] = None, op: Optional[str] = None) -> None:
    """Write one length-prefixed JSON frame. Raises `WorkerGone` when the peer
    end of the pipe/socket is closed, `FrameError` for oversized payloads, and
    — when `timeout_s` bounds the write (mandatory on socket transports, where
    a zero-window peer can stall a blocking write forever) — `FrameTimeout`
    on a missed send deadline."""
    ctx = _frame_ctx(peer, op)
    payload = json.dumps(obj, separators=(",", ":")).encode("utf-8")
    if len(payload) > MAX_FRAME_BYTES:
        raise FrameError(f"frame of {len(payload)} bytes exceeds MAX_FRAME_BYTES{ctx}")
    data = struct.pack(">I", len(payload)) + payload
    fd = _fileno(stream)
    deadline = None if timeout_s is None else time.monotonic() + float(timeout_s)
    view = memoryview(data)
    sent = 0
    while view:
        if deadline is not None:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise FrameTimeout(
                    f"timed out sending frame ({sent}/{len(data)} bytes){ctx}"
                )
            _, writable, _ = select.select([], [fd], [], remaining)
            if not writable:
                raise FrameTimeout(
                    f"timed out sending frame ({sent}/{len(data)} bytes){ctx}"
                )
        try:
            written = os.write(fd, view)
        except (BrokenPipeError, OSError) as exc:
            raise WorkerGone(
                f"peer pipe closed during send ({sent}/{len(data)} bytes){ctx}: {exc!r}"
            ) from exc
        view = view[written:]
        sent += written


def recv_frame(stream, timeout_s: Optional[float], *,
               peer: Optional[str] = None, op: Optional[str] = None) -> Dict[str, Any]:
    """Read one frame. `timeout_s` is the heartbeat deadline for the WHOLE
    frame — pass the peer's liveness budget, never None in a long-lived loop
    (TPU116). Raises `FrameTimeout` / `WorkerGone` / `FrameError`, each
    tagged with the peer identity and op in flight when given."""
    ctx = _frame_ctx(peer, op)
    fd = _fileno(stream)
    deadline = None if timeout_s is None else time.monotonic() + float(timeout_s)
    header = _read_exact(fd, 4, deadline, "frame header", ctx)
    (length,) = struct.unpack(">I", header)
    if length > MAX_FRAME_BYTES:
        raise FrameError(f"frame length {length} exceeds MAX_FRAME_BYTES{ctx}")
    payload = _read_exact(fd, length, deadline, "frame payload", ctx)
    try:
        return json.loads(payload.decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as exc:
        raise FrameError(f"undecodable frame payload{ctx}: {exc}") from exc


# ------------------------------------------------------------------ wire codecs
def request_to_wire(request) -> Dict[str, Any]:
    return {
        "request_id": int(request.request_id),
        "input_ids": [int(t) for t in np.asarray(request.input_ids).reshape(-1)],
        "max_new_tokens": int(request.max_new_tokens),
        "temperature": float(request.temperature),
        "repetition_penalty": float(request.repetition_penalty),
        "eos_token_id": None if request.eos_token_id is None else int(request.eos_token_id),
        "arrival_time": float(request.arrival_time),
        "deadline_s": None if request.deadline_s is None else float(request.deadline_s),
        "tenant": getattr(request, "tenant", None),
        "priority": int(getattr(request, "priority", 0)),
    }


def request_from_wire(data: Dict[str, Any]):
    from .serving import Request

    return Request(
        request_id=int(data["request_id"]),
        input_ids=np.asarray(data["input_ids"], np.int32),
        max_new_tokens=int(data["max_new_tokens"]),
        temperature=float(data.get("temperature", 1.0)),
        repetition_penalty=float(data.get("repetition_penalty", 1.0)),
        eos_token_id=data.get("eos_token_id"),
        arrival_time=float(data.get("arrival_time", 0.0)),
        deadline_s=data.get("deadline_s"),
        tenant=data.get("tenant"),
        priority=int(data.get("priority", 0)),
    )


def result_to_wire(result) -> Dict[str, Any]:
    return {
        "request_id": int(result.request_id),
        "tokens": [int(t) for t in result.tokens],
        "finished": bool(result.finished),
        "finish_reason": result.finish_reason,
        "error": result.error,
    }


#: Engine exception -> wire kind; the client re-raises the same type, so the
#: router's QueueFull/EngineClosed handling works unchanged out of process.
_ERROR_KINDS = ("queue_full", "engine_closed", "value_error", "key_error", "runtime_error")


def _error_reply(exc: BaseException) -> Dict[str, Any]:
    from .serving import EngineClosed, QueueFull

    if isinstance(exc, QueueFull):
        kind = "queue_full"
    elif isinstance(exc, EngineClosed):
        kind = "engine_closed"
    elif isinstance(exc, ValueError):
        kind = "value_error"
    elif isinstance(exc, KeyError):
        kind = "key_error"
    else:
        kind = "runtime_error"
    return {"ok": False, "kind": kind, "error": str(exc) or repr(exc)}


def _raise_from_reply(reply: Dict[str, Any]):
    from .serving import EngineClosed, QueueFull

    kind = reply.get("kind", "runtime_error")
    message = reply.get("error", "worker error")
    if kind == "queue_full":
        raise QueueFull(message)
    if kind == "engine_closed":
        raise EngineClosed(message)
    if kind == "value_error":
        raise ValueError(message)
    if kind == "key_error":
        raise KeyError(message)
    raise RuntimeError(message)


# ------------------------------------------------------------------ model specs
def spec_for_model(model, params_path: Optional[str] = None,
                   params_digest: Optional[str] = None) -> Dict[str, Any]:
    """Serialize a live Model bundle into a worker-buildable JSON spec: the
    family + config dataclass fields, plus the path of a `save_pytree`'d params
    file. Params ALWAYS move by file — a worker must serve the controller's
    exact weights (token parity), never a re-derived init. `params_digest`
    (the file's SHA-256, PR 2 manifest machinery) makes the handoff safe
    across hosts: a worker on another machine verifies it read the exact
    bytes the controller wrote, not a torn or stale object at the same path."""
    from . import models

    # Serving needs the slot cache: only the families whose config says so (`ServedConfig`).
    served = {f: cls for f, cls in models.CONFIG_BY_FAMILY.items() if issubclass(cls, models.ServedConfig)}
    family = next((f for f, cls in served.items() if type(getattr(model.module, "config", None)) is cls), None)
    if family is None:
        raise ValueError(
            f"{type(model.module).__name__} has no subprocess-worker family mapping; "
            f"known: {sorted(served)}"
        )
    return {
        "family": family,
        "config": dataclasses.asdict(model.module.config),
        "params_path": params_path,
        "params_digest": params_digest,
    }


def build_model_from_spec(spec: Dict[str, Any]):
    """Worker-side model construction. Accepts either a named registry model
    (`{"name": "llama-tiny"}`) or a family+config spec from `spec_for_model`;
    a `params_path` (when present) replaces the init params wholesale."""
    from . import models

    if "name" in spec:
        model = models.create_named_model(spec["name"], seq_len=int(spec.get("seq_len", 8)))
    else:
        family = models.PUBLISHED_MODEL_TYPES.get(spec["family"], spec["family"])  # a published `model_type` will do
        create = models.CREATE_BY_FAMILY.get(family)
        if create is None:
            raise ValueError(f"unknown model family {family!r} in worker spec")
        config = models.CONFIG_BY_FAMILY[family](**spec["config"])
        # Tiny init seq_len: the real params arrive via params_path below, so
        # the throwaway init should cost as little as possible.
        seq_len = int(spec.get("seq_len", 8))
        model = create(config, seq_len=seq_len)
    params_path = spec.get("params_path")
    if params_path:
        _verify_params_digest(params_path, spec.get("params_digest"))
        model.params = _load_params_on_device(params_path)
    return model


def _verify_params_digest(path: str, digest: Optional[str]) -> None:
    """End-to-end digest check for the params file handoff: the controller
    names the SHA-256 it wrote, the worker refuses to serve anything else.
    (`load_pytree` already verifies payload-vs-manifest; this closes the
    cross-host gap where the PATH resolves to different bytes.)"""
    if not digest:
        return
    from .checkpointing import file_sha256

    actual = file_sha256(path)
    if actual != digest:
        raise ValueError(
            f"params digest mismatch for {path}: controller expects "
            f"{digest[:12]}..., file hashes to {actual[:12]}... — refusing to "
            "serve unverified weights"
        )


def _load_params_on_device(path: str):
    """`load_pytree` returns numpy leaves "placed by the caller" — place them
    NOW: params left as numpy would ride every dispatch as an implicit
    host-to-device transfer (a per-step re-upload the worker's own armed
    TraceGuard rightly rejects)."""
    import jax

    from .checkpointing import load_pytree

    return jax.tree_util.tree_map(jax.device_put, load_pytree(path))


# ------------------------------------------------------------------ worker side
class EngineHost:
    """Executes protocol ops against one `ContinuousBatcher`. Pure translation:
    every engine exception maps to a typed error reply, every reply carries the
    load/queue-depth scalars the controller mirrors for routing."""

    def __init__(self, engine, worker_id: int = 0, guard=None):
        self.engine = engine
        self.worker_id = int(worker_id)
        self.guard = guard
        #: Result ids already shipped in a `finished` list (step/drain replies
        #: carry only the delta; release forgets).
        self._reported: set = set()

    # ---- op implementations ----
    def _load_view(self) -> Dict[str, Any]:
        return {
            "load": int(self.engine.load),
            "queue_depth": int(self.engine.queue_depth),
            "pending": bool(self.engine.pending),
        }

    def _finished_delta(self) -> List[Dict[str, Any]]:
        out = []
        for rid, result in self.engine.results.items():
            if result.finished and rid not in self._reported:
                self._reported.add(rid)
                out.append(result_to_wire(result))
        return out

    def _worker_stats(self) -> Dict[str, Any]:
        stats = dict(self.engine.stats)
        stats["worker"] = {
            "pid": os.getpid(),
            "worker_id": self.worker_id,
            "trace_counts": dict(self.engine.trace_counts),
            "guard": None if self.guard is None else {
                "recompiles": int(self.guard.total_recompiles),
                "host_transfers": int(self.guard.host_transfers),
            },
        }
        return stats

    def handle(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        op = msg.get("op")
        try:
            if op == "ping":
                return {"ok": True, "pid": os.getpid(), **self._load_view()}
            if op == "submit":
                request = request_from_wire(msg["request"])
                self.engine.submit(request)
                return {"ok": True, **self._load_view()}
            if op == "cancel":
                rid = int(msg["request_id"])
                cancelled = self.engine.cancel(rid)
                return {
                    "ok": True,
                    "cancelled": bool(cancelled),
                    "result": result_to_wire(self.engine.results[rid]),
                    **self._load_view(),
                }
            if op == "release":
                rid = int(msg["request_id"])
                result = self.engine.release(rid)
                self._reported.discard(rid)
                return {"ok": True, "result": result_to_wire(result)}
            if op == "step":
                events = self.engine.step()
                return {
                    "ok": True,
                    "events": [[int(rid), [int(t) for t in toks]] for rid, toks in events],
                    "finished": self._finished_delta(),
                    **self._load_view(),
                }
            if op == "drain":
                self.engine.drain()
                return {"ok": True, "finished": self._finished_delta(), **self._load_view()}
            if op == "warm":
                # Warmup pushes throwaway donated operands host->device by
                # design — suspend the armed guard (the 0/0 gate covers the
                # SERVING path, warm windows are excluded exactly like the
                # in-process benches arm after warm_inserts()).
                if self.guard is not None:
                    self.guard.__exit__(None, None, None)
                try:
                    buckets = self.engine.warm_inserts()
                finally:
                    if self.guard is not None:
                        self.guard.__enter__()
                return {"ok": True, "buckets": [int(b) for b in buckets]}
            if op == "stats":
                return {"ok": True, "stats": self._worker_stats(), **self._load_view()}
            if op == "guard_reset":
                # Benches warm the serving path first, then zero the guard so
                # the timed window's 0-recompile/0-transfer gate is exact.
                if self.guard is not None:
                    self.guard.reset()
                return {"ok": True, "armed": self.guard is not None}
            if op == "reconcile":
                # The stream-reconciliation snapshot a reconnecting controller
                # diffs its mirrors against: every request this engine knows,
                # with the FULL retained token tail (step replies ship deltas;
                # a reply lost in a partition is recovered from here).
                return {
                    "ok": True,
                    "pid": os.getpid(),
                    "worker_id": self.worker_id,
                    "requests": {
                        str(rid): result_to_wire(result)
                        for rid, result in self.engine.results.items()
                    },
                    **self._load_view(),
                }
            if op == "set_params":
                # The file handoff always carries RAW params; a quantized
                # engine (weight_dtype="int8" via engine_kwargs) re-quantizes
                # in its params setter — same seam as an in-process swap.
                # A digest (mandatory for cross-host swaps) is verified
                # against the actual file bytes before anything is served.
                _verify_params_digest(msg["path"], msg.get("digest"))
                self.engine.params = _load_params_on_device(msg["path"])
                return {"ok": True, "digest_verified": bool(msg.get("digest"))}
            if op == "close":
                self.engine.close()
                return {"ok": True, "finished": self._finished_delta()}
            return {"ok": False, "kind": "value_error", "error": f"unknown op {op!r}"}
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as exc:  # noqa: BLE001 — typed error replies, worker stays up
            return _error_reply(exc)


def _journal_line(path: str, entry: Dict[str, Any]) -> None:
    """Durably append one JSON line to the shared chaos/fleet journal.
    O_APPEND single-write + fsync: atomic against concurrent workers, durable
    against the SIGKILL that may follow immediately."""
    record = json.dumps(entry)
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    try:
        os.write(fd, (record + "\n").encode())
        os.fsync(fd)
    finally:
        os.close(fd)


class WorkerChaos:
    """Worker-side fault injection (the env-propagated half of the fleet
    sweeps): `fleet.worker_kill` delivers a REAL ``SIGKILL`` to this process at
    a matching step op, `fleet.worker_stall` sleeps past the controller's step
    timeout so the heartbeat machinery — not cooperation — detects the hang.

    Every firing is journaled (append + fsync) to the shared
    ``ACCELERATE_TPU_CHAOS_JOURNAL`` file BEFORE the damage lands, and the
    journal is read back at startup to pre-consume already-fired events — a
    restarted worker re-arms the same plan from env but must not re-kill
    itself at the same trigger (the PR 9 livelock lesson)."""

    def __init__(self, plan, worker_id: int, journal_path: Optional[str] = None,
                 tracer=None):
        from .chaos.injectors import ChaosSession

        self.session = ChaosSession(plan, tracer=tracer)
        self.token = f"worker_{int(worker_id)}"
        self.journal_path = journal_path
        if journal_path and os.path.exists(journal_path):
            for kind, count in self._journaled_counts(journal_path).items():
                self.session.preconsume(kind, count, path=self.token)
        if journal_path:
            self.session.on_inject = self._journal

    def _journaled_counts(self, path: str) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    entry = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail of a killed writer
                if entry.get("worker") == self.token:
                    counts[entry["kind"]] = counts.get(entry["kind"], 0) + 1
        return counts

    def _journal(self, entry: Dict[str, Any]):
        _journal_line(
            self.journal_path, {**entry, "worker": self.token, "pid": os.getpid()}
        )

    def arm(self, engine):
        from .chaos.injectors import ServingInjector

        ServingInjector(self.session).arm(engine)
        return self

    def poll(self, op: str):
        if op != "step":
            return
        for ev in self.session.fire("fleet.worker_stall", path=self.token):
            self.session.clock.sleep(float(ev.args.get("delay_s", 1.0)))
        for _ev in self.session.fire("fleet.worker_kill", path=self.token):
            os.kill(os.getpid(), signal.SIGKILL)
            time.sleep(5)  # unreachable — SIGKILL is unmaskable; belt for exotic platforms


def serve_worker(host: EngineHost, rstream, wstream, *,
                 heartbeat_deadline_s: Optional[float] = DEFAULT_HEARTBEAT_S,
                 chaos: Optional[WorkerChaos] = None) -> int:
    """The worker main loop: recv one frame, dispatch, reply. The heartbeat
    deadline bounds EVERY recv — a controller silent past it means this worker
    is orphaned (controller crashed without `close`), and the worker exits
    rather than leaking a process + device memory (analysis rule TPU116 flags
    loops built without this bound). Returns the process exit code."""
    while True:
        try:
            msg = recv_frame(rstream, timeout_s=heartbeat_deadline_s)
        except FrameTimeout:
            logger.warning(
                "worker %d: controller silent for %.1fs — exiting as orphaned",
                host.worker_id, heartbeat_deadline_s,
            )
            return ORPHANED_EXIT_CODE
        except (WorkerGone, FrameError) as exc:
            logger.warning("worker %d: control stream died: %r", host.worker_id, exc)
            return ORPHANED_EXIT_CODE
        if chaos is not None:
            chaos.poll(msg.get("op"))
        reply = host.handle(msg)
        try:
            send_frame(wstream, reply)
        except WorkerGone:
            return ORPHANED_EXIT_CODE
        if msg.get("op") == "close":
            return 0


def _parse_hostport(text: str) -> Tuple[str, int]:
    host, _, port = str(text).rpartition(":")
    if not host or not port.lstrip("-").isdigit() or int(port) < 0:
        raise ValueError(f"expected HOST:PORT, got {text!r}")
    return host, int(port)


def _accept_registration(host: EngineHost, conn, addr, current_epoch: int,
                         deadline_s: Optional[float],
                         ready_extra: Optional[Dict[str, Any]] = None):
    """One registration handshake on a freshly accepted connection. The
    controller opens with ``{"op": "register", "protocol", "epoch", ...}``;
    the worker validates the protocol version, rejects epochs that are not
    newer than the highest it has served (a stale controller link — e.g. a
    half-open socket's owner waking up after a reconnect — must not steal the
    stream), and replies with the ready frame: identity, protocol version,
    and the warm-state attestation. Returns ``(conn, epoch, peer)`` on
    success, None after closing a rejected connection."""
    peer = "%s:%s" % (addr[0], addr[1]) if addr else "?"
    budget = min(deadline_s, 30.0) if deadline_s is not None else 30.0
    try:
        msg = recv_frame(conn, timeout_s=budget, peer=peer, op="register")
    except (WorkerGone, FrameError, FrameTimeout) as exc:
        logger.warning("worker %d: registration from %s died: %r",
                       host.worker_id, peer, exc)
        conn.close()
        return None
    epoch = int(msg.get("epoch", 0))
    problem = None
    if msg.get("op") != "register":
        problem = ("value_error", f"expected a register frame, got op={msg.get('op')!r}")
    elif int(msg.get("protocol", -1)) != PROTOCOL_VERSION:
        problem = (
            "protocol_mismatch",
            f"protocol version {msg.get('protocol')!r} != worker's {PROTOCOL_VERSION}",
        )
    elif epoch <= current_epoch:
        problem = (
            "stale_epoch",
            f"registration epoch {epoch} is not newer than the served epoch "
            f"{current_epoch} — a stale controller link cannot steal the stream",
        )
    if problem is not None:
        kind, error = problem
        try:
            send_frame(conn, {"ok": False, "kind": kind, "error": error},
                       timeout_s=5.0, peer=peer, op="register")
        except (WorkerGone, FrameTimeout, FrameError):
            pass
        conn.close()
        logger.warning("worker %d: rejected registration from %s: %s",
                       host.worker_id, peer, error)
        return None
    ready = {
        "ok": True, "ready": True, "registered": True, "pid": os.getpid(),
        "worker_id": host.worker_id, "protocol": PROTOCOL_VERSION,
        "epoch": epoch, **(ready_extra or {}),
    }
    try:
        send_frame(conn, ready, timeout_s=budget, peer=peer, op="register")
    except (WorkerGone, FrameTimeout, FrameError) as exc:
        logger.warning("worker %d: ready frame to %s died: %r",
                       host.worker_id, peer, exc)
        conn.close()
        return None
    logger.info("worker %d: controller registered from %s (reconnect epoch %d)",
                host.worker_id, peer, epoch)
    return conn, epoch, peer


def serve_listener(host: EngineHost, listener, *,
                   heartbeat_deadline_s: Optional[float] = DEFAULT_HEARTBEAT_S,
                   chaos: Optional[WorkerChaos] = None,
                   journal_path: Optional[str] = None,
                   ready_extra: Optional[Dict[str, Any]] = None) -> int:
    """The socket-mode worker loop: accept a registration, then
    recv/dispatch/reply like `serve_worker` — but the ENGINE outlives any one
    connection. A torn link parks the worker back in accept-wait with its warm
    state, in-flight requests, and retained results intact; the controller
    re-registers under a bumped epoch and reconciles streams via the
    `reconcile` op. A registration arriving while a (possibly half-open)
    connection is live wins if and only if its epoch is newer — the select
    loop watches the listener alongside the active connection precisely so a
    reconnecting controller is never blocked behind a dead socket that the
    kernel still calls established. The heartbeat deadline spans connected
    AND disconnected time: a worker nobody has talked to for the whole window
    exits as orphaned (TPU116 discipline), never leaks. Re-registrations
    beyond the first epoch are journaled (``net.reregister``) so chaos
    invariants can reconcile controller reconnect counters against
    worker-side evidence."""
    epoch = 0
    conn = None
    peer = "unregistered"
    last_frame = time.monotonic()
    token = f"worker_{host.worker_id}"

    def _drop_conn(why: str):
        nonlocal conn
        if conn is not None:
            logger.warning(
                "worker %d: link to %s tore at reconnect epoch %d (%s) — "
                "awaiting re-registration", host.worker_id, peer, epoch, why,
            )
            try:
                conn.close()
            except OSError:
                pass
            conn = None

    while True:
        if heartbeat_deadline_s is not None:
            budget = heartbeat_deadline_s - (time.monotonic() - last_frame)
            if budget <= 0:
                logger.warning(
                    "worker %d: no controller traffic for %.1fs — exiting as orphaned",
                    host.worker_id, heartbeat_deadline_s,
                )
                return ORPHANED_EXIT_CODE
        else:
            budget = 1.0
        watch = [listener] if conn is None else [listener, conn]
        try:
            ready, _, _ = select.select(watch, [], [], min(budget, 1.0))
        except OSError:
            _drop_conn("select failed on the connection")
            continue
        if listener in ready:
            try:
                candidate, cand_addr = listener.accept()
            except OSError:
                continue
            candidate.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            accepted = _accept_registration(
                host, candidate, cand_addr, epoch, heartbeat_deadline_s,
                ready_extra=ready_extra,
            )
            if accepted is not None:
                _drop_conn("superseded by a newer registration epoch")
                conn, epoch, peer = accepted
                last_frame = time.monotonic()
                if epoch > 1 and journal_path:
                    _journal_line(journal_path, {
                        "kind": "net.reregister", "worker": token,
                        "epoch": epoch, "pid": os.getpid(),
                    })
            continue  # buffered op frames (if any) surface on the next select
        if conn is None or conn not in ready:
            continue
        try:
            msg = recv_frame(conn, timeout_s=heartbeat_deadline_s, peer=peer)
        except (WorkerGone, FrameError, FrameTimeout) as exc:
            _drop_conn(repr(exc))
            continue
        last_frame = time.monotonic()
        if chaos is not None:
            chaos.poll(msg.get("op"))
        reply = host.handle(msg)
        try:
            send_frame(conn, reply, timeout_s=heartbeat_deadline_s,
                       peer=peer, op=msg.get("op"))
        except (WorkerGone, FrameTimeout) as exc:
            _drop_conn(repr(exc))
            continue
        if msg.get("op") == "close":
            return 0


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser("accelerate-tpu serving worker")
    parser.add_argument("--spec-json", required=True,
                        help="model spec JSON (spec_for_model / {'name': ...})")
    parser.add_argument("--engine-json", default="{}",
                        help="ContinuousBatcher kwargs as JSON")
    parser.add_argument("--worker-id", type=int,
                        default=int(os.environ.get(WORKER_ID_ENV, "0")))
    parser.add_argument("--heartbeat-deadline-s", type=float, default=DEFAULT_HEARTBEAT_S)
    parser.add_argument("--no-warm", action="store_true",
                        help="skip pre-warming the insert ladder before reporting ready")
    parser.add_argument("--guard", action="store_true",
                        help="arm a record-mode TraceGuard post-warmup and report its "
                        "recompile/host-transfer counters in stats")
    parser.add_argument("--listen", default=None, metavar="HOST:PORT",
                        help="socket mode: bind HOST:PORT (port 0 = ephemeral), announce "
                        "the bound address on stdout, then serve registered controllers "
                        "over TCP instead of the stdio pipes")
    args = parser.parse_args(argv)

    # fd 1 belongs to the PROTOCOL: anything else printing to stdout (jax
    # warnings, user prints) would corrupt frames. Keep a private dup for
    # frames and point fd 1 (and sys.stdout) at stderr.
    ipc_out = os.dup(1)
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    ipc_in = 0

    from .serving import ContinuousBatcher
    from .telemetry.tracing import default_tracer
    from .utils.environment import configure_compile_cache

    configure_compile_cache()
    tracer = default_tracer()
    spec = json.loads(args.spec_json)
    engine_kwargs = json.loads(args.engine_json)
    span = tracer.start_span(
        "worker.lifetime", category="worker",
        worker_id=args.worker_id, pid=os.getpid(),
    )
    model = build_model_from_spec(spec)
    # The controller always threads its own max_queue through engine_kwargs;
    # a hand-launched worker still gets a bounded queue (TPU114 discipline).
    max_queue = engine_kwargs.pop("max_queue", 64)
    engine = ContinuousBatcher(model, tracer=tracer, max_queue=max_queue, **engine_kwargs)

    chaos = None
    from .chaos.plan import FaultPlan

    plan = FaultPlan.from_env()
    if plan is not None:
        chaos = WorkerChaos(
            plan, args.worker_id,
            journal_path=os.environ.get(CHAOS_JOURNAL_ENV), tracer=tracer,
        )
        chaos.arm(engine)

    warmed: List[int] = []
    if not args.no_warm:
        warmed = [int(b) for b in engine.warm_inserts()]

    guard = None
    if args.guard:
        from .analysis import TraceGuard

        guard = TraceGuard(
            transfer_guard="disallow", on_violation="record",
            name=f"worker-{args.worker_id}",
        )
        guard.__enter__()

    host = EngineHost(engine, worker_id=args.worker_id, guard=guard)
    if args.listen is not None:
        bind_host, bind_port = _parse_hostport(args.listen)
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        listener.bind((bind_host, bind_port))
        listener.listen(4)
        got_host, got_port = listener.getsockname()[:2]
        warm_attest = {"warm": not args.no_warm, "warmed": warmed}
        # The announce frame rides the original stdout pipe (or a terminal, for
        # a hand-launched worker): the controller — or the operator — learns the
        # bound address, then all protocol traffic moves to the socket.
        send_frame(ipc_out, {
            "ok": True, "listening": True, "host": got_host, "port": int(got_port),
            "pid": os.getpid(), "worker_id": args.worker_id,
            "protocol": PROTOCOL_VERSION, **warm_attest,
        })
        span.event("listening", host=got_host, port=int(got_port),
                   warmed_buckets=len(warmed))
        code = serve_listener(
            host, listener,
            heartbeat_deadline_s=args.heartbeat_deadline_s, chaos=chaos,
            journal_path=os.environ.get(CHAOS_JOURNAL_ENV),
            ready_extra=warm_attest,
        )
        listener.close()
        if guard is not None:
            guard.__exit__(None, None, None)
        span.annotate(exit_code=code).end()
        return code
    send_frame(ipc_out, {
        "ok": True, "ready": True, "pid": os.getpid(),
        "worker_id": args.worker_id, "warm": not args.no_warm, "warmed": warmed,
    })
    span.event("ready", warmed_buckets=len(warmed))
    code = serve_worker(
        host, ipc_in, ipc_out,
        heartbeat_deadline_s=args.heartbeat_deadline_s, chaos=chaos,
    )
    if guard is not None:
        guard.__exit__(None, None, None)
    span.annotate(exit_code=code).end()
    return code


# ------------------------------------------------------------------ controller side
class _PipeTransport:
    """The real transport: a spawned worker process with frame streams over
    its stdin/stdout pipes. Tests substitute a duck-typed fake."""

    def __init__(self, cmd: List[str], env: Dict[str, str], stderr=None,
                 worker_id: int = 0):
        self.peer = f"worker_{worker_id}/pipe"
        self._last_op: Optional[str] = None
        self.proc = subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=stderr, env=env, bufsize=0,
        )

    @property
    def pid(self) -> int:
        return self.proc.pid

    def alive(self) -> bool:
        return self.proc.poll() is None

    def send(self, obj: Dict[str, Any]):
        self._last_op = obj.get("op")
        send_frame(self.proc.stdin, obj, peer=self.peer, op=self._last_op)

    def recv(self, timeout_s: Optional[float]) -> Dict[str, Any]:
        return recv_frame(self.proc.stdout, timeout_s=timeout_s,
                          peer=self.peer, op=self._last_op)

    def kill(self):
        if self.alive():
            self.proc.kill()

    def close(self, timeout_s: float = 10.0):
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        try:
            self.proc.stdout.close()
        except OSError:
            pass


class SocketTransport:
    """Frame transport over TCP to a listening worker (`--listen HOST:PORT`).

    Duck-types `_PipeTransport` (pid/alive/send/recv/kill/close) so
    `SubprocessEngine` and every test fake stay interchangeable, and adds the
    transport-level verbs the reconnect machinery needs:

    - `handshake(timeout_s, resume=)` — dial, send a `register` frame carrying
      the protocol version and a monotonically increasing reconnect *epoch*,
      and validate the worker's ready/attestation reply. The epoch is what
      lets the worker reject a stale controller link (an older socket waking
      up after we already re-registered) without guessing from timing.
    - `reconnect(timeout_s)` — `handshake(resume=True)`: same wire exchange,
      but the caller treats the worker's retained state as authoritative and
      reconciles streams afterwards instead of assuming a fresh engine.
    - `sever()` — drop the socket WITHOUT touching the worker process. This is
      the partition seam: chaos injectors and the reconnect path both cut the
      link here, and worker death stays a separate, deliberate act (`kill`).

    `proc` is optional: a controller can adopt a worker it never spawned
    (cross-host fleet) — then pid/alive reflect the remote identity from the
    handshake and kill() can only sever the link."""

    def __init__(self, address: Tuple[str, int], proc=None, worker_id: int = 0):
        self.address = (str(address[0]), int(address[1]))
        self.proc = proc
        self.peer = "%s:%d/worker_%d" % (self.address[0], self.address[1], worker_id)
        self.epoch = 0
        self.sock = None
        self.ready_info: Dict[str, Any] = {}
        self._remote_pid: Optional[int] = None
        self._last_op: Optional[str] = None
        self._killed = False

    # ---- lifecycle ----
    def handshake(self, timeout_s: Optional[float], resume: bool = False) -> Dict[str, Any]:
        self.sever()
        self.epoch += 1
        deadline = None if timeout_s is None else time.monotonic() + timeout_s
        try:
            sock = socket.create_connection(self.address, timeout=timeout_s or 30.0)
        except OSError as exc:
            raise WorkerGone(
                f"dial {self.address[0]}:{self.address[1]} failed"
                f"{_frame_ctx(self.peer, 'register')}: {exc!r}"
            ) from exc
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        # Framing owns all deadlines via select(); a lingering socket-level
        # timeout would race it and surface as spurious BlockingIOError.
        sock.settimeout(None)
        remaining = (None if deadline is None
                     else max(0.001, deadline - time.monotonic()))
        try:
            send_frame(sock, {
                "op": "register", "protocol": PROTOCOL_VERSION,
                "epoch": self.epoch, "resume": bool(resume),
                "controller_pid": os.getpid(),
            }, timeout_s=remaining, peer=self.peer, op="register")
            ready = recv_frame(sock, timeout_s=remaining,
                               peer=self.peer, op="register")
        except (WorkerGone, FrameError, FrameTimeout):
            sock.close()
            raise
        if not ready.get("ok") or not ready.get("registered"):
            sock.close()
            raise WorkerGone(
                f"worker at {self.peer} refused registration "
                f"(epoch {self.epoch}): {ready.get('error', ready)!r}"
            )
        self.sock = sock
        self.ready_info = ready
        self._remote_pid = int(ready.get("pid", 0)) or None
        return ready

    def reconnect(self, timeout_s: Optional[float]) -> Dict[str, Any]:
        return self.handshake(timeout_s, resume=True)

    def sever(self):
        sock, self.sock = self.sock, None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass

    # ---- _PipeTransport surface ----
    @property
    def pid(self) -> Optional[int]:
        if self.proc is not None:
            return self.proc.pid
        return self._remote_pid

    def alive(self) -> bool:
        if self.proc is not None:
            return self.proc.poll() is None
        return not self._killed

    def send(self, obj: Dict[str, Any]):
        if self.sock is None:
            raise WorkerGone(
                f"transport link is severed{_frame_ctx(self.peer, obj.get('op'))}"
            )
        self._last_op = obj.get("op")
        send_frame(self.sock, obj, timeout_s=30.0, peer=self.peer, op=self._last_op)

    def recv(self, timeout_s: Optional[float]) -> Dict[str, Any]:
        if self.sock is None:
            raise WorkerGone(
                f"transport link is severed{_frame_ctx(self.peer, self._last_op)}"
            )
        return recv_frame(self.sock, timeout_s=timeout_s,
                          peer=self.peer, op=self._last_op)

    def kill(self):
        self._killed = True
        self.sever()
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()

    def close(self, timeout_s: float = 10.0):
        self.sever()
        if self.proc is not None:
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
            for stream in (self.proc.stdout, self.proc.stdin):
                if stream is not None:
                    try:
                        stream.close()
                    except OSError:
                        pass


class _TransportDown(WorkerGone):
    """Internal: the transport tore but the engine entered `reconnecting`
    instead of dying. Subclasses WorkerGone so callers that only know the old
    failure language (submit -> EngineClosed, release swallows) keep working;
    `step()` catches it specifically to drive the reconnect loop."""


def _refuse_spawn_beside_held_tpu(worker_id: int) -> None:
    """A TPU belongs to one process at a time. Once THIS process has initialised
    a TPU backend it holds the chip(s), and a worker spawned from it inherits
    the environment, needs the same chip, and fails or hangs at start-up — so
    refuse at once, with the reason. Nothing pins a worker to one chip of a
    multi-chip host yet (ROADMAP D8/R5); in-process replicas (`Router(
    out_of_process=False)`, one device each) are the multi-replica path on one
    host, and `connect=` adopts workers launched where they own their chips."""
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized() and jax.default_backend() == "tpu":
        raise RuntimeError(
            f"cannot spawn serving worker {worker_id}: this process already holds "
            "the TPU (a chip belongs to one process at a time), so a child that "
            "needs it would fail or hang. Use in-process replicas "
            "(out_of_process=False), or launch the workers first — from a parent "
            "that has not touched JAX — and adopt them with connect=HOST:PORT."
        )


class SubprocessEngine:
    """Client proxy for one out-of-process engine worker, exposing the exact
    `ContinuousBatcher` surface so `Router` needs no routing changes.

    The proxy mirrors request results locally (`results` holds real
    `RequestResult`s updated from step replies), mirrors the worker's
    load/queue-depth scalars for least-loaded routing, and converts transport
    death into the router's existing failure language: a dead/hung worker makes
    `step()` raise `WorkerGone` (-> `fail_replica` -> factory rebuild -> warm
    rejoin) and `submit()` raise `EngineClosed` (-> the router tries the next
    candidate replica).

    With `transport="socket"` (or `connect=` to adopt an already-listening
    worker on another host), a torn frame is a TRANSPORT fault, not a worker
    death: the proxy enters `reconnecting`, re-handshakes under capped
    exponential backoff + jitter budgeted by `reconnect_deadline_s`, and
    reconciles in-flight streams against the worker's retained per-request
    state — never-streamed requests re-dispatch, streamed requests resume from
    the worker's tail or finish `replica_lost`; only an exhausted budget
    escalates to the old WorkerGone/respawn path."""

    def __init__(
        self,
        spec: Dict[str, Any],
        engine_kwargs: Optional[Dict[str, Any]] = None,
        worker_id: int = 0,
        *,
        warm: bool = True,
        guard: bool = False,
        heartbeat_deadline_s: float = DEFAULT_HEARTBEAT_S,
        step_timeout_s: float = 120.0,
        start_timeout_s: float = 600.0,
        env: Optional[Dict[str, str]] = None,
        stderr=None,
        python: Optional[str] = None,
        transport: str = "pipe",
        connect: Optional[str] = None,
        reconnect_deadline_s: Optional[float] = None,
        reconnect_backoff_s: float = 0.05,
        reconnect_backoff_cap_s: float = 2.0,
        _transport=None,
    ):
        from .serving import RequestResult  # noqa: F401 — re-exported surface

        if transport not in ("pipe", "socket"):
            raise ValueError(f"transport must be 'pipe' or 'socket', got {transport!r}")
        if connect is not None:
            transport = "socket"
        self.spec = dict(spec)
        self.engine_kwargs = dict(engine_kwargs or {})
        self.worker_id = int(worker_id)
        self.max_queue = self.engine_kwargs.get("max_queue")
        self.step_timeout_s = float(step_timeout_s)
        self.transport_kind = transport
        if reconnect_deadline_s is None and transport == "socket":
            reconnect_deadline_s = 10.0
        self.reconnect_deadline_s = reconnect_deadline_s
        self.reconnect_backoff_s = float(reconnect_backoff_s)
        self.reconnect_backoff_cap_s = float(reconnect_backoff_cap_s)
        self.results: Dict[int, Any] = {}
        self.trace_guard = None  # surface parity; guards run worker-side
        self._dead = False
        self._closed = False
        self._load = 0
        self._queue_depth = 0
        self._worker_pending = False
        self._stats_cache: Dict[str, Any] = {}
        self._params_dir: Optional[str] = None
        self._params_seq = 0
        # --- reconnect state machine ---
        self.reconnects = 0  # successful re-handshakes over this proxy's life
        self._reconnecting = False
        self._in_reconcile = False
        self._rc_since = 0.0
        self._rc_attempts = 0
        self._rc_next = 0.0
        self._rc_cause: Optional[str] = None
        self._rc_last_err: Optional[str] = None
        self._rc_pending_events: List[Tuple[int, List[int]]] = []
        self._requests_wire: Dict[int, Dict[str, Any]] = {}
        self._cancel_after_reconnect: set = set()
        # --- telemetry (wired lazily via attach_telemetry) ---
        self._registry = None
        self._tracer = None
        self._replica_label = str(self.worker_id)
        self._m_reconnects = None
        self._m_rtt = None
        self._m_reconnecting = None
        self._rc_span = None
        if _transport is not None:
            self.transport = _transport
        elif connect is not None:
            self.transport = SocketTransport(
                _parse_hostport(connect), proc=None, worker_id=self.worker_id
            )
        else:
            _refuse_spawn_beside_held_tpu(self.worker_id)
            run_env = dict(os.environ if env is None else env)
            run_env[WORKER_ID_ENV] = str(self.worker_id)
            pkg_parent = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            run_env["PYTHONPATH"] = pkg_parent + os.pathsep + run_env.get("PYTHONPATH", "")
            cmd = [
                python or sys.executable, "-m", "accelerate_tpu.worker",
                "--spec-json", json.dumps(self.spec),
                "--engine-json", json.dumps(self.engine_kwargs),
                "--worker-id", str(self.worker_id),
                "--heartbeat-deadline-s", str(heartbeat_deadline_s),
            ]
            if not warm:
                cmd.append("--no-warm")
            if guard:
                cmd.append("--guard")
            if transport == "socket":
                cmd += ["--listen", "127.0.0.1:0"]
                proc = subprocess.Popen(
                    cmd, stdin=subprocess.DEVNULL, stdout=subprocess.PIPE,
                    stderr=stderr, env=run_env, bufsize=0,
                )
                try:
                    announce = recv_frame(
                        proc.stdout, timeout_s=start_timeout_s,
                        peer=f"worker_{self.worker_id}/announce", op="announce",
                    )
                except (WorkerGone, FrameTimeout, FrameError) as exc:
                    proc.kill()
                    proc.wait()
                    raise WorkerGone(
                        f"worker {self.worker_id} never announced a listen address: {exc}"
                    ) from exc
                if not announce.get("listening"):
                    proc.kill()
                    proc.wait()
                    raise WorkerGone(
                        f"worker {self.worker_id} announce frame malformed: {announce}"
                    )
                self.transport = SocketTransport(
                    (announce["host"], int(announce["port"])),
                    proc=proc, worker_id=self.worker_id,
                )
            else:
                self.transport = _PipeTransport(
                    cmd, env=run_env, stderr=stderr, worker_id=self.worker_id
                )
        handshake = getattr(self.transport, "handshake", None)
        try:
            if handshake is not None:
                self.ready_info = handshake(timeout_s=start_timeout_s)
            else:
                self.ready_info = self.transport.recv(timeout_s=start_timeout_s)
        except (WorkerGone, FrameTimeout, FrameError) as exc:
            self._mark_dead()
            raise WorkerGone(f"worker {self.worker_id} never became ready: {exc}") from exc
        if not (self.ready_info.get("ready") or self.ready_info.get("registered")):
            self._mark_dead()
            raise WorkerGone(f"worker {self.worker_id} handshake failed: {self.ready_info}")

    # ---- transport plumbing ----
    @property
    def pid(self) -> Optional[int]:
        return getattr(self.transport, "pid", None)

    def _mark_dead(self):
        self._dead = True
        kill = getattr(self.transport, "kill", None)
        if kill is not None:
            try:
                kill()
            except OSError:
                pass

    def _call(self, msg: Dict[str, Any], timeout_s: Optional[float] = None) -> Dict[str, Any]:
        if self._dead:
            raise WorkerGone(f"worker {self.worker_id} process is gone")
        if self._reconnecting and not self._in_reconcile:
            raise _TransportDown(
                f"worker {self.worker_id} transport is reconnecting "
                f"(attempt {self._rc_attempts}, cause: {self._rc_cause})"
            )
        op = msg.get("op")
        t0 = time.perf_counter()
        try:
            self.transport.send(msg)
            reply = self.transport.recv(
                timeout_s=self.step_timeout_s if timeout_s is None else timeout_s
            )
        except FrameTimeout as exc:
            self._count_frame_error("timeout")
            if self._maybe_enter_reconnecting(exc, op):
                raise _TransportDown(str(exc)) from exc
            # A hung worker is indistinguishable from a dead one from the
            # controller's side — kill it so the rebuild path can take over.
            self._mark_dead()
            raise WorkerGone(
                f"worker {self.worker_id} missed its step deadline: {exc}"
            ) from exc
        except (WorkerGone, FrameError) as exc:
            self._count_frame_error(
                "torn" if isinstance(exc, WorkerGone) else "frame_error"
            )
            if self._maybe_enter_reconnecting(exc, op):
                raise _TransportDown(str(exc)) from exc
            self._mark_dead()
            raise WorkerGone(f"worker {self.worker_id} died: {exc}") from exc
        if self._m_rtt is not None:
            self._m_rtt.observe(time.perf_counter() - t0)
        if not reply.get("ok"):
            _raise_from_reply(reply)
        self._load = int(reply.get("load", self._load))
        self._queue_depth = int(reply.get("queue_depth", self._queue_depth))
        self._worker_pending = bool(reply.get("pending", self._worker_pending))
        return reply

    # ---- reconnect state machine ----
    @property
    def reconnecting(self) -> bool:
        return self._reconnecting

    def _can_reconnect(self) -> bool:
        if self.reconnect_deadline_s is None or self._closed or self._dead:
            return False
        if not hasattr(self.transport, "reconnect"):
            return False
        alive = getattr(self.transport, "alive", None)
        # A locally spawned worker whose PROCESS exited cannot be re-dialed —
        # that is genuine death, not a transport fault.
        return alive() if alive is not None else True

    def _maybe_enter_reconnecting(self, exc: BaseException, op: Optional[str]) -> bool:
        if not self._can_reconnect():
            return False
        self._enter_reconnecting(exc, op)
        return True

    def _enter_reconnecting(self, exc: BaseException, op: Optional[str]):
        sever = getattr(self.transport, "sever", None)
        if sever is not None:
            sever()
        if self._reconnecting:
            return  # a tear mid-reconcile keeps the ORIGINAL budget anchor
        now = time.monotonic()
        self._reconnecting = True
        self._rc_since = now
        self._rc_attempts = 0
        self._rc_next = now  # first attempt fires immediately
        self._rc_cause = f"{type(exc).__name__} during op={op}: {exc}"
        self._rc_last_err = None
        if self._m_reconnecting is not None:
            self._m_reconnecting.set(1.0)
        if self._tracer is not None:
            self._rc_span = self._tracer.start_span(
                "serve.reconnect", category="serve",
                replica=self._replica_label, worker_id=self.worker_id,
                cause=self._rc_cause,
            )
        logger.warning(
            "worker %d: transport tore (%s) — entering reconnecting "
            "(deadline %.1fs)", self.worker_id, self._rc_cause,
            self.reconnect_deadline_s,
        )

    def _finish_reconnect(self, outcome: str):
        self._reconnecting = False
        if outcome == "reconnected":
            self.reconnects += 1
            if self._m_reconnects is not None:
                self._m_reconnects.inc()
        if self._m_reconnecting is not None:
            self._m_reconnecting.set(0.0)
        if self._rc_span is not None:
            self._rc_span.annotate(
                outcome=outcome, attempts=self._rc_attempts,
                waited_s=round(time.monotonic() - self._rc_since, 3),
            ).end()
            self._rc_span = None
        logger.warning(
            "worker %d: reconnect %s after %d attempt(s)",
            self.worker_id, outcome, self._rc_attempts,
        )

    def _reconnect_step(self) -> List[Tuple[int, List[int]]]:
        """One non-blocking tick of the reconnect loop, driven by `step()`.
        Returns resumed stream events on success, [] while backing off; raises
        WorkerGone only when the reconnect budget is exhausted (escalating to
        the router's existing death/respawn path)."""
        now = time.monotonic()
        # Exhaustion requires at least one REAL attempt: a controller that
        # blocked past the whole budget (e.g. a synchronous respawn elsewhere
        # in the fleet) must not condemn a healthy link it never re-dialed.
        if self._rc_attempts >= 1 and now - self._rc_since > self.reconnect_deadline_s:
            self._finish_reconnect("exhausted")
            self._mark_dead()
            raise WorkerGone(
                f"worker {self.worker_id} reconnect budget exhausted: "
                f"{self._rc_attempts} attempt(s) over {self.reconnect_deadline_s:.1f}s "
                f"(cause: {self._rc_cause}; last error: {self._rc_last_err})"
            )
        if now < self._rc_next:
            return []
        self._rc_attempts += 1
        budget_left = self.reconnect_deadline_s - (now - self._rc_since)
        try:
            ready = self.transport.reconnect(
                timeout_s=max(0.05, min(5.0, budget_left))
            )
            self._in_reconcile = True
            try:
                self._reconcile_streams(ready)
            finally:
                self._in_reconcile = False
        except (WorkerGone, FrameError, FrameTimeout, OSError) as exc:
            backoff = min(
                self.reconnect_backoff_cap_s,
                self.reconnect_backoff_s * (2 ** (self._rc_attempts - 1)),
            ) * (0.5 + random.random() / 2)  # jitter: avoid fleet-wide lockstep
            self._rc_next = time.monotonic() + backoff
            self._rc_last_err = repr(exc)
            return []
        self._finish_reconnect("reconnected")
        events, self._rc_pending_events = self._rc_pending_events, []
        return events

    def _reconcile_streams(self, ready: Dict[str, Any]):
        """Reconcile local mirrors against the worker's retained per-request
        journal after a re-handshake. The contract: a stream is never
        duplicated and never silently truncated — requests the worker never
        saw (lost in a torn submit) re-dispatch verbatim IF nothing streamed
        yet; anything already streamed either resumes from the worker's
        retained tail (prefix-verified) or finishes `replica_lost`.

        Resumed tails accumulate in `_rc_pending_events` (not returned here):
        mirror extension is idempotent across a tear-during-reconcile retry,
        and `_reconnect_step` releases the events exactly once, on full
        success, so the router streams each token exactly once."""
        reply = self._call({"op": "reconcile"}, timeout_s=self.step_timeout_s)
        worker_view = {
            int(rid): rec for rid, rec in reply.get("requests", {}).items()
        }
        for rid, result in list(self.results.items()):
            queued_cancel = rid in self._cancel_after_reconnect
            if result.finished and not queued_cancel:
                continue
            rec = worker_view.get(rid)
            if rec is None:
                if result.finished:
                    continue  # locally cancelled; the worker never knew it
                wire = self._requests_wire.get(rid)
                if not result.tokens and wire is not None:
                    # Never streamed and unknown worker-side: the submit frame
                    # died in the partition — safe to re-dispatch.
                    try:
                        self._call({"op": "submit", "request": wire})
                    except (WorkerGone, FrameError, FrameTimeout):
                        raise  # transport tore again: retry the whole reconcile
                    except RuntimeError:
                        # Engine-side rejection (queue full, bad request): the
                        # request can't ride this replica anymore.
                        result.finished = True
                        result.finish_reason = "replica_lost"
                        result.finish_time = time.perf_counter()
                else:
                    result.finished = True
                    result.finish_reason = "replica_lost"
                    result.finish_time = time.perf_counter()
                continue
            worker_tokens = [int(t) for t in rec.get("tokens", ())]
            mine = [int(t) for t in result.tokens]
            if worker_tokens[: len(mine)] != mine:
                # The worker's journal does not extend what we streamed:
                # resuming would corrupt the stream — surface the loss.
                if not result.finished:
                    result.finished = True
                    result.finish_reason = "replica_lost"
                    result.finish_time = time.perf_counter()
                continue
            tail = worker_tokens[len(mine):]
            if tail and not result.finished:
                result.tokens.extend(tail)
                if result.first_token_time is None:
                    result.first_token_time = time.perf_counter()
                self._rc_pending_events.append((rid, tail))
            if rec.get("finished") and not result.finished:
                self._apply_finished([rec])
        # Cancels issued while the link was down: the mirrors already finished
        # "cancelled" locally; now actually stop the worker-side generation.
        for rid in sorted(self._cancel_after_reconnect):
            if rid in worker_view and not worker_view[rid].get("finished"):
                try:
                    self._call({"op": "cancel", "request_id": int(rid)})
                except (KeyError, ValueError):
                    pass
        self._cancel_after_reconnect.clear()

    def _count_frame_error(self, kind: str):
        if self._registry is not None:
            self._registry.counter(
                "transport_frame_errors_total",
                help="transport frame faults by kind (timeout/torn/frame_error)",
                labels={"kind": kind},
            ).inc()

    def attach_telemetry(self, registry, tracer=None, replica=None):
        """Wire the reconnect/transport instruments into a shared registry.
        Idempotent (the registry memoizes on (name, labels)); the router calls
        this for every engine it builds so cross-host replicas report
        `router_reconnects_total`, frame-error counts, RTTs, and the
        per-replica reconnecting gauge under one scrape."""
        self._registry = registry
        if tracer is not None:
            self._tracer = tracer
        if replica is not None:
            self._replica_label = str(replica)
        labels = {"replica": self._replica_label}
        if registry is not None:
            self._m_reconnects = registry.counter(
                "router_reconnects_total",
                help="successful transport re-handshakes (reconnect, not respawn)",
                labels=labels,
            )
            self._m_rtt = registry.histogram(
                "transport_rtt_seconds",
                help="frame round-trip time per protocol call", labels=labels,
            )
            self._m_reconnecting = registry.gauge(
                "router_replica_reconnecting",
                help="1 while the replica's transport is in the reconnecting state",
                labels=labels,
            )

    # ---- mirror maintenance ----
    def _apply_finished(self, records: List[Dict[str, Any]]):
        for record in records:
            result = self.results.get(int(record["request_id"]))
            if result is None or result.finished:
                continue
            result.tokens[:] = [int(t) for t in record["tokens"]]
            result.finished = True
            result.finish_reason = record.get("finish_reason")
            result.error = record.get("error")
            result.finish_time = time.perf_counter()

    # ---- engine surface ----
    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def pending(self) -> bool:
        # A dead worker with unfinished mirrors must look pending: the router
        # only discovers replica death by stepping it.
        unfinished = any(not r.finished for r in self.results.values())
        return unfinished or (self._worker_pending and not self._dead)

    @property
    def load(self) -> int:
        return self._load

    @property
    def queue_depth(self) -> int:
        return self._queue_depth

    @property
    def stats(self) -> Dict[str, Any]:
        if not self._dead and not self._closed:
            try:
                self._stats_cache = self._call({"op": "stats"})["stats"]
            except (WorkerGone, RuntimeError):
                pass
        return self._stats_cache

    @property
    def params(self):
        return None  # live params stay worker-side; the setter ships new ones

    @params.setter
    def params(self, value):
        if value is None:
            return
        from .checkpointing import save_pytree

        if self._params_dir is None:
            self._params_dir = tempfile.mkdtemp(prefix="accelerate_tpu_worker_params_")
        self._params_seq += 1
        path = os.path.join(self._params_dir, f"params_{self._params_seq}.npz")
        save_pytree(value, path)
        from .checkpointing import file_sha256

        # Digest-verified path handoff: across hosts the params file travels
        # by shared filesystem/object store, and the worker refuses to load
        # bytes that don't hash to what the controller shipped.
        self._call({"op": "set_params", "path": path,
                    "digest": file_sha256(path)})

    def submit(self, request) -> int:
        from .serving import EngineClosed, RequestResult

        if self._closed:
            raise EngineClosed("engine is closed")
        if self._dead:
            raise EngineClosed(f"worker {self.worker_id} process is gone")
        wire = request_to_wire(request)
        try:
            self._call({"op": "submit", "request": wire})
        except WorkerGone as exc:
            # The router's dispatch loop treats EngineClosed as "try the next
            # replica" (a reconnecting transport included — _TransportDown is
            # a WorkerGone); the death itself surfaces from the next step().
            raise EngineClosed(str(exc)) from exc
        self.results[request.request_id] = RequestResult(
            request.request_id, arrival_time=request.arrival_time
        )
        # Retained verbatim so a submit that streamed nothing before a
        # partition can safely re-dispatch during stream reconciliation.
        self._requests_wire[request.request_id] = wire
        return request.request_id

    def cancel(self, request_id: int) -> bool:
        result = self.results[request_id]  # KeyError for unknown ids, like the engine
        if result.finished:
            return False
        try:
            reply = self._call({"op": "cancel", "request_id": int(request_id)})
        except _TransportDown:
            # Link is down but the worker lives: finish the mirror cancelled
            # NOW (the caller's intent is immediate) and queue the worker-side
            # cancel for delivery right after stream reconciliation.
            self._cancel_after_reconnect.add(int(request_id))
            result.finished = True
            result.finish_reason = "cancelled"
            result.finish_time = time.perf_counter()
            return True
        except WorkerGone:
            # Worker died under the cancel: the mirror finishes cancelled
            # locally (partial tokens kept) — nothing can stream anymore.
            result.finished = True
            result.finish_reason = "cancelled"
            result.finish_time = time.perf_counter()
            return True
        # `cancelled: false` means the worker finished it first (a terminal
        # token raced our cancel out): adopt the worker's record verbatim.
        self._apply_finished([reply["result"]])
        return bool(reply["cancelled"])

    def release(self, request_id: int):
        result = self.results[request_id]
        if not result.finished:
            raise ValueError(f"request {request_id} is still in flight")
        if not self._dead and not self._closed:
            try:
                self._call({"op": "release", "request_id": int(request_id)})
            except (WorkerGone, KeyError, ValueError):
                pass
        del self.results[request_id]
        self._requests_wire.pop(request_id, None)
        self._cancel_after_reconnect.discard(request_id)
        return result

    def step(self) -> List[Tuple[int, List[int]]]:
        if self._closed:
            return []
        if self._reconnecting:
            return self._reconnect_step()
        try:
            reply = self._call({"op": "step"})
        except _TransportDown:
            # The tear happened on THIS call — drive the first reconnect
            # attempt immediately instead of burning a router cycle.
            return self._reconnect_step()
        events: List[Tuple[int, List[int]]] = []
        for rid, toks in reply.get("events", ()):
            rid = int(rid)
            toks = [int(t) for t in toks]
            result = self.results.get(rid)
            if result is not None and not result.finished:
                result.tokens.extend(toks)
                if result.first_token_time is None:
                    result.first_token_time = time.perf_counter()
            events.append((rid, toks))
        self._apply_finished(reply.get("finished", ()))
        return events

    def run(self, requests=None) -> Dict[int, np.ndarray]:
        for request in requests or ():
            self.submit(request)
        while self.pending:
            self.step()
            if self._reconnecting:
                time.sleep(0.005)  # pace the backoff wait instead of spinning
        return {rid: np.asarray(r.tokens, np.int32) for rid, r in self.results.items()}

    def drain(self) -> Dict[int, Any]:
        while self._reconnecting:
            self._reconnect_step()
            if self._reconnecting:
                time.sleep(min(0.05, max(0.0, self._rc_next - time.monotonic())) or 0.005)
        reply = self._call({"op": "drain"}, timeout_s=self.step_timeout_s * 10)
        self._apply_finished(reply.get("finished", ()))
        return self.results

    def warm_inserts(self) -> List[int]:
        return [int(b) for b in self._call({"op": "warm"})["buckets"]]

    def reset_guard(self) -> bool:
        """Zero the worker-side TraceGuard counters (spawned with guard=True):
        benches call this after warmup so the timed window's 0/0 gate is
        exact. Returns whether a guard is armed at all."""
        return bool(self._call({"op": "guard_reset"})["armed"])

    def terminate(self):
        """Hard shutdown for a replica being ejected: kill the worker process
        and reap it WITHOUT the cooperative close RPC (the worker may be the
        reason we are here — hung, or erroring every dispatch). The router's
        eject path calls this so a worker that failed via error replies (its
        transport still alive) can never linger as an orphan next to its
        replacement, holding device memory."""
        self._mark_dead()
        close = getattr(self.transport, "close", None)
        if close is not None:
            close()

    def close(self) -> Dict[int, Any]:
        if self._closed:
            return self.results
        if not self._dead:
            try:
                reply = self._call({"op": "close"})
                self._apply_finished(reply.get("finished", ()))
            except (WorkerGone, RuntimeError):
                pass
        for result in self.results.values():
            if not result.finished:
                result.finished = True
                result.finish_reason = "cancelled"
                result.finish_time = time.perf_counter()
        close = getattr(self.transport, "close", None)
        if close is not None:
            close()
        self._closed = True
        return self.results


def make_subprocess_factory(
    model=None,
    spec: Optional[Dict[str, Any]] = None,
    engine_kwargs: Optional[Dict[str, Any]] = None,
    *,
    workdir: Optional[str] = None,
    warm: bool = True,
    guard: bool = False,
    env: Optional[Dict[str, str]] = None,
    heartbeat_deadline_s: float = DEFAULT_HEARTBEAT_S,
    step_timeout_s: float = 120.0,
    start_timeout_s: float = 600.0,
    stderr_dir: Optional[str] = None,
    transport: str = "pipe",
    reconnect_deadline_s: Optional[float] = None,
    connect: Optional[Sequence[str]] = None,
) -> Callable[[int], SubprocessEngine]:
    """Build a `ReplicaSet.engine_factory` that spawns one warm subprocess
    worker per replica index. When a live `model` is given, its params are
    saved ONCE to `<workdir>/params.npz` and every worker (including restarts)
    loads that exact file — subprocess fleets are token-identical to in-process
    ones by construction. `stderr_dir` (default: the workdir) collects one
    append-mode `worker_<i>.stderr.log` per index, so restarted workers extend
    their predecessor's log instead of interleaving on the controller's tty.

    `connect=["HOST:PORT", ...]` adopts EXTERNALLY launched listener workers
    (`python -m accelerate_tpu.worker --listen HOST:PORT`) instead of spawning:
    replica `i` dials `connect[i % len(connect)]`, and a factory rebuild after
    worker death re-dials the same address — respawning the remote process is
    its own supervisor's job. Implies the socket transport; the spec's params
    path must be reachable on the worker's host (digest-verified on load)."""
    if (model is None) == (spec is None):
        raise ValueError("pass exactly one of model= or spec=")
    workdir = workdir or tempfile.mkdtemp(prefix="accelerate_tpu_fleet_")
    os.makedirs(workdir, exist_ok=True)
    if model is not None:
        from .checkpointing import file_sha256, save_pytree

        params_path = os.path.join(workdir, "params.npz")
        save_pytree(model.params, params_path)
        spec = spec_for_model(
            model, params_path=params_path,
            params_digest=file_sha256(params_path),
        )
    engine_kwargs = dict(engine_kwargs or {})
    stderr_dir = stderr_dir or workdir

    addresses = list(connect) if connect else None
    if addresses is not None:
        transport = "socket"

    def factory(index: int) -> SubprocessEngine:
        if addresses is not None:
            return SubprocessEngine(
                spec, engine_kwargs, worker_id=index,
                connect=addresses[index % len(addresses)],
                heartbeat_deadline_s=heartbeat_deadline_s,
                step_timeout_s=step_timeout_s,
                start_timeout_s=start_timeout_s,
                reconnect_deadline_s=reconnect_deadline_s,
            )
        log_path = os.path.join(stderr_dir, f"worker_{index}.stderr.log")
        stderr = open(log_path, "ab")
        try:
            return SubprocessEngine(
                spec, engine_kwargs, worker_id=index,
                warm=warm, guard=guard,
                heartbeat_deadline_s=heartbeat_deadline_s,
                step_timeout_s=step_timeout_s,
                start_timeout_s=start_timeout_s,
                env=env, stderr=stderr,
                transport=transport,
                reconnect_deadline_s=reconnect_deadline_s,
            )
        finally:
            stderr.close()  # the child holds its own copy of the fd

    factory.workdir = workdir
    factory.spec = spec
    factory.transport = transport
    factory.connect = addresses
    return factory


if __name__ == "__main__":
    sys.exit(main())
