"""Replicated serving fleet: a health-routed front-end over N engines.

A single `ContinuousBatcher` is one process-wide failure domain: a SIGKILL, a
hung dispatch, or a poisoned executable takes down ALL traffic. This module
splits the fleet from the engine:

  - `ReplicaSet` owns N engine workers (in-process `ContinuousBatcher`s by
    default; the `engine_factory` seam is where subprocess/mesh-spanning
    engines plug in) plus the per-replica **health state machine**::

        live -> degraded -> ejected -> rejoining -> live
                   ^------------------------------------'

    driven by heartbeats (a replica with work that stops finishing steps),
    queue-depth / step-latency signals (degraded), and consecutive dispatch
    failures (ejected). An ejected replica re-enters through a cooldown and a
    `rejoining` probation window before it is `live` again; a replica whose
    engine died outright is rebuilt from the factory on rejoin.

  - `Router` is the front-end with the SAME surface as `ContinuousBatcher`
    (`submit` / `cancel` / `step` / `run` / `drain` / `close` / `release`,
    `results`, `pending`, `stats`): least-loaded routing over the routable
    replicas with bounded per-replica backpressure (`max_queue` rides down to
    every engine; a fleet-wide full queue surfaces as `QueueFull`), a default
    per-request deadline (`default_deadline_s`) so no request can wait
    forever, and safe failure handling:

      * a request that NEVER streamed a token is re-dispatched to another
        replica (`router_retries_total`, bounded by `max_retries`);
      * a request that already emitted tokens is finished with
        ``finish_reason="replica_lost"`` — partial tokens kept, never a
        silently duplicated stream;
      * optional **TTFT hedging**: a request still queued (zero tokens) past
        the hedge threshold is duplicated onto a second replica; the first
        copy to stream wins, the loser is cancelled, and only the winner's
        tokens are ever forwarded. The threshold is a static `hedge_after_s`
        OR a live `hedge_quantile` of the router's own `serving_ttft_seconds`
        histogram (disabled below `hedge_min_samples` observations — no
        hedging off a cold histogram, no stale hand-tuned constant).

  - `swap_weights(params)` is the zero-downtime rolling deploy: one replica at
    a time is drained (unroutable, finishes its own work while the rest keep
    serving), its params are replaced in place (same pytree structure — no
    recompile; params are per-dispatch operands), and it rejoins before the
    next replica drains. The fleet never drops below N-1 serving capacity.

  - **Out-of-process workers** (`out_of_process=True`, or any
    `engine_factory` returning `worker.SubprocessEngine`s): each replica is a
    real OS process hosting one engine behind the length-prefixed JSON IPC in
    `accelerate_tpu.worker`. The health machine's existing eject/rebuild path
    becomes true process supervision — a SIGKILLed or hung worker surfaces as
    `WorkerGone` from `step()`, is ejected, and the factory respawns a fresh
    process that pre-warms its executables before taking traffic (rejoins
    WARM). The in-process default stays the fast path and the parity oracle.

  - **Autoscaling** (`min_replicas`/`max_replicas`): the fleet floats on the
    signals the health machine already computes — scale up on fleet queue
    depth per routable replica (or the TTFT histogram's p99 against
    `autoscale_ttft_target_s`), retire the newest idle replica after
    `idle_retire_s` of a fully idle fleet, one action per
    `autoscale_cooldown_s`, every transition journaled.

  - **Admission control** (`tenant_queue_limit`): with the fleet saturated,
    requests queue at the ROUTER in per-tenant bounded queues drained in
    priority-then-fair-share order (strict `Request.priority` first,
    round-robin across tenants at equal priority) — one tenant's burst
    degrades into bounded queueing + `QueueFull` for THAT tenant, not a
    fleet-wide rejection of everyone.

Everything here is host-side bookkeeping on host scalars — the device-facing
work stays inside each engine, and the router adds zero device syncs (the same
discipline `analysis` rule TPU114 lints the construction side of).

Telemetry: `router_retries_total`, `router_ejected_total`,
`router_hedges_total` / `router_hedge_wins_total`, per-replica state/load
gauges, and one `serve.route` span per request (the engine's `serve.request`
span stitches under it), all documented in docs/observability.md and
docs/serving.md.
"""

from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .logging import get_logger
from .serving import (
    FINISH_REASONS,
    ContinuousBatcher,
    EngineClosed,
    QueueFull,
    Request,
    RequestResult,
)
from .telemetry import MetricsRegistry
from .telemetry.tracing import default_tracer

logger = get_logger(__name__)

#: Env var `accelerate-tpu launch --replicas` exports: the fleet size a serving
#: script should build when it does not hard-code one (`Router(replicas=None)`).
SERVE_REPLICAS_ENV = "ACCELERATE_TPU_SERVE_REPLICAS"

#: Terminal finish reasons a Router result can carry: the engine set plus
#: `replica_lost` (the request's replica failed after it had already streamed
#: tokens — re-dispatching would duplicate output, so the router surfaces the
#: loss explicitly with the partial tokens kept).
ROUTER_FINISH_REASONS = FINISH_REASONS + ("replica_lost",)

#: Health states, in escalation order. `draining` is the rolling-swap state —
#: unroutable like `ejected`, but healthy and finishing its own work.
#: `retired` is terminal: an autoscaler-removed replica — engine closed (a
#: subprocess worker's process exits), never rejoins, never routed.
#: `reconnecting` is the transport-fault state (socket fleets): the worker
#: process is presumed alive but the link tore — unroutable while the engine
#: proxy re-handshakes under its backoff budget; heals back to `live` on
#: reconnect, escalates through the ordinary death path (WorkerGone ->
#: eject/rebuild) only when the budget exhausts.
REPLICA_STATES = ("live", "degraded", "ejected", "rejoining", "draining", "retired",
                  "reconnecting")
_STATE_CODE = {s: i for i, s in enumerate(REPLICA_STATES)}


class ReplicaLost(RuntimeError):
    """Internal marker for a replica-level failure (engine death)."""


def default_replicas() -> int:
    """Fleet size when the caller does not pass one: the launch env protocol
    (`launch --replicas N` -> ``ACCELERATE_TPU_SERVE_REPLICAS``), else 2."""
    raw = os.environ.get(SERVE_REPLICAS_ENV, "").strip()
    if raw.isdigit() and int(raw) >= 1:
        return int(raw)
    return 2


def _normalize_params(params_or_model) -> Dict[str, Any]:
    """Accept a params pytree or a Model bundle; return the engine-shaped
    ``{"params": ...}`` dict (`ContinuousBatcher.params` convention)."""
    params = getattr(params_or_model, "params", params_or_model)
    return params if "params" in params else {"params": params}


@dataclass
class Replica:
    """One engine worker plus its health bookkeeping (all host scalars)."""

    index: int
    engine: ContinuousBatcher
    state: str = "live"
    consecutive_failures: int = 0
    #: Engine is gone (process death / fatal dispatch): rejoin must rebuild.
    dead: bool = False
    #: Last time this replica finished a step (or went idle) successfully.
    last_ok: float = 0.0
    #: When the replica entered `ejected` (cooldown anchor).
    ejected_at: Optional[float] = None
    #: Router cycles survived in `rejoining` (probation counter).
    probation_ok: int = 0
    #: When degraded pressure was last observed (recovery anchor).
    unhealthy_at: Optional[float] = None

    @property
    def routable(self) -> bool:
        return self.state in ("live", "degraded", "rejoining")


class ReplicaSet:
    """Owns the N engine workers and the per-replica health state machine.

    The set never routes — that is the `Router`'s job — it answers "which
    replicas may take work, in what preference order" and performs the state
    transitions (eject / cooldown / probation / rejoin / drain-for-swap),
    journaling every transition to `state_log` with the clock the Router
    shares, so chaos invariants can audit routing decisions against health
    history.
    """

    def __init__(
        self,
        model,
        replicas: int,
        engine_kwargs: Optional[Dict[str, Any]] = None,
        engine_factory: Optional[Callable[[int], ContinuousBatcher]] = None,
        registry: Optional[MetricsRegistry] = None,
        tracer=None,
        clock: Callable[[], float] = time.perf_counter,
        eject_after_failures: int = 3,
        rejoin_cooldown_s: float = 1.0,
        probation_steps: int = 2,
        stall_degrade_s: Optional[float] = 5.0,
        degrade_recover_s: float = 1.0,
        heartbeat_timeout_s: Optional[float] = 30.0,
    ):
        if replicas < 1:
            raise ValueError("a ReplicaSet needs at least one replica")
        self.model = model
        self.engine_kwargs = dict(engine_kwargs or {})
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else default_tracer()
        self._clock = clock
        self.eject_after_failures = int(eject_after_failures)
        self.rejoin_cooldown_s = float(rejoin_cooldown_s)
        self.probation_steps = int(probation_steps)
        self.stall_degrade_s = stall_degrade_s
        self.degrade_recover_s = float(degrade_recover_s)
        self.heartbeat_timeout_s = heartbeat_timeout_s
        #: Hooks called with (index, engine) after every engine build/rebuild —
        #: the chaos `RouterInjector` re-arms its dispatch wraps through this.
        self.on_engine_built: List[Callable[[int, ContinuousBatcher], None]] = []
        #: Weights applied to rebuilt engines (updated by rolling swaps).
        self.current_params: Optional[Dict[str, Any]] = None
        #: Every state transition: {"t", "replica", "from", "to", "why"}.
        #: Bounded like the Router's routing journal (transitions are rare,
        #: but a flapping replica over months must not grow host memory).
        self.state_log: deque = deque(maxlen=10_000)
        self._engine_factory = engine_factory
        self._m_ejected = self.registry.counter(
            "router_ejected_total", help="replica ejections (health machine -> ejected)"
        )
        self._g_live = self.registry.gauge(
            "router_replicas_live", help="replicas currently in the live state"
        )
        self._g_state: Dict[int, Any] = {}
        self._g_load: Dict[int, Any] = {}
        self.replicas: List[Replica] = []
        for _ in range(replicas):
            self.add_replica(why="initial fleet")
        self._refresh_gauges()

    def _ensure_gauges(self, index: int):
        if index in self._g_state:
            return
        self._g_state[index] = self.registry.gauge(
            "router_replica_state",
            help="health state code (0=live 1=degraded 2=ejected 3=rejoining "
            "4=draining 5=retired 6=reconnecting)",
            labels={"replica": str(index)},
        )
        self._g_load[index] = self.registry.gauge(
            "router_replica_load",
            help="queued + in-flight requests on this replica",
            labels={"replica": str(index)},
        )

    # ------------------------------------------------------------------ fleet size
    def add_replica(self, why: str = "scale up") -> Replica:
        """Grow the fleet by one replica (a new index, never a reused one —
        journals and chaos targeting stay unambiguous). The engine is built —
        and, for subprocess factories, spawned + warmed — before the replica
        becomes routable, so scale-up traffic never pays a compile."""
        index = len(self.replicas)
        self._ensure_gauges(index)
        replica = Replica(index=index, engine=self._build_engine(index), last_ok=self._clock())
        self.replicas.append(replica)
        self.state_log.append(
            {"t": self._clock(), "replica": index, "from": "new", "to": "live", "why": why}
        )
        self.tracer.event("router.replica_added", category="router", replica=index, why=why)
        logger.info("router: replica %d added (%s)", index, why)
        self._refresh_gauges()
        return replica

    def retire_replica(self, index: int, why: str = "scale down") -> Replica:
        """Remove one replica permanently: its engine closes (a subprocess
        worker exits), the state machine records terminal `retired`, and the
        index is never routed or rejoined again."""
        replica = self.replicas[index]
        if replica.state == "retired":
            return replica
        if not replica.dead:
            try:
                replica.engine.close()
            except Exception:  # noqa: BLE001 — a dying engine must not block retirement
                logger.warning("router: replica %d engine close failed on retire", index)
        replica.dead = True
        self.set_state(replica, "retired", why)
        return replica

    # ------------------------------------------------------------------ build
    def _engine_kwargs_for(self, index: int) -> Dict[str, Any]:
        """Per-replica engine kwargs: each replica gets its OWN device group
        (`tp_group=index`) — a tensor-parallel fleet (`engine_kwargs`
        ``tp=N``) spans devices ``[r*N, (r+1)*N)``, a tp=1 fleet puts replica
        r on device r — when the topology has that many, wrapping around
        otherwise (`parallel.sharding.serving_tp_mesh` resolves the group;
        CPU smoke meshes oversubscribe harmlessly). A mesh-spanning engine is
        just one replica, so replication over device groups composes with
        health routing, retries, hedging and rolling swaps for free."""
        kwargs = dict(self.engine_kwargs)
        if kwargs.get("tp_devices") is None:
            kwargs.setdefault("tp_group", index)
        return kwargs

    def _build_engine(self, index: int) -> ContinuousBatcher:
        if self._engine_factory is not None:
            engine = self._engine_factory(index)
        else:
            engine = ContinuousBatcher(
                self.model, tracer=self.tracer, **self._engine_kwargs_for(index)
            )
        if self.current_params is not None:
            engine.params = self.current_params
        # Share ONE params tree across the fleet: a weight_dtype="int8"
        # engine's setter quantizes, and without this rebind every replica
        # would quantize the same raw tree into its OWN int8+scale copy
        # (N x the weight HBM). Adopting the first engine's (possibly
        # quantized) tree makes later setter calls pass-throughs — the
        # setter is idempotent. Subprocess engines keep params worker-side
        # (their getter returns None), so the controller copy stays as-is.
        # Mesh-spanning engines are excluded: their setters re-shard onto
        # their OWN submesh, so adopting one replica's placed tree would
        # just churn device_put round trips through every other group.
        if getattr(engine, "params", None) is not None and getattr(engine, "mesh", None) is None:
            self.current_params = engine.params
        attach = getattr(engine, "attach_telemetry", None)
        if attach is not None:
            # Subprocess proxies report reconnects/frame errors/RTTs into the
            # fleet's shared registry, labeled by replica index, and stitch
            # their serve.reconnect spans into the fleet trace.
            attach(self.registry, tracer=self.tracer, replica=index)
        for hook in self.on_engine_built:
            hook(index, engine)
        return engine

    # ------------------------------------------------------------------ state
    def set_state(self, replica: Replica, state: str, why: str):
        if state not in REPLICA_STATES:
            raise ValueError(f"unknown replica state {state!r}")
        if replica.state == state:
            return
        old = replica.state
        replica.state = state
        now = self._clock()
        self.state_log.append(
            {"t": now, "replica": replica.index, "from": old, "to": state, "why": why}
        )
        self.tracer.event(
            "router.replica_state", category="router",
            replica=replica.index, **{"from": old, "to": state}, why=why,
        )
        logger.info(
            "router: replica %d %s -> %s (%s)", replica.index, old, state, why
        )
        if state == "ejected":
            replica.ejected_at = now
            self._m_ejected.inc()
        if state == "rejoining":
            replica.probation_ok = 0
        if state == "live":
            replica.consecutive_failures = 0
            replica.ejected_at = None
            replica.unhealthy_at = None
        self._refresh_gauges()

    def _refresh_gauges(self):
        self._g_live.set(sum(r.state == "live" for r in self.replicas))
        for r in self.replicas:
            self._g_state[r.index].set(_STATE_CODE[r.state])
            self._g_load[r.index].set(0 if r.dead else r.engine.load)

    # ------------------------------------------------------------------ health
    def record_step(self, replica: Replica, duration_s: float, errored: bool):
        """Fold one driven engine step into the health machine: failures feed
        the consecutive counter (ejecting at the threshold), slow steps degrade,
        clean fast steps heal and advance probation."""
        now = self._clock()
        if errored:
            replica.consecutive_failures += 1
            replica.unhealthy_at = now
            if replica.state == "rejoining":
                self.set_state(replica, "ejected", "failure during rejoin probation")
            elif replica.consecutive_failures >= self.eject_after_failures:
                self.set_state(
                    replica, "ejected",
                    f"{replica.consecutive_failures} consecutive dispatch failures",
                )
            elif replica.state == "live":
                self.set_state(replica, "degraded", "dispatch failure")
            return
        replica.consecutive_failures = 0
        replica.last_ok = now
        slow = self.stall_degrade_s is not None and duration_s > self.stall_degrade_s
        pressured = (
            replica.engine.max_queue is not None
            and replica.engine.queue_depth >= replica.engine.max_queue
        )
        if slow or pressured:
            replica.unhealthy_at = now
            if replica.state == "live":
                self.set_state(
                    replica, "degraded",
                    f"slow step ({duration_s:.3f}s)" if slow else "queue at capacity",
                )
            return
        if replica.state == "degraded" and (
            replica.unhealthy_at is None
            or now - replica.unhealthy_at >= self.degrade_recover_s
        ):
            self.set_state(replica, "live", "healthy again")
        elif replica.state == "rejoining":
            replica.probation_ok += 1
            if replica.probation_ok >= self.probation_steps:
                self.set_state(replica, "live", "probation passed")

    def heartbeat_expired(self, replica: Replica) -> bool:
        """A replica that HAS work but has not finished a step inside the
        heartbeat window is hung (the subprocess-worker seam; in-process
        engines step synchronously and rarely trip this)."""
        if self.heartbeat_timeout_s is None or replica.dead:
            return False
        if not replica.engine.pending:
            replica.last_ok = self._clock()
            return False
        return self._clock() - replica.last_ok > self.heartbeat_timeout_s

    def poll(self):
        """Cooldown sweep: ejected replicas whose cooldown elapsed re-enter as
        `rejoining` (rebuilding the engine first when it died with the fault).
        A FAILED rebuild (a subprocess respawn that never reaches its ready
        handshake, an OOM during engine construction) must not escape into the
        router's step loop — that would crash the whole fleet over one
        replica, the exact blast radius this layer exists to remove. The
        replica stays ejected and retries after another full cooldown."""
        now = self._clock()
        for replica in self.replicas:
            if replica.state != "ejected" or replica.ejected_at is None:
                continue
            if now - replica.ejected_at < self.rejoin_cooldown_s:
                continue
            if replica.dead:
                try:
                    replica.engine = self._build_engine(replica.index)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except BaseException as exc:  # noqa: BLE001 — rebuild failure stays per-replica
                    logger.warning(
                        "router: replica %d rebuild failed (%r); retrying after cooldown",
                        replica.index, exc,
                    )
                    replica.ejected_at = now
                    continue
                replica.dead = False
            self.set_state(replica, "rejoining", "cooldown elapsed")
        self._refresh_gauges()

    # ------------------------------------------------------------------ routing view
    def candidates(self) -> List[Replica]:
        """Routable replicas in preference order: live first, then degraded,
        then rejoining (probation traffic) — least-loaded within each class.
        Ejected and draining replicas are NEVER returned."""
        order = {"live": 0, "degraded": 1, "rejoining": 2}
        routable = [r for r in self.replicas if r.routable and not r.dead]
        return sorted(routable, key=lambda r: (order[r.state], r.engine.load, r.index))


class Router:
    """The replicated serving front-end: same surface as `ContinuousBatcher`,
    N engines behind it. See the module docstring for the full contract.

    Typical driving loop (identical to the single-engine one)::

        router = Router(model, replicas=3, num_slots=8, max_queue=64,
                        default_deadline_s=60.0)
        for r in requests:
            router.submit(r)
        while router.pending:
            for request_id, new_tokens in router.step():
                stream(request_id, new_tokens)
        router.swap_weights(new_model)   # rolling deploy, fleet stays >= N-1
    """

    def __init__(
        self,
        model,
        replicas: Optional[int] = None,
        max_queue: Optional[int] = 64,
        default_deadline_s: Optional[float] = None,
        hedge_after_s: Optional[float] = None,
        hedge_quantile: Optional[float] = None,
        hedge_min_samples: int = 20,
        max_retries: int = 1,
        retry_window_s: float = 5.0,
        tenant_queue_limit: Optional[int] = None,
        min_replicas: Optional[int] = None,
        max_replicas: Optional[int] = None,
        autoscale_queue_high: float = 2.0,
        autoscale_ttft_target_s: Optional[float] = None,
        autoscale_cooldown_s: float = 5.0,
        idle_retire_s: float = 30.0,
        out_of_process: bool = False,
        worker_kwargs: Optional[Dict[str, Any]] = None,
        registry: Optional[MetricsRegistry] = None,
        tracer=None,
        clock: Callable[[], float] = time.perf_counter,
        engine_factory: Optional[Callable[[int], ContinuousBatcher]] = None,
        eject_after_failures: int = 3,
        rejoin_cooldown_s: float = 1.0,
        probation_steps: int = 2,
        stall_degrade_s: Optional[float] = 5.0,
        degrade_recover_s: float = 1.0,
        heartbeat_timeout_s: Optional[float] = 30.0,
        **engine_kwargs,
    ):
        if replicas is not None:
            n = int(replicas)
        elif min_replicas is not None:
            n = int(min_replicas)
        else:
            n = default_replicas()
        self._clock = clock
        self.metrics = registry if registry is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else default_tracer()
        self.max_queue = None if max_queue is None else int(max_queue)
        self.default_deadline_s = default_deadline_s
        if hedge_after_s is not None and hedge_quantile is not None:
            raise ValueError(
                "pass hedge_after_s (static threshold) OR hedge_quantile "
                "(derived from the live TTFT histogram), not both"
            )
        if hedge_quantile is not None and not 0.0 < hedge_quantile < 1.0:
            raise ValueError("hedge_quantile must be in (0, 1)")
        self.hedge_after_s = hedge_after_s
        self.hedge_quantile = hedge_quantile
        self.hedge_min_samples = int(hedge_min_samples)
        self.max_retries = int(max_retries)
        self.retry_window_s = float(retry_window_s)
        # Admission control (fair-share, per-tenant): None keeps the legacy
        # fleet-wide QueueFull contract; an int bounds EACH tenant's
        # router-level wait queue so one tenant's burst degrades into bounded
        # queueing for that tenant while the rest keep admitting.
        self.tenant_queue_limit = (
            None if tenant_queue_limit is None else int(tenant_queue_limit)
        )
        if self.tenant_queue_limit is not None and self.tenant_queue_limit < 1:
            raise ValueError("tenant_queue_limit must be >= 1 (or None to disable)")
        self._admission: Dict[str, deque] = {}
        self._admission_rr: List[str] = []  # round-robin order across tenants
        # Autoscaling: enabled when max_replicas is set; the fleet floats in
        # [min_replicas, max_replicas] on queue-depth / TTFT pressure.
        self.min_replicas = n if min_replicas is None else int(min_replicas)
        self.max_replicas = None if max_replicas is None else int(max_replicas)
        if self.min_replicas < 1:
            raise ValueError("min_replicas must be >= 1")
        if self.max_replicas is not None and self.max_replicas < self.min_replicas:
            raise ValueError("max_replicas must be >= min_replicas")
        self.autoscale_queue_high = float(autoscale_queue_high)
        self.autoscale_ttft_target_s = autoscale_ttft_target_s
        self.autoscale_cooldown_s = float(autoscale_cooldown_s)
        self.idle_retire_s = float(idle_retire_s)
        self._last_scale_t: Optional[float] = None
        self._idle_since: Optional[float] = None
        engine_kwargs = dict(engine_kwargs)
        engine_kwargs.setdefault("max_queue", self.max_queue)
        if out_of_process and int(engine_kwargs.get("tp", 1) or 1) > 1:
            # The subprocess factory bypasses ReplicaSet._engine_kwargs_for,
            # so every worker would build its submesh at the default
            # tp_group=0 — all replicas silently sharing one device block.
            # Refuse rather than degrade (multi-host TP workers are ROADMAP
            # item 2); the serve CLI carries the same guard.
            raise ValueError(
                "tp > 1 composes with in-process replicas only for now: "
                "subprocess workers pin their own device view, so an "
                "out-of-process TP fleet would stack every replica on the "
                "same device block — pass out_of_process=False"
            )
        if out_of_process and engine_factory is None:
            from .worker import make_subprocess_factory

            engine_factory = make_subprocess_factory(
                model, engine_kwargs=engine_kwargs, **(worker_kwargs or {})
            )
        self.replica_set = ReplicaSet(
            model,
            n,
            engine_kwargs=engine_kwargs,
            engine_factory=engine_factory,
            registry=self.metrics,
            tracer=self.tracer,
            clock=clock,
            eject_after_failures=eject_after_failures,
            rejoin_cooldown_s=rejoin_cooldown_s,
            probation_steps=probation_steps,
            stall_degrade_s=stall_degrade_s,
            degrade_recover_s=degrade_recover_s,
            heartbeat_timeout_s=heartbeat_timeout_s,
        )
        self.results: Dict[int, RequestResult] = {}
        #: request_id -> tracking record (attempts, stream state, span).
        self._tracked: Dict[int, Dict[str, Any]] = {}
        #: engine-level id -> (request_id, attempt dict); engine ids are
        #: globally unique across replicas so retries/hedges never collide.
        self._engine_map: Dict[int, Tuple[int, Dict[str, Any]]] = {}
        self._next_engine_id = 0
        self._retry_queue: deque = deque()
        self._no_capacity_since: Optional[float] = None
        self._closed = False
        self._draining = False
        #: Pending rolling swap: {"params", "queue": [indices], "active": idx}.
        self._swap: Optional[Dict[str, Any]] = None
        #: Every routing decision: {"t", "request_id", "replica", "kind",
        #: "state"} — the chaos no-route-to-ejected invariant audits this.
        #: Bounded (newest-kept ring, like the flight recorder) so a
        #: long-running fleet's journal cannot grow host memory without limit.
        self.routing_log: deque = deque(maxlen=10_000)

        self._m_requests = self.metrics.counter(
            "router_requests_total", help="requests accepted by the router"
        )
        self._m_retries = self.metrics.counter(
            "router_retries_total",
            help="never-streamed requests re-dispatched after a replica failure",
        )
        self._m_hedges = self.metrics.counter(
            "router_hedges_total", help="TTFT hedge copies dispatched"
        )
        self._m_hedge_wins = self.metrics.counter(
            "router_hedge_wins_total", help="requests whose hedge copy streamed first"
        )
        self._m_finish = {
            reason: self.metrics.counter(
                "router_requests_finished_total",
                help="router-level terminal finish reasons",
                labels={"reason": reason},
            )
            for reason in ROUTER_FINISH_REASONS
        }
        # Router-level TTFT: submit() -> first forwarded token, fleet-wide.
        # This is the histogram hedge_quantile and the autoscaler's TTFT signal
        # read — it works identically for in-process and subprocess fleets
        # (engine-side serving_ttft histograms live in each engine's registry).
        self._m_ttft = self.metrics.histogram(
            "serving_ttft_seconds",
            help="router submit() -> first streamed token (host wall clock)",
        )
        self._m_scale_up = self.metrics.counter(
            "router_scale_up_total", help="autoscaler replica additions"
        )
        self._m_scale_down = self.metrics.counter(
            "router_scale_down_total", help="autoscaler replica retirements"
        )
        self._g_replicas = self.metrics.gauge(
            "router_replicas_total", help="replicas not retired (fleet size)"
        )
        self._g_admission = self.metrics.gauge(
            "router_admission_queue_depth",
            help="requests waiting in router-level tenant admission queues",
        )
        self._m_admission_rejected: Dict[str, Any] = {}
        self._g_replicas.set(self.num_replicas)

    # ------------------------------------------------------------------ views
    @property
    def num_replicas(self) -> int:
        return len(self.replica_set.replicas)

    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def pending(self) -> bool:
        return any(not t["result"].finished for t in self._tracked.values())

    @property
    def swap_in_progress(self) -> bool:
        return self._swap is not None

    @property
    def active_replicas(self) -> int:
        """Replicas that are part of the fleet (not autoscaler-retired)."""
        return sum(r.state != "retired" for r in self.replica_set.replicas)

    @property
    def replica_states(self) -> Dict[int, str]:
        return {r.index: r.state for r in self.replica_set.replicas}

    @property
    def stats(self) -> Dict[str, Any]:
        view = {
            "replicas": self.num_replicas,
            "active_replicas": self.active_replicas,
            "replica_states": self.replica_states,
            "retries": int(self._m_retries.value),
            "ejected": int(self.replica_set._m_ejected.value),
            "hedges": int(self._m_hedges.value),
            "hedge_wins": int(self._m_hedge_wins.value),
            "hedge_threshold_s": self.hedge_threshold(),
            "finish_reasons": {
                reason: int(counter.value) for reason, counter in self._m_finish.items()
            },
            "per_replica": [
                None if r.dead else r.engine.stats for r in self.replica_set.replicas
            ],
        }
        if self.max_replicas is not None:
            view["autoscale"] = {
                "min_replicas": self.min_replicas,
                "max_replicas": self.max_replicas,
                "scale_ups": int(self._m_scale_up.value),
                "scale_downs": int(self._m_scale_down.value),
            }
        if self.tenant_queue_limit is not None:
            view["admission"] = {
                "tenant_queue_limit": self.tenant_queue_limit,
                "queued": {t: len(q) for t, q in self._admission.items() if q},
                "rejected": {
                    t: int(c.value) for t, c in self._m_admission_rejected.items()
                },
            }
        return view

    def warm_inserts(self) -> Dict[int, List[int]]:
        """Precompile every replica's insert-bucket ladder (the bench's
        mechanical 0-recompile guarantee, fleet edition)."""
        return {
            r.index: r.engine.warm_inserts()
            for r in self.replica_set.replicas
            if not r.dead
        }

    # ------------------------------------------------------------------ submit
    def submit(self, request: Request) -> int:
        """Route + enqueue on the least-loaded routable replica. Same caller
        contract as the engine: `ValueError` for malformed requests,
        `QueueFull` when EVERY routable replica's bounded queue is at capacity,
        `EngineClosed` after `close()`/mid-`drain()`."""
        if self._closed:
            raise EngineClosed("router is closed")
        if self._draining:
            raise EngineClosed("router is draining; resubmit after drain() returns")
        if request.request_id in self.results:
            raise ValueError(f"duplicate request_id {request.request_id}")
        ids = np.asarray(request.input_ids, np.int32).reshape(-1)
        deadline_s = request.deadline_s
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        now = self._clock()
        tracked: Dict[str, Any] = {
            "request": dataclasses.replace(request, input_ids=ids, deadline_s=deadline_s),
            "result": RequestResult(request.request_id, arrival_time=request.arrival_time),
            "attempts": [],
            "winner": None,  # engine_id of the attempt whose tokens we forward
            "retries": 0,
            "hedged": False,
            "submit_t": now,
            "deadline_at": None if deadline_s is None else now + float(deadline_s),
            "span": None,
        }
        span = self.tracer.start_span(
            "serve.route", category="router",
            request_id=int(request.request_id), replicas=self.num_replicas,
        )
        tracked["span"] = span
        # With admission control armed, a new request may not jump ahead of
        # tenants already queued at the router: it enqueues behind them and the
        # sweep dispatches in priority/fair-share order.
        queued_behind = self.tenant_queue_limit is not None and any(
            self._admission.values()
        )
        try:
            attempt = None if queued_behind else self._dispatch(tracked, kind="submit")
        except ValueError:
            span.annotate(error="invalid_request").end()
            raise
        if attempt is None:
            if self.tenant_queue_limit is None:
                span.annotate(error="queue_full").end()
                raise QueueFull(
                    "every routable replica's queue is at capacity; shed load or retry later"
                )
            # Admission control: the fleet is saturated — queue at the ROUTER
            # in this tenant's bounded fair-share queue instead of failing the
            # whole fleet closed. Only this tenant's own bound rejects.
            tenant = request.tenant or "default"
            queue = self._admission.get(tenant)
            if queue is None:
                queue = self._admission[tenant] = deque()
                self._admission_rr.append(tenant)
            if len(queue) >= self.tenant_queue_limit:
                self._admission_rejected(tenant).inc()
                span.annotate(error="queue_full", tenant=tenant).end()
                raise QueueFull(
                    f"tenant {tenant!r} admission queue is at "
                    f"tenant_queue_limit={self.tenant_queue_limit}; shed load or retry later"
                )
            queue.append(request.request_id)
            span.event("admission_queued", tenant=tenant, depth=len(queue))
            self._g_admission.set(sum(len(q) for q in self._admission.values()))
        self.results[request.request_id] = tracked["result"]
        self._tracked[request.request_id] = tracked
        self._m_requests.inc()
        return request.request_id

    def _admission_rejected(self, tenant: str):
        counter = self._m_admission_rejected.get(tenant)
        if counter is None:
            counter = self._m_admission_rejected[tenant] = self.metrics.counter(
                "router_admission_rejected_total",
                help="requests rejected at a tenant's bounded admission queue",
                labels={"tenant": tenant},
            )
        return counter

    def _admission_sweep(self):
        """Drain the per-tenant admission queues into replica capacity:
        strict priority first (a tenant whose head request carries a higher
        `priority` dispatches before lower ones), round-robin across tenants
        at equal priority (fair share — no tenant starves another at its own
        priority level). Expired queued requests finish `timeout`."""
        if not self._admission:
            return
        progressed = True
        while progressed:
            progressed = False
            heads: List[Tuple[int, int, str]] = []
            for rr_pos, tenant in enumerate(self._admission_rr):
                queue = self._admission.get(tenant)
                while queue:
                    tracked = self._tracked.get(queue[0])
                    if tracked is None or tracked["result"].finished:
                        queue.popleft()  # cancelled / finished while queued
                        continue
                    now = self._clock()
                    deadline_at = tracked["deadline_at"]
                    if deadline_at is not None and now >= deadline_at:
                        self._finish(tracked, "timeout")
                        queue.popleft()
                        continue
                    heads.append((-int(tracked["request"].priority), rr_pos, tenant))
                    break
            for _neg_priority, _rr_pos, tenant in sorted(heads):
                queue = self._admission[tenant]
                if not queue:
                    continue
                tracked = self._tracked.get(queue[0])
                if tracked is None:
                    queue.popleft()
                    continue
                attempt = self._dispatch(tracked, kind="admit")
                if attempt is None:
                    continue  # no capacity for this one; try other tenants
                queue.popleft()
                # Fair share: a tenant that just dispatched goes to the back
                # of the round-robin order.
                self._admission_rr.remove(tenant)
                self._admission_rr.append(tenant)
                progressed = True
        self._g_admission.set(sum(len(q) for q in self._admission.values()))

    def _dispatch(self, tracked: Dict[str, Any], kind: str) -> Optional[Dict[str, Any]]:
        """Place one attempt of `tracked` on the best routable replica (skipping
        replicas that already host an attempt). Returns the attempt record, or
        None when no replica could take it. `ValueError` from engine validation
        propagates (the caller's bug, reported synchronously, like the engine)."""
        exclude = {a["replica"] for a in tracked["attempts"] if not a["done"]}
        if kind == "retry":
            # Do not retry onto the replica that just failed the request.
            exclude |= {a["replica"] for a in tracked["attempts"]}
        request = tracked["request"]
        now = self._clock()
        deadline_at = tracked["deadline_at"]
        remaining = None if deadline_at is None else max(deadline_at - now, 0.0)
        for replica in self.replica_set.candidates():
            if replica.index in exclude:
                continue
            engine_id = self._next_engine_id
            engine_request = dataclasses.replace(
                request, request_id=engine_id, deadline_s=remaining
            )
            try:
                replica.engine.submit(engine_request)
            except QueueFull:
                continue
            except EngineClosed:
                continue
            self._next_engine_id += 1
            attempt = {"replica": replica.index, "engine_id": engine_id,
                       "kind": kind, "done": False}
            tracked["attempts"].append(attempt)
            self._engine_map[engine_id] = (request.request_id, attempt)
            self.routing_log.append({
                "t": now, "request_id": request.request_id,
                "replica": replica.index, "kind": kind, "state": replica.state,
            })
            span = tracked["span"]
            if span is not None:
                span.event(kind, replica=replica.index, engine_id=engine_id)
            self.replica_set._refresh_gauges()
            return attempt
        return None

    # ------------------------------------------------------------------ cancel / release
    def cancel(self, request_id: int) -> bool:
        """Cancel a queued or in-flight request on whichever replica(s) own it:
        the result finishes `cancelled` with partial tokens kept — the same
        terminal contract as the single-engine path. Returns False when already
        finished; raises KeyError for an unknown id."""
        tracked = self._tracked[request_id]
        if tracked["result"].finished:
            return False
        self._finish(tracked, "cancelled")
        return True

    def release(self, request_id: int) -> RequestResult:
        """Drop a FINISHED request's result (host-memory hygiene, engine
        contract)."""
        result = self.results[request_id]
        if not result.finished:
            raise ValueError(f"request {request_id} is still in flight")
        del self.results[request_id]
        self._tracked.pop(request_id, None)
        return result

    def _abandon_attempt(self, attempt: Dict[str, Any]):
        """Cancel one engine-level attempt and drop its mapping (router-initiated:
        the engine's `cancelled` result must never resurface as ours)."""
        if attempt["done"]:
            return
        attempt["done"] = True
        self._engine_map.pop(attempt["engine_id"], None)
        replica = self.replica_set.replicas[attempt["replica"]]
        if replica.dead:
            return
        try:
            replica.engine.cancel(attempt["engine_id"])
            replica.engine.release(attempt["engine_id"])
        except (KeyError, ValueError):
            pass

    def _finish(self, tracked: Dict[str, Any], reason: str, error: Optional[str] = None):
        for attempt in tracked["attempts"]:
            self._abandon_attempt(attempt)
        result = tracked["result"]
        if result.finished:
            return
        result.finished = True
        result.finish_time = self._clock()
        result.finish_reason = reason
        if error is not None:
            result.error = error
        self._m_finish[reason].inc()
        span = tracked["span"]
        if span is not None:
            span.annotate(finish_reason=reason, tokens=len(result.tokens),
                          retries=tracked["retries"])
            if error is not None:
                span.annotate(error=error)
            span.end()

    # ------------------------------------------------------------------ failure handling
    def _handle_attempt_failure(self, tracked: Dict[str, Any], attempt: Dict[str, Any],
                                error: str):
        """The safe re-dispatch rule: a request that already streamed tokens
        surfaces `replica_lost` (tokens kept, never duplicated); a never-
        streamed one retries on another replica inside its retry budget."""
        attempt["done"] = True
        self._engine_map.pop(attempt["engine_id"], None)
        result = tracked["result"]
        if result.finished:
            return
        if any(not a["done"] for a in tracked["attempts"]):
            return  # a hedge copy is still running; it carries the request
        if result.tokens:
            self._finish(tracked, "replica_lost", error=error)
            return
        if tracked["retries"] >= self.max_retries:
            self._finish(tracked, "error", error=error)
            return
        tracked["retries"] += 1
        self._retry_queue.append(tracked["request"].request_id)

    def fail_replica(self, index: int, reason: str = "killed", dead: bool = True):
        """Handle an observed replica failure (the chaos / ops seam; also what
        `step()` calls when an engine dies under it). Every request with an
        attempt on the replica goes through the re-dispatch rule; the replica
        is ejected and — when `dead` — its engine is rebuilt on rejoin."""
        replica = self.replica_set.replicas[index]
        victims = [
            (rid, attempt) for eid, (rid, attempt) in list(self._engine_map.items())
            if attempt["replica"] == index and not attempt["done"]
        ]
        for rid, attempt in victims:
            if not dead and not replica.dead:
                # Engine is still healthy (soft kill): free its slot/queue entry.
                try:
                    replica.engine.cancel(attempt["engine_id"])
                    replica.engine.release(attempt["engine_id"])
                except (KeyError, ValueError):
                    pass
            tracked = self._tracked.get(rid)
            if tracked is not None:
                self._handle_attempt_failure(tracked, attempt, error=f"replica {index} {reason}")
        if dead and not replica.dead:
            # The engine is being written off for a rebuild: tear the old one
            # down NOW. An out-of-process worker that failed via error replies
            # still has a live process — left to the garbage collector it
            # would linger holding device memory next to its replacement.
            terminate = getattr(replica.engine, "terminate", None)
            try:
                if terminate is not None:
                    terminate()
                else:
                    replica.engine.close()
            except Exception:  # noqa: BLE001 — teardown of a failed engine is best-effort
                logger.warning("router: replica %d engine teardown failed on eject", index)
        replica.dead = replica.dead or bool(dead)
        self.replica_set.set_state(replica, "ejected", reason)

    # ------------------------------------------------------------------ hedging
    def hedge_threshold(self) -> Optional[float]:
        """The live hedge trigger in seconds, or None when hedging is off.
        Static `hedge_after_s` wins when set; otherwise `hedge_quantile` reads
        the router's own `serving_ttft_seconds` histogram — hedging stays
        DISABLED until `hedge_min_samples` observations exist, so a cold fleet
        never hedges off noise (and a stale hand-tuned constant never fires
        at yesterday's latency)."""
        if self.hedge_after_s is not None:
            return self.hedge_after_s
        if self.hedge_quantile is None:
            return None
        if self._m_ttft.count < self.hedge_min_samples:
            return None
        return self._m_ttft.quantile(self.hedge_quantile)

    def _hedge_sweep(self):
        threshold = self.hedge_threshold()
        if threshold is None:
            return
        now = self._clock()
        for tracked in self._tracked.values():
            result = tracked["result"]
            if result.finished or result.tokens or tracked["hedged"]:
                continue
            if now - tracked["submit_t"] < threshold:
                continue
            if sum(not a["done"] for a in tracked["attempts"]) != 1:
                continue
            attempt = self._dispatch(tracked, kind="hedge")
            if attempt is not None:
                tracked["hedged"] = True
                self._m_hedges.inc()

    # ------------------------------------------------------------------ retries
    def _retry_sweep(self):
        if not self._retry_queue:
            self._no_capacity_since = None
            return
        pending = len(self._retry_queue)
        for _ in range(pending):
            rid = self._retry_queue.popleft()
            tracked = self._tracked.get(rid)
            if tracked is None or tracked["result"].finished:
                continue
            deadline_at = tracked["deadline_at"]
            now = self._clock()
            if deadline_at is not None and now >= deadline_at:
                self._finish(tracked, "timeout")
                continue
            attempt = self._dispatch(tracked, kind="retry")
            if attempt is None:
                self._retry_queue.append(rid)
            else:
                # Counted at DISPATCH (not at queue time) so the counter and
                # the routing journal's `retry` entries reconcile exactly.
                self._m_retries.inc()
        if self._retry_queue:
            now = self._clock()
            if self._no_capacity_since is None:
                self._no_capacity_since = now
            elif now - self._no_capacity_since > self.retry_window_s:
                # The whole fleet has been unroutable for the retry window:
                # surface the loss instead of queueing invisibly forever.
                while self._retry_queue:
                    tracked = self._tracked.get(self._retry_queue.popleft())
                    if tracked is not None and not tracked["result"].finished:
                        self._finish(tracked, "error", error="no routable replica")
        else:
            self._no_capacity_since = None

    # ------------------------------------------------------------------ autoscaling
    def _fleet_queue_depth(self) -> int:
        depth = len(self._retry_queue) + sum(len(q) for q in self._admission.values())
        for replica in self.replica_set.replicas:
            if not replica.dead and replica.state != "retired":
                depth += replica.engine.queue_depth
        return depth

    def _autoscale_sweep(self):
        """Traffic-adaptive fleet sizing inside [min_replicas, max_replicas]:
        scale UP on queue-depth pressure (fleet queue depth per routable
        replica >= `autoscale_queue_high`) or — when `autoscale_ttft_target_s`
        is set — on the live TTFT histogram's p99 exceeding the target; scale
        DOWN by retiring one replica after the fleet has been fully idle for
        `idle_retire_s`. One action per `autoscale_cooldown_s`, journaled on
        the state log like every other transition."""
        if self.max_replicas is None:
            return
        now = self._clock()
        active = [r for r in self.replica_set.replicas if r.state != "retired"]
        routable = [r for r in active if r.routable and not r.dead]
        queue_depth = self._fleet_queue_depth()
        pressure = queue_depth >= self.autoscale_queue_high * max(len(routable), 1)
        if not pressure and self.autoscale_ttft_target_s is not None:
            if self._m_ttft.count >= self.hedge_min_samples:
                p99 = self._m_ttft.quantile(0.99)
                pressure = p99 is not None and p99 > self.autoscale_ttft_target_s
        cooled = (
            self._last_scale_t is None
            or now - self._last_scale_t >= self.autoscale_cooldown_s
        )
        if pressure:
            self._idle_since = None
            if len(active) < self.max_replicas and cooled:
                # NOTE: the build is synchronous — an out-of-process spawn
                # blocks this step for the worker's cold start (it comes up
                # WARM in exchange). The cooldown bounds how often that cost
                # can recur; a failed spawn backs off the same way instead of
                # crashing the serving loop.
                try:
                    self.replica_set.add_replica(
                        why=f"autoscale up: fleet queue depth {queue_depth}"
                    )
                except (KeyboardInterrupt, SystemExit):
                    raise
                except BaseException as exc:  # noqa: BLE001 — spawn failure must not kill serving
                    logger.warning("router: autoscale spawn failed (%r); backing off", exc)
                    self._last_scale_t = now
                    return
                self._last_scale_t = now
                self._m_scale_up.inc()
                self._g_replicas.set(self.active_replicas)
            return
        load = sum(
            r.engine.load for r in active if not r.dead
        )
        if queue_depth == 0 and load == 0:
            if self._idle_since is None:
                self._idle_since = now
            elif (
                now - self._idle_since >= self.idle_retire_s
                and len(active) > self.min_replicas
                and cooled
            ):
                # Retire the NEWEST idle live replica: scale-down unwinds
                # scale-up, and the original fleet keeps its indices.
                victim = next(
                    (r for r in reversed(active)
                     if r.state == "live" and not r.engine.pending),
                    None,
                )
                if victim is not None:
                    self.replica_set.retire_replica(
                        victim.index, why="autoscale down: fleet idle"
                    )
                    self._last_scale_t = now
                    self._idle_since = now  # next retirement waits a full window
                    self._m_scale_down.inc()
                    self._g_replicas.set(self.active_replicas)
        else:
            self._idle_since = None

    # ------------------------------------------------------------------ swap
    def swap_weights(self, params_or_model, wait: bool = True) -> List[Tuple[int, List[int]]]:
        """Rolling weight swap: one replica at a time drains (unroutable,
        finishing its own work while the rest serve), gets the new params
        applied in place (per-dispatch operands — no recompile), and rejoins
        before the next drains; the fleet never drops below N-1 routable.

        `wait=True` (default) drives `step()` until the swap completes and
        returns the stream events those steps produced (nothing is dropped);
        `wait=False` just arms the swap — the caller's own `step()` loop
        advances it.

        Pass RAW (unquantized) params for any fleet: engines built with
        `weight_dtype="int8"` (riding `engine_kwargs`) re-quantize in their
        `params` setter — per-output-channel scales are recomputed at swap
        time, exactly as at load time (subprocess workers do the same in
        their `set_params` op after the file handoff)."""
        if self._closed:
            raise EngineClosed("router is closed")
        if self._swap is not None:
            raise RuntimeError("a weight swap is already in progress")
        params = _normalize_params(params_or_model)
        self._swap = {
            "params": params,
            "queue": deque(r.index for r in self.replica_set.replicas),
            "active": None,
        }
        events: List[Tuple[int, List[int]]] = []
        if wait:
            while self._swap is not None:
                events.extend(self.step())
        return events

    def _advance_swap(self):
        swap = self._swap
        if swap is None:
            return
        if swap["active"] is None:
            if not swap["queue"]:
                self.replica_set.current_params = swap["params"]
                self.tracer.event("router.swap_complete", category="router")
                self._swap = None
                return
            index = swap["queue"].popleft()
            replica = self.replica_set.replicas[index]
            if replica.dead or replica.state == "ejected":
                # A dead/ejected replica gets the new params via the rebuild
                # path on rejoin — nothing to drain.
                self.replica_set.current_params = swap["params"]
                return self._advance_swap()
            swap["active"] = index
            self.replica_set.set_state(replica, "draining", "rolling weight swap")
            return
        replica = self.replica_set.replicas[swap["active"]]
        if replica.dead or replica.state == "ejected":
            # The draining replica failed mid-swap: it will pick the new
            # params up through the rebuild/rejoin path instead.
            self.replica_set.current_params = swap["params"]
            swap["active"] = None
            return self._advance_swap()
        if not replica.engine.pending:
            replica.engine.params = swap["params"]
            # One quantize per swap, not per replica: adopt the first
            # swapped engine's (possibly quantized) tree so the remaining
            # replicas' setters share it by reference (idempotent setter;
            # subprocess engines expose no params and keep the raw tree;
            # mesh-spanning engines keep the raw tree too — each TP group
            # re-shards onto its own submesh at its setter).
            if (
                getattr(replica.engine, "params", None) is not None
                and getattr(replica.engine, "mesh", None) is None
            ):
                swap["params"] = replica.engine.params
            self.replica_set.set_state(replica, "live", "weights swapped")
            self.tracer.event(
                "router.replica_swapped", category="router", replica=replica.index
            )
            swap["active"] = None
            self._advance_swap()

    # ------------------------------------------------------------------ step
    def step(self) -> List[Tuple[int, List[int]]]:
        """One fleet cycle: advance swaps/cooldowns, re-dispatch retries, hedge
        stale queued requests, drive every replica's engine one step, forward
        the winning attempts' tokens, and fold failures through the health
        machine. Returns `(request_id, new_tokens)` in stream order, exactly
        like the engine."""
        if self._closed:
            return []
        self.replica_set.poll()
        self._advance_swap()
        self._autoscale_sweep()
        self._admission_sweep()
        self._retry_sweep()
        self._hedge_sweep()
        events: List[Tuple[int, List[int]]] = []
        for replica in self.replica_set.replicas:
            if replica.dead or replica.state in ("ejected", "retired"):
                continue
            if (
                not replica.engine.pending
                and not getattr(replica.engine, "reconnecting", False)
                and replica.state not in ("rejoining", "degraded", "reconnecting")
            ):
                # (reconnecting must still be stepped even when idle: step()
                # is what drives the engine proxy's reconnect attempts. The
                # engine attribute is checked too — an idle engine can tear
                # during a failed submit, before the router state catches up.)
                replica.last_ok = self._clock()
                continue
            t0 = self._clock()
            try:
                engine_events = replica.engine.step()
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as exc:  # noqa: BLE001 — a dead engine must not kill the fleet
                # An exception ESCAPING the engine (its own fault isolation
                # swallows ordinary dispatch errors) is replica death — the
                # in-process analogue of a serving worker SIGKILL.
                logger.warning("router: replica %d died in step(): %r", replica.index, exc)
                self.fail_replica(replica.index, reason=f"engine died: {exc!r}", dead=True)
                continue
            events.extend(self._forward_events(replica, engine_events))
            if getattr(replica.engine, "reconnecting", False):
                # Transport fault, not death: park the replica unroutable and
                # keep stepping it (each step drives one reconnect attempt).
                # The health machine is bypassed — a reconnect in progress is
                # neither a dispatch failure nor a hang — and budget
                # exhaustion surfaces as WorkerGone from step() above,
                # escalating through the ordinary fail_replica path.
                self.replica_set.set_state(
                    replica, "reconnecting", "transport tore — reconnect in progress"
                )
                replica.last_ok = self._clock()
                continue
            if replica.state == "reconnecting":
                self.replica_set.set_state(replica, "live", "transport reconnected")
            errored = self._collect_finished(replica)
            self.replica_set.record_step(replica, self._clock() - t0, errored)
            if self.replica_set.heartbeat_expired(replica):
                self.fail_replica(
                    replica.index, reason="heartbeat expired (hung engine)", dead=True
                )
        self.replica_set._refresh_gauges()
        return events

    def _forward_events(self, replica: Replica,
                        engine_events: List[Tuple[int, List[int]]]) -> List[Tuple[int, List[int]]]:
        out: List[Tuple[int, List[int]]] = []
        for engine_id, toks in engine_events:
            mapped = self._engine_map.get(engine_id)
            if mapped is None or not toks:
                continue
            rid, attempt = mapped
            tracked = self._tracked.get(rid)
            if tracked is None or tracked["result"].finished:
                continue
            if tracked["winner"] is None:
                tracked["winner"] = engine_id
                if attempt["kind"] == "hedge":
                    self._m_hedge_wins.inc()
                # First token decided the race: cancel every other copy so the
                # loser can never stream a duplicate.
                for other in tracked["attempts"]:
                    if other is not attempt:
                        self._abandon_attempt(other)
                span = tracked["span"]
                if span is not None:
                    span.event("first_token", replica=replica.index,
                               hedge=attempt["kind"] == "hedge")
            if tracked["winner"] != engine_id:
                continue  # a losing copy raced a token out before its cancel
            tracked["result"].tokens.extend(toks)
            if tracked["result"].first_token_time is None:
                now = self._clock()
                tracked["result"].first_token_time = now
                # The live TTFT signal hedge_quantile and the autoscaler read.
                self._m_ttft.observe(max(now - tracked["submit_t"], 0.0))
            out.append((rid, list(toks)))
        return out

    def _collect_finished(self, replica: Replica) -> bool:
        """Scan the replica's finished engine results, map them to router
        outcomes, and release them from the engine. Returns True when any
        attempt failed at the replica level this step (feeds the health
        machine's consecutive-failure counter)."""
        errored = False
        finished = [
            (eid, res) for eid, res in replica.engine.results.items() if res.finished
        ]
        for engine_id, res in finished:
            mapped = self._engine_map.get(engine_id)
            if mapped is None:
                # A copy we already abandoned (hedge loser / router cancel).
                try:
                    replica.engine.release(engine_id)
                except (KeyError, ValueError):
                    pass
                continue
            rid, attempt = mapped
            tracked = self._tracked.get(rid)
            replica.engine.release(engine_id)
            if tracked is None or tracked["result"].finished:
                attempt["done"] = True
                self._engine_map.pop(engine_id, None)
                continue
            reason = res.finish_reason
            if reason == "error":
                errored = True
                self._handle_attempt_failure(tracked, attempt, error=res.error or "error")
                continue
            attempt["done"] = True
            self._engine_map.pop(engine_id, None)
            if tracked["winner"] not in (None, engine_id):
                continue  # the losing copy of a hedge finished; winner carries on
            # Forward any tokens the engine finished with that we have not
            # streamed yet (first-token-at-insert of a winning copy whose
            # terminal landed in the same engine step).
            if len(res.tokens) > len(tracked["result"].tokens) and reason in ("eos", "length"):
                missing = res.tokens[len(tracked["result"].tokens):]
                tracked["result"].tokens.extend(missing)
            self._finish(tracked, reason, error=res.error)
        return errored

    # ------------------------------------------------------------------ drive / lifecycle
    def run(self, requests: Optional[List[Request]] = None) -> Dict[int, np.ndarray]:
        for req in requests or ():
            self.submit(req)
        while self.pending:
            self.step()
        return {rid: np.asarray(r.tokens, np.int32) for rid, r in self.results.items()}

    def drain(self) -> Dict[int, RequestResult]:
        """Flush: refuse new submissions while finishing everything in flight
        across the fleet, then reopen."""
        self._draining = True
        try:
            while self.pending:
                self.step()
        finally:
            self._draining = False
        return self.results

    def close(self) -> Dict[int, RequestResult]:
        """Terminal shutdown: unfinished requests finish `cancelled` (partial
        tokens kept), every engine closes, the router refuses new work."""
        if self._closed:
            return self.results
        for tracked in self._tracked.values():
            if not tracked["result"].finished:
                self._finish(tracked, "cancelled")
        for replica in self.replica_set.replicas:
            if not replica.dead:
                replica.engine.close()
        self._closed = True
        return self.results
