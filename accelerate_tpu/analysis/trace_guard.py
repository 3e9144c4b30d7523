"""Runtime half of `accelerate analyze`: prove the no-recompile / no-host-sync
discipline actually holds on a live step.

`TraceGuard` is a (re-entrant) context manager that, while armed:

  - **counts jit cache misses per executable** — jax has no public compile
    hook, but with ``jax_log_compiles`` enabled every cache miss logs
    ``"Compiling <name> with global shapes..."`` from the pxla internals; a
    logging handler on that logger gives us a per-executable miss ledger
    (cross-checked by a `jax.monitoring` backend-compile event counter, which
    carries no name but survives log-format drift);
  - **arms ``jax.transfer_guard``** (default ``"disallow"``) so accidental
    *implicit* transfers — raw numpy leaking into a jitted call, an implicit
    ``bool()`` on a device value — raise at the offending line, while the
    sanctioned explicit step-boundary pattern (``jnp.asarray(np_array)`` /
    ``jax.device_put`` operand pushes, ``jax.device_get`` drains) passes
    untouched. On a TPU ``np.asarray(x)``, ``int(x)``, ``float(x)`` and
    ``x.item()`` on a device value are IMPLICIT device-to-host reads and are
    rejected; XLA:CPU lets them through (there is no copy to guard), so a
    "0 host transfers" count taken on CPU says nothing about them. That
    asymmetry is the whole point: the guard encodes the repo's host
    discipline, not "no transfers ever".

On exit, ``on_violation="raise"`` turns any observed cache miss into a
`TraceGuardViolation` naming the recompiled executables; ``"record"`` just
keeps the ledger (bench integration reads it into the result JSON).

Steady-state is the caller's business: arm the guard AFTER warmup (every
program compiles once, by design). `TraceGuard.wrap(step_fn, warmup=1)` does
that bookkeeping for per-call arming — `Accelerator(analyze=True)` uses it to
watch the fused train step.
"""

from __future__ import annotations

import contextlib
import logging
import re
import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

# jax 0.9 logs the executable as "jit(<name>)"; the ledger is keyed by <name>.
_COMPILE_LOG_RE = re.compile(r"Compiling (?:jit\()?([^\s()]+)\)? with global shapes")
_TRANSFER_RE = re.compile(
    r"Disallowed (host-to-device|device-to-host|device-to-device) transfer"
)
#: The logger jax's executable build path logs "Compiling <name> ..." on.
_PXLA_LOGGER = "jax._src.interpreters.pxla"

# jax.monitoring listeners cannot be unregistered individually, so a single
# module-level listener fans out to whatever guards are currently armed.
_ARMED_GUARDS: List["TraceGuard"] = []
_LISTENER_LOCK = threading.Lock()
_LISTENER_INSTALLED = False


def _ensure_monitoring_listener():
    global _LISTENER_INSTALLED
    with _LISTENER_LOCK:
        if _LISTENER_INSTALLED:
            return
        import jax.monitoring

        def on_duration(event: str, duration: float, **kwargs):
            if event == "/jax/core/compile/backend_compile_duration":
                for guard in list(_ARMED_GUARDS):
                    guard.backend_compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _LISTENER_INSTALLED = True


class TraceGuardViolation(RuntimeError):
    """A steady-state step recompiled (or the wrapped step saw a guarded
    transfer). Carries the report so CI output names the executable."""

    def __init__(self, message: str, report: "TraceReport"):
        super().__init__(message)
        self.report = report


@dataclass
class TraceReport:
    """What one armed window observed."""

    compiles: Dict[str, int] = field(default_factory=dict)  # executable -> misses
    backend_compiles: int = 0
    transfer_violations: List[str] = field(default_factory=list)
    steps: int = 0

    @property
    def total_recompiles(self) -> int:
        # The named ledger is primary; the monitoring counter catches misses
        # whose log line we failed to parse (format drift across jax versions).
        return max(sum(self.compiles.values()), self.backend_compiles)

    @property
    def host_transfers(self) -> int:
        return len(self.transfer_violations)

    def summary(self) -> str:
        if not self.compiles and not self.backend_compiles and not self.transfer_violations:
            return "clean: 0 recompiles, 0 guarded transfers"
        parts = []
        if self.compiles:
            named = ", ".join(f"{name} x{n}" for name, n in sorted(self.compiles.items()))
            parts.append(f"recompiled: {named}")
        elif self.backend_compiles:
            parts.append(f"{self.backend_compiles} backend compile(s) (unnamed)")
        if self.transfer_violations:
            parts.append(f"{len(self.transfer_violations)} guarded transfer(s)")
        return "; ".join(parts)


class _CompileLogHandler(logging.Handler):
    def __init__(self, guard: "TraceGuard"):
        super().__init__(level=logging.DEBUG)
        self._guard = guard

    def emit(self, record: logging.LogRecord):
        try:
            message = record.getMessage()
        except Exception:  # noqa: BLE001 — never let telemetry break the step
            return
        m = _COMPILE_LOG_RE.search(message)
        if m:
            name = m.group(1)
            self._guard.compiles[name] = self._guard.compiles.get(name, 0) + 1


class TraceGuard:
    """Armed window asserting "this code neither recompiles nor host-syncs".

    Parameters:
      - ``transfer_guard``: jax transfer-guard level while armed ("disallow" by
        default; "log" to only trace, None to leave transfers unguarded).
      - ``on_violation``: "raise" — exit raises `TraceGuardViolation` when any
        cache miss was observed; "record" — only keep the ledger.
      - ``name``: label used in violation messages.

    The per-window counters (`compiles`, `transfer_violations`, `steps`)
    accumulate across re-entries until `reset()`.
    """

    def __init__(
        self,
        transfer_guard: Optional[str] = "disallow",
        on_violation: str = "raise",
        name: str = "trace-guard",
        guard_device_to_device: bool = False,
    ):
        if on_violation not in ("raise", "record"):
            raise ValueError("on_violation must be 'raise' or 'record'")
        self.transfer_guard = transfer_guard
        # d2d is OFF by default: replicating an uncommitted scalar operand
        # across the mesh at dispatch is routine GSPMD placement, not a host
        # sync — guarding it would flag every sharded train step.
        self.guard_device_to_device = guard_device_to_device
        self.on_violation = on_violation
        self.name = name
        self.compiles: Dict[str, int] = {}
        self.backend_compiles = 0
        self.transfer_violations: List[str] = []
        self.steps = 0
        self._depth = 0
        self._stack: Optional[contextlib.ExitStack] = None
        self._handler: Optional[_CompileLogHandler] = None
        self._saved_log_compiles = None
        self._saved_propagate = True
        self._saved_dispatch_level = logging.NOTSET

    # ------------------------------------------------------------------ arming
    def __enter__(self) -> "TraceGuard":
        self._depth += 1
        if self._depth > 1:
            return self
        import jax

        _ensure_monitoring_listener()
        _ARMED_GUARDS.append(self)
        self._saved_log_compiles = bool(jax.config.jax_log_compiles)
        pxla_logger = logging.getLogger(_PXLA_LOGGER)
        if not self._saved_log_compiles:
            jax.config.update("jax_log_compiles", True)
            # We turned the compile logs on for OUR handler only — keep them
            # out of the user's stderr (restored on exit). If the user had
            # jax_log_compiles on already, their logging setup is respected.
            self._saved_propagate = pxla_logger.propagate
            pxla_logger.propagate = False
            dispatch_logger = logging.getLogger("jax._src.dispatch")
            self._saved_dispatch_level = dispatch_logger.level
            dispatch_logger.setLevel(logging.ERROR)
        self._handler = _CompileLogHandler(self)
        pxla_logger.addHandler(self._handler)
        self._stack = contextlib.ExitStack()
        if self.transfer_guard is not None:
            self._stack.enter_context(jax.transfer_guard_host_to_device(self.transfer_guard))
            self._stack.enter_context(jax.transfer_guard_device_to_host(self.transfer_guard))
            if self.guard_device_to_device:
                self._stack.enter_context(jax.transfer_guard_device_to_device(self.transfer_guard))
        return self

    def __exit__(self, exc_type, exc, tb):
        self._depth -= 1
        if self._depth > 0:
            return False
        import jax

        # Disarm from the monitoring fan-out FIRST: compiles outside the armed
        # window must not reach this guard's ledger (and per-step re-arming
        # must not grow the list).
        try:
            _ARMED_GUARDS.remove(self)
        except ValueError:
            pass
        if self._stack is not None:
            self._stack.close()
            self._stack = None
        if self._handler is not None:
            logging.getLogger(_PXLA_LOGGER).removeHandler(self._handler)
            self._handler = None
        if self._saved_log_compiles is False:
            jax.config.update("jax_log_compiles", False)
            logging.getLogger(_PXLA_LOGGER).propagate = self._saved_propagate
            logging.getLogger("jax._src.dispatch").setLevel(self._saved_dispatch_level)
        self._saved_log_compiles = None
        if exc is not None:
            # An in-flight exception (possibly a transfer violation) wins;
            # record it on the way out but don't mask it.
            self.observe(exc)
            return False
        if self.on_violation == "raise" and self.report().total_recompiles:
            raise TraceGuardViolation(
                f"[{self.name}] steady-state step recompiled — {self.report().summary()}",
                self.report(),
            )
        return False

    # ------------------------------------------------------------------ ledger
    def reset(self):
        self.compiles = {}
        self.backend_compiles = 0
        self.transfer_violations = []
        self.steps = 0

    def report(self) -> TraceReport:
        return TraceReport(
            compiles=dict(self.compiles),
            backend_compiles=self.backend_compiles,
            transfer_violations=list(self.transfer_violations),
            steps=self.steps,
        )

    @property
    def total_recompiles(self) -> int:
        return self.report().total_recompiles

    @property
    def host_transfers(self) -> int:
        return len(self.transfer_violations)

    # ----------------------------------------------------------------- helpers
    @staticmethod
    def is_transfer_violation(exc: BaseException) -> bool:
        """Does this exception come from an armed jax transfer guard?"""
        return bool(_TRANSFER_RE.search(str(exc)))

    def observe(self, exc: BaseException) -> bool:
        """Record `exc` if it is a guarded-transfer error (serving's fault
        isolation calls this before swallowing a step exception, so swallowed
        violations still reach the ledger). Returns True when recorded."""
        if self.is_transfer_violation(exc):
            self.transfer_violations.append(str(exc).splitlines()[0][:200])
            return True
        return False

    def wrap(self, fn: Callable, warmup: int = 1) -> Callable:
        """Per-call arming with a warmup allowance: the first `warmup` calls
        run unguarded (compiles are expected), every later call runs inside the
        armed guard — so call N+1 onward raising means a *steady-state*
        recompile, reported with the executable's name."""

        state = {"calls": 0}

        def guarded(*args, **kwargs):
            state["calls"] += 1
            if state["calls"] <= warmup:
                return fn(*args, **kwargs)
            with self:
                # In-flight exceptions (including guarded transfers) are
                # observe()d once by __exit__ on the way out.
                self.steps += 1
                return fn(*args, **kwargs)

        guarded.__wrapped__ = fn  # type: ignore[attr-defined]
        guarded.trace_guard = self  # type: ignore[attr-defined]
        return guarded
