"""The TPU-hazard rule registry.

Each rule names one mechanical way the repo's compile-once discipline breaks:
a host sync on a traced value, a recompile trigger, a donation misuse, or an
in-repo convention violation. Rules are data (`Rule`), detection lives in
`linter.py` — the registry is what the CLI catalog, the docs table, and the
suppression parser all key on.

Severity ladder:
  - ``error``  — breaks the discipline outright (host sync inside a jitted
    program, donated buffer reused): CI fails on these (`--fail-on error`).
  - ``warn``   — a recompile / throughput hazard that has legitimate uses
    (module-level jit in a script, a per-step ``float(loss)`` for logging);
    reviewers decide, ``--fail-on warn`` opts a tree into strictness.
  - ``info``   — style-level observations.
"""

from __future__ import annotations

from dataclasses import dataclass

#: Ordered severities, weakest first. Comparisons use list position.
SEVERITIES = ("info", "warn", "error")


@dataclass(frozen=True)
class Rule:
    """One linter rule: a stable id (``TPU1xx``), a short slug used in
    suppression comments (`# tpu-lint: disable=<id or slug>`), the severity it
    reports at, and a fixit hint rendered with every finding."""

    id: str
    slug: str
    severity: str
    summary: str
    fixit: str

    def __post_init__(self):
        if self.severity not in SEVERITIES:
            raise ValueError(f"unknown severity {self.severity!r} for rule {self.id}")


RULES = (
    Rule(
        id="TPU101",
        slug="host-sync-item",
        severity="error",
        summary=".item() on a traced value inside jit-reachable code",
        fixit="keep the value on device (jnp ops) or return it from the jitted "
        "program and read it at the step boundary",
    ),
    Rule(
        id="TPU102",
        slug="host-scalar-cast",
        severity="error",
        summary="float()/int()/bool() on a traced array inside jit-reachable code",
        fixit="use jnp.float32(x)/x.astype(...) to stay traced; host casts force "
        "a device sync and fail under jit",
    ),
    Rule(
        id="TPU103",
        slug="host-transfer-numpy",
        severity="error",
        summary="np.asarray/np.array/jax.device_get on a traced value inside "
        "jit-reachable code",
        fixit="use jnp equivalents inside the program; device_get/np conversion "
        "belongs at the step boundary",
    ),
    Rule(
        id="TPU104",
        slug="traced-bool-branch",
        severity="error",
        summary="Python if/while on a traced array (implicit bool()) inside "
        "jit-reachable code",
        fixit="branch with jnp.where / jax.lax.cond / jax.lax.select; Python "
        "control flow on traced values raises TracerBoolConversionError",
    ),
    Rule(
        id="TPU105",
        slug="closure-scalar-capture",
        severity="warn",
        summary="Python scalar from an enclosing scope captured by a jitted "
        "closure (baked in at trace time)",
        fixit="pass the scalar as an operand (jnp.float32(x) argument) so "
        "changing it never recompiles; closure captures are compile-time "
        "constants",
    ),
    Rule(
        id="TPU106",
        slug="jit-in-loop",
        severity="warn",
        summary="jax.jit(...) called inside a loop body (fresh cache per "
        "iteration)",
        fixit="hoist the jax.jit call out of the loop (or memoize per static "
        "key) so the executable cache survives iterations",
    ),
    Rule(
        id="TPU107",
        slug="static-argnums-varying",
        severity="error",
        summary="a static_argnums position fed a loop-varying value (recompile "
        "every iteration)",
        fixit="pass per-step values as traced operands; reserve static_argnums "
        "for genuinely constant configuration",
    ),
    Rule(
        id="TPU108",
        slug="donated-reuse",
        severity="error",
        summary="an argument donated via donate_argnums is read again after "
        "the call",
        fixit="rebind the name to the call's output (the donated buffer is "
        "invalidated in place) or drop the donation",
    ),
    Rule(
        id="TPU109",
        slug="module-level-jit",
        severity="warn",
        summary="jax.jit invoked at module import time",
        fixit="build jitted callables lazily (inside a function/class) so "
        "importing the module never traces or touches a backend",
    ),
    Rule(
        id="TPU110",
        slug="pjit-no-sharding",
        severity="warn",
        summary="pjit without in_shardings/out_shardings annotations",
        fixit="annotate shardings explicitly (or use jax.jit + "
        "with_sharding_constraint); unannotated pjit silently replicates",
    ),
    Rule(
        id="TPU111",
        slug="loop-host-sync",
        severity="warn",
        summary="per-iteration host sync (float()/.item()) on a stepped value "
        "inside a host loop",
        fixit="accumulate on device and read once at the epoch/loop boundary; "
        "a per-step sync serializes dispatch against the device",
    ),
    Rule(
        id="TPU112",
        slug="span-host-sync",
        severity="warn",
        summary="device-value read (.item()/float()/np.asarray) used in a "
        "tracer span/event annotation or inside a `with tracer.span(...)` block",
        fixit="read device values at the step boundary (np.asarray/.item() on "
        "already-fetched outputs) and annotate spans with host scalars; an "
        "instrumentation-side read hides a blocking device sync in the very "
        "code that exists to observe the hot path",
    ),
    Rule(
        id="TPU113",
        slug="blocking-ckpt-in-jit",
        severity="error",
        summary="blocking checkpoint I/O (save_pytree/atomic_write/save_state/"
        "file_sha256/...) called inside jit-reachable code",
        fixit="checkpoint at the step boundary from host code — snapshot the "
        "state (snapshot_pytree) and hand it to save_state (async_save=True "
        "commits it on the background committer); serialize+fsync inside a "
        "traced program is a host sync at best and a trace error at worst",
    ),
    Rule(
        id="TPU114",
        slug="unbounded-serving-queue",
        severity="warn",
        summary="ContinuousBatcher/Router constructed without bounded queue "
        "backpressure (max_queue) — or a Router without a default request "
        "deadline — in jit-adjacent serving code",
        fixit="pass max_queue=<bound> so overload surfaces as QueueFull "
        "backpressure instead of unbounded host-memory growth, and give "
        "Router a default_deadline_s=<seconds> so every request reaches a "
        "terminal finish_reason even when a replica stalls",
    ),
    Rule(
        id="TPU115",
        slug="kernel-fallback",
        severity="warn",
        summary='serving decode/verify programs whose KV read is pinned by a '
        'literal attention_impl="xla", or a Pallas attention kernel forced '
        "into interpret mode outside test code",
        fixit="thread attention_impl as a value the caller sets, or leave it "
        "out (an engine that names no read takes the Pallas page-walk kernel "
        "on a TPU where it can — about half the XLA read's device time a layer "
        "on a v5e, PERF.md section 6, PR 37 — and the XLA read elsewhere) — or "
        "suppress where the pin is deliberate; interpret=True is the CPU-test "
        "shim, production call "
        "sites must let the kernel compile (interpret=None auto-selects)",
    ),
    Rule(
        id="TPU116",
        slug="worker-loop-no-heartbeat",
        severity="warn",
        summary="subprocess worker loop without a heartbeat deadline, or an IPC "
        "recv with no timeout inside a loop",
        fixit="pass heartbeat_deadline_s=<seconds> to serve_worker/WorkerLoop (an "
        "orphaned worker must exit, not leak a process + device memory) and give "
        "every looped recv_frame/recv_message a timeout_s=<seconds> — an unbounded "
        "IPC read turns a hung peer into a hung fleet controller, invisible to the "
        "health machine that exists to catch it",
    ),
    Rule(
        id="TPU117",
        slug="quant-scale-literal",
        severity="warn",
        summary="a quantization scale passed as a Python numeric literal to a "
        "serving attention/kernel seam, or a kv_cache_dtype literal off the "
        "supported set",
        fixit="thread scales as traced ARRAY operands (the pool's parallel "
        "key_scale/value_scale arrays) — a Python scalar bakes the scale into "
        "the executable at trace time, so every scale change retraces the "
        'decode program; kv_cache_dtype must be one of "bf16" | "int8" | '
        '"fp8_e4m3" (static config, ops/quantization.KV_CACHE_DTYPES) — an '
        "off-set literal fails at engine construction, or worse, silently "
        "selects nothing",
    ),
    Rule(
        id="TPU118",
        slug="tp-replicated-operand",
        severity="warn",
        summary="a mesh-spanning serving module places params/pool trees with "
        "device_put but no NamedSharding — the tree lands on one device and "
        "jit replicates it to every chip (silent full replication)",
        fixit="pass a NamedSharding pytree to device_put (derive it with "
        "parallel.sharding.derive_tp_param_shardings / "
        "derive_tp_cache_shardings from the model family's Megatron rules) — "
        "or build the engine with ContinuousBatcher(tp=N), whose params "
        "setter and cache init place everything sharded; an unsharded "
        "placement serves token-identically while spending N x the per-chip "
        "HBM the mesh exists to save (the accidental-fallback analogue of "
        "TPU115)",
    ),
    Rule(
        id="TPU119",
        slug="dead-partition-rule",
        severity="warn",
        summary="a (pattern, spec) entry in a sharding-rules table whose regex "
        "matches no parameter path of the model it ships with, or a literal "
        "per-leaf PartitionSpec scattered in model code outside the rule "
        "tables",
        fixit="delete the dead entry (or fix its regex to name a module the "
        "model actually defines) — an entry that matches nothing silently "
        "replicates the weight it was written to shard, the same failure the "
        "planner's audit would catch; and keep per-leaf PartitionSpecs out of "
        "model code: route them through the family's *_SHARDING_RULES table "
        "or let sharding_rules=\"auto\" (parallel.planner) emit the table, so "
        "every placement decision stays visible to the one derivation seam",
    ),
    Rule(
        id="TPU120",
        slug="replicated-optimizer-state",
        severity="warn",
        summary="a module that builds a training mesh with a \"data\" axis "
        "places an optimizer-state tree with device_put but no (or a "
        "replicated) sharding — fp32 Adam moments are 8 bytes/param on EVERY "
        "chip, the single largest avoidable HBM account in data-parallel "
        "training",
        fixit="shard the weight update: derive the state's placement with "
        "parallel.sharding.derive_opt_state_shardings (pass the planner's "
        "opt_rules table for ZeRO sharding along \"data\" even where params "
        "replicate — plan_train_sharding emits it), or prepare the optimizer "
        "through Accelerator.prepare with sharding_rules=\"auto\", whose "
        "AcceleratedOptimizer init/out_shardings discipline places moments "
        "sharded from the first step; reduce-scatter + all-gather moves the "
        "same ICI bytes the all-reduce already paid, so the sharded update "
        "is pure per-chip-HBM savings (Xu et al., cross-replica weight-update "
        "sharding)",
    ),
    Rule(
        id="TPU121",
        slug="host-hop-in-stage-handoff",
        severity="warn",
        summary="a module that builds a \"pipeline\" mesh axis moves an "
        "inter-stage activation/gradient carry through the host — "
        "jax.device_get, a numpy coercion (np.asarray/np.array), or "
        ".block_until_ready() on the handoff path serializes the 1F1B "
        "schedule on PCIe and stalls every stage behind the transfer",
        fixit="ship the carry submesh-to-submesh with jax.device_put(carry, "
        "NamedSharding(next_stage_mesh, spec)) — a pure device-to-device ICI "
        "transfer that async dispatch overlaps with the other stages' compute "
        "(parallel.mpmd's _ship seam); keep TraceGuard armed around the step "
        "so any host round-trip that does sneak in fails loudly instead of "
        "silently flattening the pipeline",
    ),
    Rule(
        id="TPU122",
        slug="unbounded-reconnect",
        severity="warn",
        summary="a serving-transport module reconnects or reads the wire "
        "without a bound — socket.create_connection with no timeout, a "
        "recv loop on a socket that was never given a deadline, or a "
        "reconnect retried in a loop with neither a backoff cap nor a "
        "deadline budget — one partitioned peer then hangs the controller "
        "(or hot-loops the dial) instead of surfacing a transport fault "
        "the fleet can route around",
        fixit="bound every wire wait: dial with "
        "socket.create_connection(addr, timeout=...), arm a deadline before "
        "protocol reads (settimeout, or select-based framing like "
        "worker.recv_frame's timeout_s), and drive reconnect attempts "
        "through a budgeted state machine — capped exponential backoff plus "
        "a reconnect_deadline_s that escalates to the worker-death/respawn "
        "path when exhausted (worker.SubprocessEngine is the reference "
        "shape: reconnect(timeout_s=...) per attempt, never a bare retry "
        "loop)",
    ),
)

RULES_BY_ID = {r.id: r for r in RULES}
RULES_BY_SLUG = {r.slug: r for r in RULES}


def resolve_rule(token: str):
    """A suppression/CLI token -> Rule, accepting either the id or the slug
    (case-insensitive). Returns None for unknown tokens — suppressions never
    crash a lint run."""
    token = token.strip()
    return RULES_BY_ID.get(token.upper()) or RULES_BY_SLUG.get(token.lower())


def severity_at_least(severity: str, floor: str) -> bool:
    return SEVERITIES.index(severity) >= SEVERITIES.index(floor)
