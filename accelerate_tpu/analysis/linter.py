"""AST-based TPU-hazard linter (stdlib `ast` only — no jax import, so the lint
runs on CI boxes with no accelerator stack).

The pass is module-local and two-phase:

  1. **Index**: resolve import aliases (``jax``, ``jnp``, ``np``, bare ``jit``/
     ``pjit``), find every *jit root* — a function jitted by decorator, by a
     ``jax.jit(fn)`` reference, or handed to ``jax.lax`` control flow — then
     close over module-local calls and nested defs to get the **jit-reachable**
     set. Code outside that set is host code, where ``np.asarray``/``float()``
     at step boundaries is the sanctioned discipline, not a hazard.
  2. **Check**: walk each function with per-rule detectors (see `rules.py` for
     the catalog). Traced-value tracking is a deliberately simple fixpoint over
     assignments: a function parameter or anything computed from ``jnp``/
     ``jax`` calls is traced; ``.shape``/``.ndim``/``.dtype`` projections are
     static and exempt.

Suppressions: a ``# tpu-lint: disable=<rule-id>[,<rule-id>]`` comment on the
flagged line drops those findings (``all`` drops every rule); a
``# tpu-lint: disable-file=<rule-id>`` comment anywhere silences the rule for
the whole file. Unknown tokens are ignored rather than fatal.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .report import Finding
from .rules import resolve_rule

#: Array methods whose *call* on a traced value yields a traced value that a
#: Python branch would then implicitly bool() (``if x.any():``).
ARRAY_TEST_METHODS = {"any", "all", "sum", "max", "min", "mean", "prod"}
#: Static projections of an array — branching on these is shape-level Python
#: and perfectly jit-safe.
STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}
#: ``jax.lax`` combinators whose function-valued arguments get traced.
LAX_TRACED_FN_CONSUMERS = {
    "scan", "while_loop", "fori_loop", "cond", "switch", "map", "associative_scan",
}
#: The tracing API surface (`telemetry.tracing`): calls whose arguments are
#: span annotations, and whose `with` blocks wrap hot-path dispatches.
SPAN_API_ATTRS = {"span", "start_span", "event", "annotate"}
#: Blocking checkpoint-I/O entry points (`accelerate_tpu.checkpointing` + the
#: Accelerator facade): serialize/fsync/digest work that must never run inside
#: a traced program (rule TPU113). Matched as a bare name or the final
#: attribute of a call chain (`accelerator.save_state(...)`, `mgr.save(...)`
#: is deliberately NOT here — `.save` alone is too generic).
CHECKPOINT_IO_CALLS = {
    "save_pytree",
    "save_pytree_host_shards",
    "save_pytree_shards",
    "save_accelerator_state",
    "write_accelerator_snapshot",
    "save_state",
    "load_state",
    "atomic_write",
    "atomic_write_bytes",
    "atomic_write_json",
    "file_sha256",
    "write_checkpoint_manifest",
    "save_custom_state",
}

_SUPPRESS_LINE = re.compile(r"#\s*tpu-lint:\s*disable=([A-Za-z0-9_,\- ]+)")
_SUPPRESS_FILE = re.compile(r"#\s*tpu-lint:\s*disable-file=([A-Za-z0-9_,\- ]+)")


def _parse_suppressions(source: str) -> Tuple[Dict[int, Set[str]], Set[str]]:
    """-> ({line: {rule ids}}, {file-wide rule ids}); tokens resolve via id or
    slug, ``all`` means every rule."""
    per_line: Dict[int, Set[str]] = {}
    file_wide: Set[str] = set()

    def resolve_tokens(blob: str) -> Set[str]:
        out: Set[str] = set()
        for token in blob.split(","):
            token = token.strip()
            if not token:
                continue
            if token.lower() == "all":
                out.add("all")
                continue
            rule = resolve_rule(token)
            if rule is not None:
                out.add(rule.id)
        return out

    for lineno, line in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_FILE.search(line)
        if m:
            file_wide |= resolve_tokens(m.group(1))
            continue
        m = _SUPPRESS_LINE.search(line)
        if m:
            tokens = resolve_tokens(m.group(1))
            per_line.setdefault(lineno, set()).update(tokens)
            if line.strip().startswith("#"):
                # A standalone suppression comment covers the next line too
                # (the statement it annotates).
                per_line.setdefault(lineno + 1, set()).update(tokens)
    return per_line, file_wide


class _ModuleIndex:
    """Import aliases + function defs + the jit-reachable set for one module."""

    def __init__(self, tree: ast.Module):
        self.tree = tree
        self.jax_aliases: Set[str] = set()
        #: A REAL jax/jax.numpy import was seen (the conventional jnp/np
        #: fallbacks below don't count): the "jit-adjacent module" signal
        #: rules like TPU114 scope themselves to.
        self.imports_jax = False
        #: A flax import was seen: the "model module" signal TPU119 scopes
        #: itself to (sharding-rule tables ship next to the flax modules
        #: whose parameter paths they must match).
        self.imports_flax = False
        self.jnp_aliases: Set[str] = set()
        self.np_aliases: Set[str] = set()
        self.lax_aliases: Set[str] = set()
        self.jit_names: Set[str] = set()  # bare names bound to jax.jit / pjit
        self.pjit_names: Set[str] = set()
        self.partial_names: Set[str] = set()
        self.defs_by_name: Dict[str, List[ast.AST]] = {}
        self.jit_calls: List[ast.Call] = []  # every jax.jit / pjit invocation

        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                child._tpu_parent = parent  # type: ignore[attr-defined]

        self._collect_imports()
        self._collect_defs()
        self.jit_roots = self._find_jit_roots()
        self.reachable = self._close_reachability(self.jit_roots)

    # -- indexing ---------------------------------------------------------------
    def _collect_imports(self):
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    name, bound = alias.name, alias.asname or alias.name.split(".")[0]
                    if name == "jax":
                        self.jax_aliases.add(bound)
                        self.imports_jax = True
                    elif name in ("jax.numpy",):
                        self.jnp_aliases.add(alias.asname or "jax")
                        self.imports_jax = True
                    elif name in ("numpy",):
                        self.np_aliases.add(bound)
                    elif name == "flax" or name.startswith("flax."):
                        self.imports_flax = True
                    elif name == "functools":
                        pass
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod == "jax" or mod.startswith("jax."):
                    self.imports_jax = True
                if mod == "flax" or mod.startswith("flax."):
                    self.imports_flax = True
                for alias in node.names:
                    bound = alias.asname or alias.name
                    if mod == "jax" and alias.name == "numpy":
                        self.jnp_aliases.add(bound)
                    elif mod == "jax" and alias.name == "jit":
                        self.jit_names.add(bound)
                    elif mod == "jax" and alias.name == "lax":
                        self.lax_aliases.add(bound)
                    elif alias.name == "pjit" and "pjit" in mod:
                        self.pjit_names.add(bound)
                    elif mod == "functools" and alias.name == "partial":
                        self.partial_names.add(bound)
        # Conventional fallbacks: most sources spell these jnp/np even when the
        # import is renamed out of our sight (e.g. injected globals in fixtures).
        self.jnp_aliases.add("jnp")
        self.np_aliases.add("np")

    def _collect_defs(self):
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs_by_name.setdefault(node.name, []).append(node)

    # -- alias predicates -------------------------------------------------------
    def _attr_root(self, node: ast.AST) -> Optional[List[str]]:
        """Attribute/Name chain -> ['jax', 'lax', 'scan'] (None if not a chain)."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            parts.append(node.id)
            return list(reversed(parts))
        return None

    def is_jit_func(self, node: ast.AST) -> bool:
        """Does this expression denote jax.jit (or pjit)?"""
        chain = self._attr_root(node)
        if chain is None:
            return False
        if len(chain) == 1:
            return chain[0] in self.jit_names or chain[0] in self.pjit_names
        if chain[0] in self.jax_aliases and chain[-1] in ("jit", "pjit"):
            return True
        return chain[-1] == "pjit"  # pjit.pjit / experimental chains

    def is_pjit_func(self, node: ast.AST) -> bool:
        chain = self._attr_root(node)
        if chain is None:
            return False
        return chain[-1] == "pjit" or (len(chain) == 1 and chain[0] in self.pjit_names)

    def is_jnp_rooted(self, node: ast.AST) -> bool:
        chain = self._attr_root(node)
        return bool(chain) and (chain[0] in self.jnp_aliases or chain[0] in self.jax_aliases or chain[0] in self.lax_aliases)

    def is_np_rooted(self, node: ast.AST) -> bool:
        chain = self._attr_root(node)
        return bool(chain) and chain[0] in self.np_aliases

    # -- jit roots & reachability ----------------------------------------------
    def _jit_target_of_call(self, call: ast.Call) -> Optional[str]:
        """`jax.jit(fn, ...)` / `partial(jax.jit, ...)` -> 'fn' when it's a bare
        Name that resolves to a module-local def."""
        func = call.func
        is_jit = self.is_jit_func(func)
        if not is_jit and isinstance(func, ast.Call):
            # partial(jax.jit, ...) applied later — the partial call IS the jit.
            inner = func
            if (
                isinstance(inner.func, ast.Name)
                and inner.func.id in self.partial_names
                and inner.args
                and self.is_jit_func(inner.args[0])
            ):
                is_jit = True
        if not is_jit:
            return None
        if call.args and isinstance(call.args[0], ast.Name):
            return call.args[0].id
        return None

    def _find_jit_roots(self) -> Set[ast.AST]:
        roots: Set[ast.AST] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if self.is_jit_func(dec):
                        roots.add(node)
                    elif isinstance(dec, ast.Call):
                        if self.is_jit_func(dec.func):
                            roots.add(node)
                        elif (
                            isinstance(dec.func, ast.Name)
                            and dec.func.id in self.partial_names
                            and dec.args
                            and self.is_jit_func(dec.args[0])
                        ):
                            roots.add(node)
            elif isinstance(node, ast.Call):
                if self.is_jit_func(node.func) or (
                    isinstance(node.func, ast.Call) and self._jit_target_of_call(node) is not None
                ):
                    self.jit_calls.append(node)
                    target = self._jit_target_of_call(node)
                    if target and target in self.defs_by_name:
                        roots.update(self.defs_by_name[target])
                else:
                    chain = self._attr_root(node.func)
                    if (
                        chain
                        and chain[-1] in LAX_TRACED_FN_CONSUMERS
                        and (chain[0] in self.jax_aliases or chain[0] in self.lax_aliases)
                    ):
                        for arg in node.args:
                            if isinstance(arg, ast.Name) and arg.id in self.defs_by_name:
                                roots.update(self.defs_by_name[arg.id])
        return roots

    def _close_reachability(self, roots: Set[ast.AST]) -> Set[ast.AST]:
        """Roots + nested defs + module-local functions they call, to fixpoint."""
        reachable = set(roots)
        frontier = list(roots)
        while frontier:
            fn = frontier.pop()
            for node in ast.walk(fn):
                new: List[ast.AST] = []
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                    new.append(node)
                elif isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                    new.extend(self.defs_by_name.get(node.func.id, ()))
                for cand in new:
                    if cand not in reachable:
                        reachable.add(cand)
                        frontier.append(cand)
        return reachable


def _enclosing_function(node: ast.AST) -> Optional[ast.AST]:
    cur = getattr(node, "_tpu_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return cur
        cur = getattr(cur, "_tpu_parent", None)
    return None


def _enclosing_loop(node: ast.AST, stop_at: Optional[ast.AST] = None) -> Optional[ast.AST]:
    cur = getattr(node, "_tpu_parent", None)
    while cur is not None and cur is not stop_at:
        if isinstance(cur, (ast.For, ast.While)):
            return cur
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return None  # a nested def is a new host frame, not "inside the loop"
        cur = getattr(cur, "_tpu_parent", None)
    return None


#: Annotation spellings that declare a parameter host-static: a `use_scaler:
#: bool` or `k: int` param is a trace-time constant, not a traced array.
_STATIC_ANNOTATION = re.compile(
    r"^(?:typing\.)?(?:Optional\[)?(?:bool|int|float|str|bytes)\]?$"
)


def _param_names(fn: ast.AST) -> Set[str]:
    a = fn.args
    names = []
    for p in (*a.posonlyargs, *a.args, *a.kwonlyargs):
        if p.annotation is not None:
            try:
                if _STATIC_ANNOTATION.match(ast.unparse(p.annotation)):
                    continue
            except Exception:  # noqa: BLE001 — exotic annotation, assume traced
                pass
        names.append(p.arg)
    if a.vararg:
        names.append(a.vararg.arg)
    return {n for n in names if n not in ("self", "cls")}


class _FunctionChecker:
    """Per-function rule evaluation. `jit_reachable` switches between the
    traced-code rule set (TPU101-104) and the host-loop rule (TPU111)."""

    def __init__(self, index: _ModuleIndex, fn: ast.AST, path: str):
        self.index = index
        self.fn = fn
        self.path = path
        self.findings: List[Finding] = []
        self.traced: Set[str] = _param_names(fn)
        self._infer_traced_locals()

    def emit(self, node: ast.AST, rule_id: str, message: str):
        self.findings.append(
            Finding(self.path, node.lineno, node.col_offset, rule_id, message)
        )

    # -- traced-name inference --------------------------------------------------
    def _direct_statements(self):
        """Statements belonging to this function, excluding nested defs (their
        params are their own frame's business)."""
        for node in ast.walk(self.fn):
            owner = _enclosing_function(node) if node is not self.fn else self.fn
            if owner is self.fn:
                yield node

    def _infer_traced_locals(self):
        for _ in range(2):  # tiny fixpoint: handles one level of chained assigns
            for node in self._direct_statements():
                if isinstance(node, ast.Assign) and self._is_traced_expr(node.value):
                    for tgt in node.targets:
                        for name in ast.walk(tgt):
                            if isinstance(name, ast.Name):
                                self.traced.add(name.id)

    def _is_traced_expr(self, node: ast.AST) -> bool:
        """Does evaluating this expression yield (or require syncing) a traced
        array? Static projections (.shape and friends), `is None` tests and
        len() stay host-side."""
        if isinstance(node, ast.Name):
            return node.id in self.traced
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return False
            return False  # plain attribute access (config.do_sample) is host data
        if isinstance(node, ast.Subscript):
            return self._is_traced_expr(node.value)
        if isinstance(node, ast.Call):
            func = node.func
            if self.index.is_jnp_rooted(func):
                return True
            if isinstance(func, ast.Attribute) and func.attr in ARRAY_TEST_METHODS:
                return self._is_traced_expr(func.value)
            return False
        if isinstance(node, ast.UnaryOp):
            return self._is_traced_expr(node.operand)
        if isinstance(node, ast.BinOp):
            return self._is_traced_expr(node.left) or self._is_traced_expr(node.right)
        if isinstance(node, ast.BoolOp):
            return any(self._is_traced_expr(v) for v in node.values)
        if isinstance(node, ast.Compare):
            if any(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
            return self._is_traced_expr(node.left) or any(
                self._is_traced_expr(c) for c in node.comparators
            )
        if isinstance(node, ast.IfExp):
            return self._is_traced_expr(node.body) or self._is_traced_expr(node.orelse)
        return False

    # -- jit-reachable rules ----------------------------------------------------
    def check_traced_rules(self):
        for node in self._direct_statements():
            if isinstance(node, ast.Call):
                self._check_item(node)
                self._check_scalar_cast(node)
                self._check_numpy_transfer(node)
                self._check_checkpoint_io(node)
            elif isinstance(node, (ast.If, ast.While)):
                if self._is_traced_expr(node.test):
                    kind = "if" if isinstance(node, ast.If) else "while"
                    self.emit(
                        node,
                        "TPU104",
                        f"`{kind}` on a traced value implicitly calls bool() — a "
                        "host sync that fails under jit; use jnp.where/lax.cond",
                    )

    def _check_item(self, node: ast.Call):
        if isinstance(node.func, ast.Attribute) and node.func.attr == "item" and not node.args:
            self.emit(
                node,
                "TPU101",
                ".item() inside jit-reachable code syncs the device and fails "
                "under tracing",
            )

    def _check_checkpoint_io(self, node: ast.Call):
        """TPU113: blocking checkpoint I/O in jit-reachable code. Serialize +
        fsync under trace is a host sync when it works and a tracer leak when
        it doesn't; checkpoints belong at step boundaries (async_save moves
        even the boundary cost to a background committer)."""
        func = node.func
        name = None
        if isinstance(func, ast.Name) and func.id in CHECKPOINT_IO_CALLS:
            name = func.id
        elif isinstance(func, ast.Attribute) and func.attr in CHECKPOINT_IO_CALLS:
            name = func.attr
        if name is not None:
            self.emit(
                node,
                "TPU113",
                f"{name}() is blocking checkpoint I/O inside jit-reachable code — "
                "checkpoint from host code at the step boundary (async_save commits "
                "in the background)",
            )

    def _check_scalar_cast(self, node: ast.Call):
        if (
            isinstance(node.func, ast.Name)
            and node.func.id in ("float", "int", "bool")
            and len(node.args) == 1
            and self._is_traced_expr(node.args[0])
        ):
            self.emit(
                node,
                "TPU102",
                f"{node.func.id}() on a traced value is a host sync (and a "
                "TracerConversionError under jit)",
            )

    def _check_numpy_transfer(self, node: ast.Call):
        func = node.func
        chain = self.index._attr_root(func)
        if chain is None:
            return
        if (
            len(chain) >= 2
            and chain[0] in self.index.np_aliases
            and chain[-1] in ("asarray", "array")
            and node.args
            and self._is_traced_expr(node.args[0])
        ):
            self.emit(
                node,
                "TPU103",
                f"{'.'.join(chain)}() on a traced value forces a device-to-host "
                "copy inside the program",
            )
        elif chain[0] in self.index.jax_aliases and chain[-1] == "device_get":
            self.emit(
                node,
                "TPU103",
                "jax.device_get inside jit-reachable code is a host transfer; "
                "return the value and read it at the step boundary",
            )

    # -- host-side rules --------------------------------------------------------
    def check_host_loop_syncs(self):
        """TPU111: float()/.item() on a value produced by a call in the SAME
        loop — the per-step logging sync that serializes dispatch."""
        for loop in self._direct_statements():
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            stepped: Set[str] = set()
            for node in ast.walk(loop):
                if _enclosing_loop(node, stop_at=self.fn) is not loop:
                    continue
                if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                    for tgt in node.targets:
                        for name in ast.walk(tgt):
                            if isinstance(name, ast.Name):
                                stepped.add(name.id)
            for node in ast.walk(loop):
                if _enclosing_loop(node, stop_at=self.fn) is not loop:
                    continue
                if not isinstance(node, ast.Call):
                    continue
                synced = None
                if (
                    isinstance(node.func, ast.Name)
                    and node.func.id == "float"
                    and len(node.args) == 1
                    and isinstance(node.args[0], ast.Name)
                    and node.args[0].id in stepped
                ):
                    synced = f"float({node.args[0].id})"
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in stepped
                ):
                    synced = f"{node.func.value.id}.item()"
                if synced:
                    self.emit(
                        node,
                        "TPU111",
                        f"{synced} every loop iteration blocks on the device; "
                        "accumulate on device and read once per epoch",
                    )

    # -- tracer instrumentation (TPU112) ----------------------------------------
    def _device_derived_names(self) -> Set[str]:
        """Names assigned from jnp/jax-rooted calls: device arrays living in
        HOST code — perfectly legal until something reads them synchronously.
        (Deliberately excludes parameters and opaque calls: host code reading
        back its OWN dispatch outputs at the step boundary is the sanctioned
        discipline, not a hazard.)"""
        device: Set[str] = set()
        for _ in range(2):  # tiny fixpoint, like _infer_traced_locals
            for node in self._direct_statements():
                if isinstance(node, ast.Assign) and self._is_device_expr(node.value, device):
                    for tgt in node.targets:
                        for name in ast.walk(tgt):
                            if isinstance(name, ast.Name):
                                device.add(name.id)
        return device

    def _is_device_expr(self, node: ast.AST, device: Set[str]) -> bool:
        if isinstance(node, ast.Name):
            return node.id in device
        if isinstance(node, ast.Attribute):
            return False  # .shape/.dtype and host attributes alike
        if isinstance(node, ast.Subscript):
            return self._is_device_expr(node.value, device)
        if isinstance(node, ast.Call):
            func = node.func
            if self.index.is_jnp_rooted(func):
                return True
            if isinstance(func, ast.Attribute) and func.attr in ARRAY_TEST_METHODS:
                return self._is_device_expr(func.value, device)
            return False
        if isinstance(node, ast.BinOp):
            return self._is_device_expr(node.left, device) or self._is_device_expr(
                node.right, device
            )
        if isinstance(node, ast.UnaryOp):
            return self._is_device_expr(node.operand, device)
        return False

    def _device_read(self, node: ast.AST, device: Set[str]) -> Optional[str]:
        """A call that synchronously pulls a device value to host — `.item()`,
        `float()/int()/bool()`, `np.asarray`/`np.array`, `jax.device_get` — of
        a device-derived expression. Returns its spelling, or None."""
        if not isinstance(node, ast.Call):
            return None
        func = node.func
        if (
            isinstance(func, ast.Attribute)
            and func.attr == "item"
            and not node.args
            and self._is_device_expr(func.value, device)
        ):
            return ".item()"
        if (
            isinstance(func, ast.Name)
            and func.id in ("float", "int", "bool")
            and len(node.args) == 1
            and self._is_device_expr(node.args[0], device)
        ):
            return f"{func.id}()"
        chain = self.index._attr_root(func)
        if chain and node.args and self._is_device_expr(node.args[0], device):
            if chain[0] in self.index.np_aliases and chain[-1] in ("asarray", "array"):
                return f"{'.'.join(chain)}()"
            if chain[0] in self.index.jax_aliases and chain[-1] == "device_get":
                return "jax.device_get()"
        return None

    @staticmethod
    def _is_span_api_call(node: ast.AST) -> bool:
        return (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in SPAN_API_ATTRS
        )

    def check_span_hazards(self):
        """TPU112: instrumentation can never reintroduce a host sync. Flags a
        device-value read feeding a span/event annotation, a device array
        passed as an annotation outright, and synchronous device reads sitting
        inside a `with ...span(...)` block (where they would serialize the
        very dispatch the span is timing)."""
        device = self._device_derived_names()
        flagged: Set[int] = set()

        def flag(node: ast.AST, what: str, where: str):
            if id(node) in flagged:
                return
            flagged.add(id(node))
            self.emit(
                node,
                "TPU112",
                f"{what} {where} hides a blocking device sync in the "
                "instrumentation — read at the step boundary, annotate with the "
                "host scalar",
            )

        for node in self._direct_statements():
            if self._is_span_api_call(node):
                for arg in list(node.args) + [kw.value for kw in node.keywords]:
                    read = self._device_read(arg, device)
                    if read is not None:
                        flag(arg, read, "in a span annotation")
                    elif self._is_device_expr(arg, device):
                        flag(arg, "a device array", "as a span annotation")
            elif isinstance(node, ast.With) and any(
                self._is_span_api_call(item.context_expr) for item in node.items
            ):
                for stmt in node.body:
                    for sub in ast.walk(stmt):
                        read = self._device_read(sub, device)
                        if read is not None:
                            flag(sub, read, "inside a `with ...span(...)` block")


class _ModuleChecker:
    """Module-scope rules: jit-in-loop, static_argnums misuse, donated reuse,
    import-time jit, pjit annotations, closure scalar capture."""

    def __init__(self, index: _ModuleIndex, path: str):
        self.index = index
        self.path = path
        self.findings: List[Finding] = []

    def emit(self, node: ast.AST, rule_id: str, message: str):
        self.findings.append(
            Finding(self.path, node.lineno, node.col_offset, rule_id, message)
        )

    def run(self):
        self._check_jit_placement()
        self._check_pjit_annotations()
        self._check_static_argnums_and_donation()
        self._check_closure_capture()
        self._check_serving_construction()
        self._check_kernel_fallback()
        self._check_tp_replicated_operand()
        self._check_replicated_optimizer_state()
        self._check_host_hop_in_stage_handoff()
        self._check_worker_loop()
        self._check_unbounded_reconnect()
        self._check_quantization()
        self._check_dead_partition_rule()
        return self.findings

    # -- quantized serving (TPU117) ----------------------------------------------
    #: Serving attention/kernel seams whose scale arguments must be traced
    #: arrays (the pool's parallel scale pools), never Python scalars.
    _QUANT_SCALE_FUNCS = {
        "paged_decode_attention",
        "paged_verify_attention",
        "slot_cache_attention",
        "quantized_pool_write",
        "dequantize_kv",
        "quantize_kv",
    }
    _QUANT_SCALE_KWARGS = {"k_scale", "v_scale"}
    #: KV cache dtype knobs and their one legal value set
    #: (ops/quantization.KV_CACHE_DTYPES; duplicated as literals so the
    #: linter stays stdlib-only with no jax import).
    _KV_DTYPE_KWARGS = {"kv_cache_dtype", "decode_kv_cache_dtype"}
    _KV_DTYPES_OK = {"bf16", "int8", "fp8_e4m3"}

    def _check_quantization(self):
        """TPU117: quantization knobs that silently break the compiled-once
        discipline or fail late. (a) A scale passed as a Python NUMERIC
        LITERAL to a serving attention/kernel seam is baked into the
        executable at trace time — the scale pool exists precisely so scale
        changes ride as operands; one hard-coded float either pins every page
        to one scale or retraces per value. (b) A `kv_cache_dtype` /
        `decode_kv_cache_dtype` string literal off the supported set fails at
        engine construction at best — flag it where it's written, not where
        it detonates."""
        if not self.index.imports_jax:
            return
        for node in ast.walk(self.index.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self._call_name(node.func)
            for kw in node.keywords:
                if kw.arg is None:
                    continue
                value = kw.value
                if (
                    kw.arg in self._QUANT_SCALE_KWARGS
                    and name in self._QUANT_SCALE_FUNCS
                    and isinstance(value, ast.Constant)
                    and isinstance(value.value, (int, float))
                    and not isinstance(value.value, bool)
                ):
                    self.emit(
                        node,
                        "TPU117",
                        f"{name}({kw.arg}={value.value!r}) bakes a quantization "
                        "scale into the executable at trace time — pass the "
                        "pool's traced scale array (key_scale/value_scale) so "
                        "scale updates never retrace the decode program",
                    )
                if (
                    kw.arg in self._KV_DTYPE_KWARGS
                    and isinstance(value, ast.Constant)
                    and isinstance(value.value, str)
                    and value.value not in self._KV_DTYPES_OK
                ):
                    supported = ", ".join(sorted(self._KV_DTYPES_OK))
                    self.emit(
                        node,
                        "TPU117",
                        f"{kw.arg}={value.value!r} is not a supported KV cache "
                        f"dtype (expected one of: {supported}) — this fails at "
                        "engine construction; int4 packing is explicitly out of "
                        "scope (docs/limitations.md)",
                    )

    # -- subprocess worker loops (TPU116) ----------------------------------------
    #: Worker-loop entry points whose heartbeat deadline is the orphan guard.
    _WORKER_LOOP_FUNCS = {"serve_worker", "WorkerLoop"}
    #: IPC receive calls that must carry a timeout when called from a loop.
    _IPC_RECV_FUNCS = {"recv_frame", "recv_message"}

    def _check_worker_loop(self):
        """TPU116: an out-of-process serving worker is supervised through
        TIMEOUTS — the controller's step deadline detects a hung worker, the
        worker's heartbeat deadline detects a dead controller. A worker loop
        built without a heartbeat deadline leaks an orphaned process (and its
        device memory) when the controller dies; an IPC recv with no timeout
        inside a loop turns a hung peer into a hung caller, invisible to the
        health machine that exists to catch exactly that."""
        if not self.index.imports_jax:
            return
        for node in ast.walk(self.index.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self._call_name(node.func)
            kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
            if name in self._WORKER_LOOP_FUNCS:
                deadline = kwargs.get("heartbeat_deadline_s")
                if "heartbeat_deadline_s" not in kwargs or (
                    isinstance(deadline, ast.Constant) and deadline.value is None
                ):
                    self.emit(
                        node,
                        "TPU116",
                        f"{name}(...) without heartbeat_deadline_s never notices a "
                        "dead controller — the worker process (and its device "
                        "memory) leaks as an orphan; pass a deadline in seconds",
                    )
            if name in self._IPC_RECV_FUNCS and _enclosing_loop(node) is not None:
                timeout = kwargs.get("timeout_s")
                if "timeout_s" not in kwargs or (
                    isinstance(timeout, ast.Constant) and timeout.value is None
                ):
                    self.emit(
                        node,
                        "TPU116",
                        f"{name}(...) inside a loop with no timeout_s blocks forever "
                        "on a hung peer — bound every looped IPC recv so the "
                        "heartbeat machinery can observe the hang",
                    )

    # -- socket transports (TPU122) ----------------------------------------------
    #: Socket receive methods that block forever on an unarmed socket.
    _SOCKET_RECV_METHODS = {"recv", "recv_into"}

    def _check_unbounded_reconnect(self):
        """TPU122: a socket-transport protocol path is only as healthy as its
        worst-case wait. Flags, in jit-adjacent modules that import `socket`:
        (a) `socket.create_connection` dialed with no (or a None) `timeout=` —
        the connect hangs on a partitioned peer for the kernel's default,
        minutes, not the transport's budget; (b) a looped `.recv`/`.recv_into`
        with no `timeout_s=` in a module that never arms a non-None
        `settimeout` — the read blocks forever on a half-open link; (c) a
        `.reconnect(...)` driven from a loop with no `timeout_s=` — the retry
        loop has neither a per-attempt bound nor (visibly) a deadline budget,
        so a dead peer hot-loops the dial instead of escalating."""
        if not self.index.imports_jax:
            return
        imports_socket = any(
            isinstance(node, ast.Import)
            and any(alias.name == "socket" for alias in node.names)
            for node in ast.walk(self.index.tree)
        )
        if not imports_socket:
            return
        #: Any non-None settimeout anywhere in the module counts as "the
        #: module arms read deadlines" — the bound need not be adjacent to
        #: the recv (select-based framing passes the deadline separately).
        arms_settimeout = any(
            isinstance(node, ast.Call)
            and self._call_name(node.func) == "settimeout"
            and node.args
            and not (
                isinstance(node.args[0], ast.Constant)
                and node.args[0].value is None
            )
            for node in ast.walk(self.index.tree)
        )
        for node in ast.walk(self.index.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self._call_name(node.func)
            kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
            if name == "create_connection":
                timeout = kwargs.get("timeout")
                if "timeout" not in kwargs or (
                    isinstance(timeout, ast.Constant) and timeout.value is None
                ):
                    self.emit(
                        node,
                        "TPU122",
                        "socket.create_connection(...) without timeout= waits "
                        "out the kernel's connect default on a partitioned peer "
                        "— dial under the transport's own deadline budget",
                    )
            elif (
                name in self._SOCKET_RECV_METHODS
                and isinstance(node.func, ast.Attribute)
                and _enclosing_loop(node) is not None
                and "timeout_s" not in kwargs
                and not arms_settimeout
            ):
                self.emit(
                    node,
                    "TPU122",
                    f".{name}(...) inside a loop on a socket that was never "
                    "given a deadline (no settimeout, no timeout_s) blocks "
                    "forever on a half-open link — arm a read deadline so the "
                    "health machinery can observe the hang",
                )
            elif (
                name == "reconnect"
                and isinstance(node.func, ast.Attribute)
                and _enclosing_loop(node) is not None
                and "timeout_s" not in kwargs
            ):
                self.emit(
                    node,
                    "TPU122",
                    ".reconnect(...) retried in a loop with no timeout_s bound "
                    "per attempt hot-loops the dial against a dead peer — give "
                    "each attempt a deadline and budget the loop "
                    "(reconnect_deadline_s) so exhaustion escalates to the "
                    "respawn path",
                )

    # -- serving-engine construction (TPU114) -----------------------------------
    #: Serving front-end constructors whose robustness knobs this rule audits.
    _SERVING_CTORS = {"ContinuousBatcher", "Router"}

    def _check_serving_construction(self):
        """TPU114: a serving engine/router built in jit-adjacent code (the
        module really imports jax) without bounded queue backpressure — or a
        Router without a default deadline — fails open under overload:
        the host queue grows without limit and a stalled replica can hold a
        request forever instead of surfacing a terminal finish_reason."""
        if not self.index.imports_jax:
            return
        for node in ast.walk(self.index.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            name = None
            if isinstance(func, ast.Name) and func.id in self._SERVING_CTORS:
                name = func.id
            elif isinstance(func, ast.Attribute) and func.attr in self._SERVING_CTORS:
                name = func.attr
            if name is None:
                continue
            kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
            max_queue = kwargs.get("max_queue")
            if "max_queue" not in kwargs or (
                isinstance(max_queue, ast.Constant) and max_queue.value is None
            ):
                self.emit(
                    node,
                    "TPU114",
                    f"{name}(...) without a bounded max_queue grows the host wait "
                    "queue without limit under overload — pass max_queue=<bound> "
                    "so backpressure surfaces as QueueFull",
                )
            if name == "Router":
                deadline = kwargs.get("default_deadline_s")
                if "default_deadline_s" not in kwargs or (
                    isinstance(deadline, ast.Constant) and deadline.value is None
                ):
                    self.emit(
                        node,
                        "TPU114",
                        "Router(...) without default_deadline_s lets a request wait "
                        "forever on a stalled replica — give the fleet a default "
                        "per-request deadline",
                    )

    # -- pinned KV read / forced interpreter (TPU115) ----------------------------
    #: Pallas attention kernel entry points whose `interpret=` knob is a
    #: CPU-test shim, never a production setting.
    _PALLAS_KERNEL_FUNCS = {
        "paged_decode_attention",
        "paged_verify_attention",
        "flash_attention",
    }
    #: Constructors/seams that accept an attention implementation flag.
    _ATTENTION_IMPL_KWARGS = {"attention_impl", "decode_attention_impl"}

    @staticmethod
    def _call_name(func: ast.AST) -> Optional[str]:
        if isinstance(func, ast.Name):
            return func.id
        if isinstance(func, ast.Attribute):
            return func.attr
        return None

    def _check_kernel_fallback(self):
        """TPU115: the slot cache has two reads, the XLA live-page read and
        the Pallas page-walk kernel, and an engine that names neither chooses
        (`ops.attention.slot_attention_impl`: the kernel on a TPU where it
        reads the pool in place). Flags (a) a
        serving decode/verify construction whose read is pinned by a LITERAL
        attention_impl="xla", and (b) a kernel call forced into interpret
        mode with a literal interpret=True — the CPU-test shim; production
        call sites use interpret=None so the kernel compiles on TPU. Each is
        one explicit keyword that fixes the read where the call site is."""
        if not self.index.imports_jax:
            return
        for node in ast.walk(self.index.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self._call_name(node.func)
            kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
            impl = next(
                (kwargs[k] for k in self._ATTENTION_IMPL_KWARGS if k in kwargs), None
            )
            if (
                impl is not None
                and isinstance(impl, ast.Constant)
                and impl.value == "xla"
            ):
                self.emit(
                    node,
                    "TPU115",
                    'the literal attention_impl="xla" pins this decode/verify '
                    "program's KV read to the XLA live-page read — thread the "
                    "impl as a value the caller sets, or suppress where the pin "
                    "is deliberate",
                )
            if name in self._PALLAS_KERNEL_FUNCS:
                interp = kwargs.get("interpret")
                if isinstance(interp, ast.Constant) and interp.value is True:
                    self.emit(
                        node,
                        "TPU115",
                        f"{name}(interpret=True) forces the Pallas interpreter — the "
                        "CPU-test shim — onto this call site; use interpret=None so "
                        "the kernel compiles on TPU (tests belong under tests/, "
                        "which the self-lint roots exclude)",
                    )

    # -- tensor-parallel replicated placement (TPU118) ---------------------------
    @classmethod
    def _mentions_model_axis(cls, node: ast.AST) -> bool:
        return any(
            isinstance(sub, ast.Constant) and sub.value == "model"
            for sub in ast.walk(node)
        )

    def _module_spans_mesh(self) -> bool:
        """True when this module builds a tensor-parallel serving mesh: a
        `serving_tp_mesh(...)` call, or a `Mesh(...)` whose axis names include
        "model" — the context in which an unsharded placement is a silent
        full replication rather than ordinary single-device code."""
        for node in ast.walk(self.index.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self._call_name(node.func)
            if name == "serving_tp_mesh":
                return True
            if name == "Mesh" and any(
                self._mentions_model_axis(arg)
                for arg in list(node.args) + [kw.value for kw in node.keywords]
            ):
                return True
        return False

    @classmethod
    def _placement_is_devicey(cls, node: ast.AST) -> bool:
        """A placement expression that is a raw DEVICE (not a sharding):
        `jax.devices()[...]` / `jax.local_devices()[...]` subscripts or calls,
        or a name that spells a device. Unknown names get the benefit of the
        doubt — a precomputed shardings pytree is the sanctioned pattern."""
        if isinstance(node, ast.Subscript):
            return cls._placement_is_devicey(node.value)
        if isinstance(node, ast.Call):
            return cls._call_name(node.func) in {"devices", "local_devices"}
        if isinstance(node, (ast.Name, ast.Attribute)):
            label = node.id if isinstance(node, ast.Name) else node.attr
            return label.lower() in {"device", "dev"}
        return False

    def _check_tp_replicated_operand(self):
        """TPU118: in a module that spans a serving mesh, `device_put` with no
        sharding argument (or a raw device) lands the params/pool tree on ONE
        device — every sharded executable that consumes it then replicates the
        full tree to every chip, serving token-identically while spending N x
        the per-chip HBM the mesh exists to save. The sanctioned spellings
        carry a NamedSharding (pytree): `derive_tp_param_shardings` /
        `derive_tp_cache_shardings`, or `ContinuousBatcher(tp=N)` doing the
        placement internally."""
        if not self.index.imports_jax or not self._module_spans_mesh():
            return
        for node in ast.walk(self.index.tree):
            if not isinstance(node, ast.Call):
                continue
            if self._call_name(node.func) != "device_put":
                continue
            placement = None
            if len(node.args) >= 2:
                placement = node.args[1]
            else:
                for kw in node.keywords:
                    if kw.arg in ("device", "shardings", "sharding"):
                        placement = kw.value
                        break
            missing = placement is None or (
                isinstance(placement, ast.Constant) and placement.value is None
            )
            if missing or self._placement_is_devicey(placement):
                self.emit(
                    node,
                    "TPU118",
                    "device_put without a NamedSharding in a mesh-spanning serving "
                    "module places the tree on one device and lets jit replicate it "
                    "to every chip — derive shardings from the model family's rules "
                    "(derive_tp_param_shardings / derive_tp_cache_shardings) or let "
                    "ContinuousBatcher(tp=N) place it",
                )

    # -- replicated optimizer state (TPU120) --------------------------------------
    @classmethod
    def _mentions_data_axis(cls, node: ast.AST) -> bool:
        return any(
            isinstance(sub, ast.Constant) and sub.value == "data"
            for sub in ast.walk(node)
        )

    def _module_spans_data_mesh(self) -> bool:
        """True when this module builds a TRAINING mesh with a "data" axis: a
        `Mesh(...)` whose axis names include "data", a `build_mesh(...)` call
        (whose default ParallelismConfig fills "data" with every chip), or a
        `ParallelismConfig(...)` given a data degree — the context in which a
        replicated optimizer-state placement spends data_n x the moment HBM
        each chip needs for the shard of the update it actually computes."""
        for node in ast.walk(self.index.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self._call_name(node.func)
            if name == "build_mesh":
                return True
            if name == "ParallelismConfig" and any(
                kw.arg == "data" for kw in node.keywords
            ):
                return True
            if name == "Mesh" and any(
                self._mentions_data_axis(arg)
                for arg in list(node.args) + [kw.value for kw in node.keywords]
            ):
                return True
        return False

    #: Identifier fragments that label a placed tree as optimizer state.
    #: Substring match against every Name/Attribute inside the placed operand.
    _OPT_STATE_LABELS = ("opt_state", "optimizer_state", "adam_state", "moments")

    @classmethod
    def _is_opt_state_expr(cls, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name):
                label = sub.id
            elif isinstance(sub, ast.Attribute):
                label = sub.attr
            else:
                continue
            label = label.lower()
            if any(tok in label for tok in cls._OPT_STATE_LABELS):
                return True
        return False

    @classmethod
    def _placement_is_replicated(cls, node: ast.AST) -> bool:
        """A placement expression that spells REPLICATE explicitly: it contains
        PartitionSpec()/P() calls and every one of them is empty (a
        `NamedSharding(mesh, PartitionSpec())` pytree lands the full tree on
        every chip by construction). Placements without any literal spec —
        derived sharding pytrees, precomputed names — keep the benefit of the
        doubt, same as TPU118."""
        specs = [
            sub
            for sub in ast.walk(node)
            if isinstance(sub, ast.Call)
            and cls._call_name(sub.func) in {"PartitionSpec", "P"}
        ]
        return bool(specs) and all(
            not spec.args and not spec.keywords for spec in specs
        )

    def _check_replicated_optimizer_state(self):
        """TPU120: in a module that builds a data-axis training mesh,
        `device_put` of an optimizer-state tree with no sharding (or a raw
        device, or an explicitly replicated PartitionSpec()) parks fp32 Adam
        moments — 8 bytes/param — on EVERY chip, the single largest avoidable
        HBM account in data-parallel training. The sanctioned spellings derive
        the placement: `derive_opt_state_shardings` (with the planner's
        opt_rules table for ZeRO sharding along "data"), or
        Accelerator.prepare's AcceleratedOptimizer, whose init/out_shardings
        discipline places moments sharded from the first step."""
        if not self.index.imports_jax or not self._module_spans_data_mesh():
            return
        for node in ast.walk(self.index.tree):
            if not isinstance(node, ast.Call):
                continue
            if self._call_name(node.func) != "device_put":
                continue
            if not node.args or not self._is_opt_state_expr(node.args[0]):
                continue
            placement = None
            if len(node.args) >= 2:
                placement = node.args[1]
            else:
                for kw in node.keywords:
                    if kw.arg in ("device", "shardings", "sharding"):
                        placement = kw.value
                        break
            missing = placement is None or (
                isinstance(placement, ast.Constant) and placement.value is None
            )
            if (
                missing
                or self._placement_is_devicey(placement)
                or self._placement_is_replicated(placement)
            ):
                self.emit(
                    node,
                    "TPU120",
                    "optimizer state device_put without a sharded placement in a "
                    "data-axis-mesh module replicates fp32 moments (8 bytes/param) "
                    "to every chip — derive the placement with "
                    "derive_opt_state_shardings (pass the planner's opt_rules for "
                    "ZeRO sharding along \"data\"; plan_train_sharding emits it) "
                    "or prepare the optimizer through Accelerator.prepare with "
                    "sharding_rules=\"auto\"",
                )

    # -- host hop in stage handoff (TPU121) ----------------------------------------
    @classmethod
    def _mentions_pipeline_axis(cls, node: ast.AST) -> bool:
        return any(
            isinstance(sub, ast.Constant) and sub.value == "pipeline"
            for sub in ast.walk(node)
        )

    def _module_spans_pipeline_mesh(self) -> bool:
        """True when this module builds (or slices) a mesh with a "pipeline"
        axis: a `Mesh(...)`/`build_mesh(...)` naming the axis, a
        `ParallelismConfig(...)` given a pipeline degree, or a
        `slice_mesh(...)` call (the MPMD stage-submesh API itself) — the
        context in which an inter-stage carry lives on one submesh and must
        reach the next as a device-to-device transfer."""
        for node in ast.walk(self.index.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self._call_name(node.func)
            if name == "slice_mesh":
                return True
            if name == "ParallelismConfig" and any(
                kw.arg == "pipeline" for kw in node.keywords
            ):
                return True
            if name in ("Mesh", "build_mesh") and any(
                self._mentions_pipeline_axis(arg)
                for arg in list(node.args) + [kw.value for kw in node.keywords]
            ):
                return True
        return False

    #: Identifier fragments that label a value as an inter-stage handoff: the
    #: forward activation carry or the backward cotangent riding between stage
    #: submeshes. Substring match against every Name/Attribute in the operand.
    _HANDOFF_LABELS = (
        "carry", "carries", "activation", "hidden", "handoff",
        "cotangent", "microbatch", "g_out", "g_in", "stage_out", "stage_in",
    )

    @classmethod
    def _is_handoff_expr(cls, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name):
                label = sub.id
            elif isinstance(sub, ast.Attribute):
                label = sub.attr
            else:
                continue
            label = label.lower()
            if any(tok in label for tok in cls._HANDOFF_LABELS):
                return True
        return False

    def _is_numpy_coercion(self, node: ast.Call) -> bool:
        """`np.asarray(...)` / `np.array(...)` through a numpy alias — the
        silent device_get. jnp spellings stay on device and are not flagged."""
        func = node.func
        return (
            isinstance(func, ast.Attribute)
            and func.attr in ("asarray", "array")
            and isinstance(func.value, ast.Name)
            and func.value.id in (self.index.np_aliases or {"np", "numpy"})
        )

    def _check_host_hop_in_stage_handoff(self):
        """TPU121: in a module that builds a "pipeline" mesh axis, pulling an
        inter-stage activation/gradient carry through the host —
        `jax.device_get(carry)`, `np.asarray(carry)`, or
        `carry.block_until_ready()` between stages — serializes the 1F1B
        schedule on PCIe: every stage stalls behind the transfer instead of
        overlapping via async dispatch. The sanctioned handoff is
        `jax.device_put(carry, NamedSharding(next_stage_mesh, spec))`, a pure
        d2d ICI transfer that an armed TraceGuard leaves unguarded."""
        if not self.index.imports_jax or not self._module_spans_pipeline_mesh():
            return
        msg = (
            "inter-stage carry pulled through the host in a pipeline-mesh "
            "module serializes the 1F1B schedule on PCIe — hand activations "
            "and cotangents to the next stage submesh with jax.device_put("
            "carry, NamedSharding(next_stage_mesh, spec)) (a device-to-device "
            "transfer async dispatch overlaps), and keep TraceGuard armed so "
            "host round-trips fail loudly"
        )
        for node in ast.walk(self.index.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self._call_name(node.func)
            if name == "device_get" or self._is_numpy_coercion(node):
                if node.args and self._is_handoff_expr(node.args[0]):
                    self.emit(node, "TPU121", msg)
            elif name == "block_until_ready":
                if node.args:
                    operand = node.args[0]
                elif isinstance(node.func, ast.Attribute):
                    operand = node.func.value
                else:
                    continue
                if self._is_handoff_expr(operand):
                    self.emit(node, "TPU121", msg)

    # -- dead partition rules (TPU119) --------------------------------------------
    #: Pattern tokens that name STORAGE details every family table shares, not
    #: module identity — a pattern made only of these can't be judged dead.
    _RULE_GENERIC_TOKENS = {
        "kernel",
        "embedding",
        "embed",
        "bias",
        "scale",
        "layers",
        "layer",
        "params",
        "weight",
    }

    @staticmethod
    def _pattern_tokens(pattern: str) -> List[str]:
        """Identifier-ish fragments of a path regex ("(wq|wk|wv)/kernel" ->
        [wq, wk, wv]), generic storage words removed; single letters are too
        ambiguous to judge."""
        tokens = re.findall(r"[A-Za-z_][A-Za-z0-9_]+", pattern)
        return [
            t
            for t in tokens
            if len(t) >= 2 and t.lower() not in _ModuleChecker._RULE_GENERIC_TOKENS
        ]

    def _sharding_tables(self) -> List[ast.Assign]:
        tables = []
        for node in ast.walk(self.index.tree):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id.endswith("SHARDING_RULES")
                and isinstance(node.value, (ast.List, ast.Tuple))
            ):
                tables.append(node)
        return tables

    def _name_evidence(self, exclude: List[ast.AST]) -> Set[str]:
        """Every name-ish string in the module OUTSIDE the rule tables: flax
        submodule names arrive as `name="wq"` constants or f-string parts,
        attribute targets, dict keys, identifiers. This is what a live
        pattern's tokens must connect to."""
        skip = set()
        for table in exclude:
            for sub in ast.walk(table):
                skip.add(id(sub))
        evidence: Set[str] = set()
        for node in ast.walk(self.index.tree):
            if id(node) in skip:
                continue
            if isinstance(node, ast.Name):
                evidence.add(node.id)
            elif isinstance(node, ast.Attribute):
                evidence.add(node.attr)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                evidence.add(node.name)
            elif isinstance(node, ast.keyword) and node.arg:
                evidence.add(node.arg)
            elif isinstance(node, ast.Constant) and isinstance(node.value, str):
                # Identifier-like strings only (flax `name="wq"` kwargs,
                # f-string parts like "layer_"): free-text constants such as
                # docstrings would vouch for anything they happen to mention.
                if re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", node.value):
                    evidence.add(node.value)
        return {e for e in evidence if len(e) >= 2}

    def _check_dead_partition_rule(self):
        """TPU119: a sharding-rules entry whose regex names modules the model
        never defines matches NO parameter path at derivation time — the
        weight it was written to shard silently replicates, the table-side
        twin of TPU118's silent-replication placement. Also flagged: a
        literal string-axis `PartitionSpec(...)` in model code — per-leaf
        placement decisions scattered outside the one rules table the
        derivation seam (and the planner's emitted tables) can audit."""
        if not self.index.imports_jax or not self.index.imports_flax:
            return
        tables = self._sharding_tables()
        evidence = self._name_evidence(exclude=tables) if tables else set()
        for table in tables:
            for entry in table.value.elts:
                if not (isinstance(entry, ast.Tuple) and len(entry.elts) == 2):
                    continue
                pattern = entry.elts[0]
                if not (isinstance(pattern, ast.Constant) and isinstance(pattern.value, str)):
                    continue
                tokens = self._pattern_tokens(pattern.value)
                if not tokens:
                    continue  # all-generic pattern: can't judge statically
                alive = any(tok in ev for tok in tokens for ev in evidence)
                if not alive:
                    self.emit(
                        entry,
                        "TPU119",
                        f"rule pattern {pattern.value!r} names no module this "
                        "model defines — the entry matches no parameter path, "
                        "so the weight it was written to shard silently "
                        "replicates; fix the regex or delete the entry",
                    )
        for node in ast.walk(self.index.tree):
            if not isinstance(node, ast.Call):
                continue
            if self._call_name(node.func) != "PartitionSpec":
                continue
            has_axis_literal = any(
                isinstance(arg, ast.Constant) and isinstance(arg.value, str)
                for arg in node.args
            ) or any(
                isinstance(sub, ast.Constant) and isinstance(sub.value, str)
                for arg in node.args
                if isinstance(arg, (ast.Tuple, ast.List))
                for sub in arg.elts
            )
            if has_axis_literal:
                self.emit(
                    node,
                    "TPU119",
                    "literal per-leaf PartitionSpec in model code bypasses the "
                    "family's sharding-rules table — move the placement into "
                    "*_SHARDING_RULES (or let sharding_rules=\"auto\" emit it) "
                    "so the one derivation seam sees every decision",
                )

    def _check_jit_placement(self):
        for call in self.index.jit_calls:
            loop = _enclosing_loop(call)
            if loop is not None:
                self.emit(
                    call,
                    "TPU106",
                    "jax.jit inside a loop builds a fresh executable cache every "
                    "iteration — hoist it out of the loop",
                )
            elif _enclosing_function(call) is None:
                self.emit(
                    call,
                    "TPU109",
                    "jax.jit at module scope runs at import time (traces/compiles "
                    "on import); construct it lazily",
                )

    def _check_pjit_annotations(self):
        for call in self.index.jit_calls:
            if not self.index.is_pjit_func(call.func):
                continue
            kwargs = {kw.arg for kw in call.keywords if kw.arg}
            if not kwargs & {"in_shardings", "out_shardings", "in_axis_resources", "out_axis_resources"}:
                self.emit(
                    call,
                    "TPU110",
                    "pjit without in_shardings/out_shardings replicates every "
                    "operand — annotate the partitioning explicitly",
                )

    # -- static_argnums over loop-varying values + donated-buffer reuse ---------
    @staticmethod
    def _literal_argnums(call: ast.Call, kwarg: str) -> Optional[Tuple[int, ...]]:
        for kw in call.keywords:
            if kw.arg != kwarg:
                continue
            try:
                value = ast.literal_eval(kw.value)
            except (ValueError, SyntaxError):
                return None
            if isinstance(value, int):
                return (value,)
            if isinstance(value, (tuple, list)) and all(isinstance(v, int) for v in value):
                return tuple(value)
        return None

    @staticmethod
    def _owned_by(node: ast.AST, scope: ast.AST) -> bool:
        """Does `node` belong to `scope`'s own frame (not a nested function's)?"""
        owner = _enclosing_function(node)
        return owner is scope or (owner is None and isinstance(scope, ast.Module))

    def _jitted_bindings(self, scope: ast.AST) -> Dict[str, ast.Call]:
        """`f = jax.jit(g, ...)` assignments directly inside `scope`'s frame."""
        out: Dict[str, ast.Call] = {}
        for node in ast.walk(scope):
            if (
                isinstance(node, ast.Assign)
                and self._owned_by(node, scope)
                and isinstance(node.value, ast.Call)
                and node.value in self.index.jit_calls
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
            ):
                out[node.targets[0].id] = node.value
        return out

    def _scopes(self):
        yield self.index.tree
        for node in ast.walk(self.index.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield node

    def _check_static_argnums_and_donation(self):
        for scope in self._scopes():
            bindings = self._jitted_bindings(scope)
            if not bindings:
                continue
            static = {
                name: nums
                for name, call in bindings.items()
                if (nums := self._literal_argnums(call, "static_argnums")) is not None
            }
            donated = {
                name: nums
                for name, call in bindings.items()
                if (nums := self._literal_argnums(call, "donate_argnums")) is not None
            }
            for node in ast.walk(scope):
                if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)):
                    continue
                if not self._owned_by(node, scope):
                    continue
                name = node.func.id
                if name in static:
                    loop = _enclosing_loop(node)
                    if loop is not None:
                        loop_vars = {
                            n.id
                            for n in ast.walk(loop.target)
                            if isinstance(n, ast.Name)
                        } if isinstance(loop, ast.For) else set()
                        for pos in static[name]:
                            if pos < len(node.args) and any(
                                isinstance(n, ast.Name) and n.id in loop_vars
                                for n in ast.walk(node.args[pos])
                            ):
                                self.emit(
                                    node,
                                    "TPU107",
                                    f"static_argnums position {pos} of `{name}` is fed "
                                    "the loop variable — every iteration recompiles",
                                )
                if name in donated:
                    self._check_donated_reuse(scope, node, donated[name])

    def _check_donated_reuse(self, scope: ast.AST, call: ast.Call, positions: Sequence[int]):
        donated_names = {
            call.args[p].id
            for p in positions
            if p < len(call.args) and isinstance(call.args[p], ast.Name)
        }
        if not donated_names:
            return
        call_line = call.lineno
        rebound: Set[str] = set()
        in_call = {id(n) for n in ast.walk(call)}  # the donation site itself
        for node in sorted(
            (
                n
                for n in ast.walk(scope)
                if hasattr(n, "lineno") and n.lineno >= call_line and id(n) not in in_call
                # Same frame only: a nested function's own `params` is a fresh
                # binding, not the donated buffer (and must neither be flagged
                # nor mask a real reuse as a rebind).
                and self._owned_by(n, scope)
            ),
            key=lambda n: (n.lineno, n.col_offset),
        ):
            if isinstance(node, ast.Name) and node.id in donated_names:
                parent = getattr(node, "_tpu_parent", None)
                if (
                    isinstance(parent, ast.Attribute)
                    and parent.value is node
                    and parent.attr in STATIC_ATTRS
                ):
                    continue  # .shape/.dtype metadata stays valid after donation
                if isinstance(node.ctx, ast.Store):
                    rebound.add(node.id)
                elif isinstance(node.ctx, ast.Load) and node.id not in rebound:
                    self.emit(
                        node,
                        "TPU108",
                        f"`{node.id}` was donated to the jitted call on line "
                        f"{call_line}; its buffer is invalidated — rebind it to "
                        "the call's output",
                    )
                    rebound.add(node.id)  # one finding per name is enough

    # -- closure scalar capture -------------------------------------------------
    def _check_closure_capture(self):
        for root in self.index.jit_roots:
            enclosing = _enclosing_function(root)
            if enclosing is None:
                continue
            scalar_locals: Set[str] = set()
            for node in ast.walk(enclosing):
                if _enclosing_function(node) is not enclosing:
                    continue
                if isinstance(node, ast.Assign) and isinstance(node.value, ast.Constant):
                    if isinstance(node.value.value, (int, float)) and not isinstance(
                        node.value.value, bool
                    ):
                        for tgt in node.targets:
                            if isinstance(tgt, ast.Name):
                                scalar_locals.add(tgt.id)
                elif (
                    isinstance(node, ast.AugAssign)
                    and isinstance(node.target, ast.Name)
                    and isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, (int, float))
                ):
                    # `i += 1`-style counters are Python scalars; `acc += x`
                    # may well be a traced array accumulator — don't flag it.
                    scalar_locals.add(node.target.id)
            if not scalar_locals:
                continue
            local = _param_names(root)
            for node in ast.walk(root):
                if (
                    isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in scalar_locals
                    and node.id not in local
                ):
                    self.emit(
                        node,
                        "TPU105",
                        f"`{node.id}` is a Python scalar captured from the enclosing "
                        "scope — it is baked in at trace time; pass it as an operand",
                    )
                    scalar_locals.discard(node.id)  # once per name per root


def analyze_source(source: str, path: str = "<string>") -> List[Finding]:
    """Lint one Python source. Returns findings with suppressions applied.
    Unparseable sources return no findings (a syntax error is the Python
    toolchain's job, not this linter's) — they still count as scanned."""
    try:
        tree = ast.parse(source)
    except SyntaxError:
        return []  # unparseable files are skipped (not this linter's concern)

    index = _ModuleIndex(tree)
    findings: List[Finding] = []

    seen: Set[int] = set()
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if id(fn) in seen:
            continue
        seen.add(id(fn))
        checker = _FunctionChecker(index, fn, path)
        if fn in index.reachable:
            checker.check_traced_rules()
        else:
            checker.check_host_loop_syncs()
            checker.check_span_hazards()
        findings.extend(checker.findings)

    findings.extend(_ModuleChecker(index, path).run())

    per_line, file_wide = _parse_suppressions(source)
    kept: List[Finding] = []
    for f in findings:
        if "all" in file_wide or f.rule_id in file_wide:
            continue
        line_rules = per_line.get(f.line, set())
        if "all" in line_rules or f.rule_id in line_rules:
            continue
        kept.append(f)
    return kept
