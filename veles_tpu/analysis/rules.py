"""The per-file veleslint rules.

Each rule is one class with a ``name``, a one-line ``doc`` (the
catalog in docs/guide.md section 10 is written from these), and
``check(ctx) -> [Finding]`` over one :class:`ModuleContext`.  Rules
are syntactic and deliberately conservative: a name that cannot be
resolved statically is SKIPPED, not flagged — every finding should be
actionable, and the inline waiver / baseline machinery exists for the
rare justified exception, not for noise.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional

from veles_tpu.analysis.engine import Finding, ModuleContext

#: attribute calls that force a device->host sync (or are host-only)
#: inside traced code
_HOST_SYNC_METHODS = frozenset((
    "item", "block_until_ready", "numpy", "tolist"))
#: numpy-module functions that materialize a tracer on the host
_NUMPY_MATERIALIZERS = frozenset((
    "asarray", "array", "save", "savez", "frombuffer"))
#: mutating container methods for the lock-discipline rule
_MUTATORS = frozenset((
    "append", "appendleft", "add", "clear", "pop", "popleft",
    "popitem", "update", "setdefault", "remove", "discard", "extend",
    "insert", "sort", "reverse"))
#: telemetry entry points whose first argument is a registry name
_TELEMETRY_FUNCS = frozenset((
    "event", "counter", "gauge", "histogram", "span",
    "recent_events"))
#: the exit codes owned by the launcher/supervisor contract
_CONTRACT_CODES = (13, 14)


def _in_scope(path: str, prefixes: List[str]) -> bool:
    return any(path == p or path.startswith(p.rstrip("/") + "/")
               for p in prefixes)


def _call_name(func: ast.expr) -> Optional[str]:
    """Trailing identifier of a call target: ``jit`` for both
    ``jit(...)`` and ``jax.jit(...)``."""
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


class AtomicWriteRule:
    """Persistent-state files must be written tempfile-then-
    ``os.replace``; a bare ``open(path, "w")`` tears under crashes and
    concurrent writers (the PR-6 compile-cache corruption family)."""

    name = "atomic-write"
    doc = ("bare `open(..., \"w\")` in package code — route through "
           "the tempfile+os.replace helpers "
           "(snapshotter.atomic_write / write_json_atomic)")

    def check(self, ctx: ModuleContext) -> List[Finding]:
        if not _in_scope(ctx.path, ctx.config.atomic_write_scope):
            return []
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "open"):
                continue
            mode: Optional[ast.expr] = None
            if len(node.args) >= 2:
                mode = node.args[1]
            for kw in node.keywords:
                if kw.arg == "mode":
                    mode = kw.value
            if not (isinstance(mode, ast.Constant)
                    and isinstance(mode.value, str)
                    and "w" in mode.value):
                continue
            out.append(Finding(
                self.name, ctx.path, node.lineno, node.col_offset,
                f"open-{mode.value}",
                f"bare open(..., {mode.value!r}) is a torn-write "
                "window: write via snapshotter.atomic_write / "
                "write_json_atomic (tempfile + os.replace)"))
        return out


class EnvRegistryRule:
    """Every ``os.environ`` read/write of a ``VELES_*`` name must be
    declared in veles_tpu/knobs.py (which also generates the guide's
    knob table); an undeclared knob is read forever and set never."""

    name = "env-registry"
    doc = ("`VELES_*` environment access whose name is not declared "
           "in veles_tpu/knobs.py (also verifies the generated "
           "docs/guide.md knob table is in sync)")

    def _env_key_nodes(self, ctx: ModuleContext
                       ) -> Iterator[ast.expr]:
        for node in ast.walk(ctx.tree):
            # os.environ.get/pop/setdefault(KEY, ...), os.getenv(KEY)
            if isinstance(node, ast.Call):
                f = node.func
                if isinstance(f, ast.Attribute) and node.args:
                    base = f.value
                    if (isinstance(base, ast.Attribute)
                            and base.attr == "environ"
                            and f.attr in ("get", "pop",
                                           "setdefault")):
                        yield node.args[0]
                    elif (isinstance(base, ast.Name)
                          and base.id == "os"
                          and f.attr == "getenv"):
                        yield node.args[0]
            # os.environ[KEY] in any expression context
            elif isinstance(node, ast.Subscript):
                v = node.value
                if isinstance(v, ast.Attribute) and \
                        v.attr == "environ":
                    yield node.slice

    def check(self, ctx: ModuleContext) -> List[Finding]:
        if ctx.path in ctx.config.registry_exempt:
            return []
        from veles_tpu import knobs
        declared = knobs.names()
        out: List[Finding] = []
        for key_node in self._env_key_nodes(ctx):
            name = ctx.resolve_str(key_node)
            if name is None or not name.startswith("VELES_"):
                continue
            if name in declared:
                continue
            out.append(Finding(
                self.name, ctx.path, key_node.lineno,
                key_node.col_offset, name,
                f"undeclared env knob {name!r}: declare it in "
                "veles_tpu/knobs.py (name, default, parser, doc) and "
                "regenerate the guide table"))
        return out


class EventRegistryRule:
    """Telemetry names (journal events, counters, gauges, histograms,
    spans) must be the declared constants from veles_tpu/events.py,
    never ad-hoc string literals — an emitter/asserter typo otherwise
    only surfaces when a chaos drill reads an event that never
    fired."""

    name = "event-registry"
    doc = ("string literal passed to telemetry.event / counter / "
           "gauge / histogram / span / recent_events — use the "
           "declared constant from veles_tpu/events.py")

    def check(self, ctx: ModuleContext) -> List[Finding]:
        if ctx.path in ctx.config.registry_exempt or \
                ctx.path == "veles_tpu/telemetry.py":
            # telemetry.py forwards caller-supplied names; the
            # registries declare literals by design
            return []
        from veles_tpu import events
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            f = node.func
            is_telemetry_call = (
                isinstance(f, ast.Attribute)
                and f.attr in _TELEMETRY_FUNCS
                and isinstance(f.value, ast.Name)
                and f.value.id == "telemetry")
            if not is_telemetry_call:
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                continue   # constants/variables/f-strings pass
            literal = arg.value
            if events.known(literal):
                hint = ("declared in veles_tpu/events.py — import "
                        "and use its constant instead of the literal")
            else:
                hint = ("NOT declared in veles_tpu/events.py — a "
                        "typo, or a new name missing its registry "
                        "entry")
            out.append(Finding(
                self.name, ctx.path, arg.lineno, arg.col_offset,
                literal,
                f"ad-hoc telemetry name literal {literal!r}: {hint}"))
        return out


class TracerHygieneRule:
    """Functions traced by jit/vmap/pmap/shard_map must not host-sync
    (``.item()``, ``np.asarray``, ``print``, ``block_until_ready``,
    float/int casts of traced args) or branch in Python on traced
    values — each is a silent round-trip or a trace-time error that
    only fires on the chip."""

    name = "tracer-hygiene"
    doc = ("host sync or Python control flow on traced values inside "
           "a jit/vmap/pmap/shard_map-traced function")

    _TRACERS = frozenset(("jit", "vmap", "pmap", "shard_map"))

    def _traced_functions(self, ctx: ModuleContext
                          ) -> List[ast.FunctionDef]:
        traced_names = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and \
                    _call_name(node.func) in self._TRACERS:
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        traced_names.add(arg.id)
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if node.name in traced_names:
                out.append(node)
                continue
            for dec in node.decorator_list:
                d = dec.func if isinstance(dec, ast.Call) else dec
                name = _call_name(d)
                if name in self._TRACERS or (
                        name == "partial"
                        and isinstance(dec, ast.Call) and dec.args
                        and _call_name(dec.args[0]) in self._TRACERS):
                    out.append(node)
                    break
        return out

    def _flag(self, ctx: ModuleContext, node: ast.AST, fn_name: str,
              what: str, out: List[Finding]) -> None:
        out.append(Finding(
            self.name, ctx.path, node.lineno,
            getattr(node, "col_offset", 0),
            f"{fn_name}:{what}",
            f"{what} inside traced function {fn_name!r}: forces a "
            "host sync (or a trace-time error on the chip) — keep "
            "traced code device-pure"))

    def check(self, ctx: ModuleContext) -> List[Finding]:
        out: List[Finding] = []
        for fn in self._traced_functions(ctx):
            params = {a.arg for a in fn.args.args
                      + fn.args.posonlyargs + fn.args.kwonlyargs}
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    f = node.func
                    if isinstance(f, ast.Attribute) and \
                            f.attr in _HOST_SYNC_METHODS:
                        self._flag(ctx, node, fn.name,
                                   f".{f.attr}()", out)
                    elif isinstance(f, ast.Attribute) and \
                            isinstance(f.value, ast.Name) and \
                            f.value.id in ("np", "numpy") and \
                            f.attr in _NUMPY_MATERIALIZERS:
                        self._flag(ctx, node, fn.name,
                                   f"np.{f.attr}()", out)
                    elif isinstance(f, ast.Attribute) and \
                            f.attr == "device_get":
                        self._flag(ctx, node, fn.name,
                                   "device_get()", out)
                    elif isinstance(f, ast.Name) and \
                            f.id == "print":
                        self._flag(ctx, node, fn.name, "print()",
                                   out)
                    elif isinstance(f, ast.Name) and \
                            f.id in ("float", "int", "bool") and \
                            len(node.args) == 1 and \
                            isinstance(node.args[0], ast.Name) and \
                            node.args[0].id in params:
                        self._flag(
                            ctx, node, fn.name,
                            f"{f.id}({node.args[0].id})", out)
                elif isinstance(node, (ast.If, ast.While)):
                    for sub in ast.walk(node.test):
                        if isinstance(sub, ast.Call) and \
                                isinstance(sub.func, ast.Attribute) \
                                and isinstance(sub.func.value,
                                               ast.Name) \
                                and sub.func.value.id == "jnp":
                            self._flag(
                                ctx, node, fn.name,
                                "python branch on jnp value", out)
                            break
        return out


class ExitCodeLiteralsRule:
    """The 13/14 exit-code contract flows from the named constants
    (Launcher.MULTIHOST_ABORT_EXIT / PREEMPT_EXIT, supervisor.EXIT_*);
    a bare 13 or 14 in an exit call or comparison silently forks the
    contract."""

    name = "exit-code-literals"
    doc = ("literal 13/14 in exit calls or comparisons inside the "
           "exit-contract modules — use the launcher/supervisor "
           "constants")

    _EXIT_CALLS = frozenset(("_exit", "exit", "SystemExit"))

    def check(self, ctx: ModuleContext) -> List[Finding]:
        if ctx.path not in ctx.config.exit_code_modules:
            return []
        out: List[Finding] = []

        def flag(node: ast.AST, value: int, where: str) -> None:
            out.append(Finding(
                self.name, ctx.path, node.lineno,
                getattr(node, "col_offset", 0),
                f"{where}-{value}",
                f"exit-code literal {value} in {where}: use the "
                "named constant (Launcher.PREEMPT_EXIT / "
                "MULTIHOST_ABORT_EXIT, supervisor.EXIT_PREEMPTED / "
                "EXIT_MULTIHOST_ABORT)"))

        def contract_consts(node: ast.expr) -> Iterator[ast.Constant]:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Constant) and \
                        sub.value in _CONTRACT_CODES and \
                        isinstance(sub.value, int):
                    yield sub

        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and \
                    _call_name(node.func) in self._EXIT_CALLS:
                for arg in node.args:
                    for c in contract_consts(arg):
                        flag(c, c.value, "exit-call")
            elif isinstance(node, ast.Compare):
                for side in [node.left] + list(node.comparators):
                    for c in contract_consts(side):
                        flag(c, c.value, "comparison")
        return out


class LockDisciplineRule:
    """Module-level mutable containers in the thread-spawning modules
    must be mutated under a held lock (``with <...lock...>:``) —
    anything else is a data race a drill can only catch by luck."""

    name = "lock-discipline"
    doc = ("module-level mutable container mutated outside a held "
           "lock in a thread-spawning module")

    _CTORS = frozenset(("dict", "list", "set", "deque",
                        "defaultdict", "OrderedDict"))

    def _module_mutables(self, ctx: ModuleContext) -> set:
        names = set()
        for stmt in ctx.tree.body:
            targets: List[ast.expr] = []
            value: Optional[ast.expr] = None
            if isinstance(stmt, ast.Assign):
                targets, value = stmt.targets, stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value:
                targets, value = [stmt.target], stmt.value
            if value is None:
                continue
            mutable = isinstance(value, (ast.Dict, ast.List,
                                         ast.Set)) or (
                isinstance(value, ast.Call)
                and _call_name(value.func) in self._CTORS)
            if not mutable:
                continue
            for t in targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
        return names

    def check(self, ctx: ModuleContext) -> List[Finding]:
        if ctx.path not in ctx.config.lock_modules:
            return []
        mutables = self._module_mutables(ctx)
        if not mutables:
            return []
        out: List[Finding] = []

        def flag(node: ast.AST, name: str, how: str) -> None:
            out.append(Finding(
                self.name, ctx.path, node.lineno,
                getattr(node, "col_offset", 0),
                f"{name}.{how}",
                f"module-level mutable {name!r} mutated ({how}) "
                "outside a held lock in a thread-spawning module — "
                "wrap in `with <lock>:` (or waive with a written "
                "reason if provably single-threaded/GIL-atomic)"))

        for node in ast.walk(ctx.tree):
            # import-time statements run before any thread exists
            if not ctx.in_function(node):
                continue
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    isinstance(node.func.value, ast.Name) and \
                    node.func.value.id in mutables and \
                    node.func.attr in _MUTATORS:
                if not ctx.under_lock(node):
                    flag(node, node.func.value.id, node.func.attr)
            elif isinstance(node, ast.Subscript) and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id in mutables and \
                    isinstance(node.ctx, (ast.Store, ast.Del)):
                if not ctx.under_lock(node):
                    flag(node, node.value.id, "setitem")
            elif isinstance(node, ast.AugAssign) and \
                    isinstance(node.target, ast.Subscript) and \
                    isinstance(node.target.value, ast.Name) and \
                    node.target.value.id in mutables:
                if not ctx.under_lock(node):
                    flag(node, node.target.value.id, "augassign")
        return out


class EngineResidencySeamRule:
    """Data residency and buffer donation are the execution core's
    job: a stray ``jax.device_put`` bypasses the HBM arbiter's ledger
    and a stray ``donate_argnums`` bypasses the core's donation
    policy, so both may only appear inside the seam modules
    (engine/core.py, serve/residency.py, parallel/mesh.py) —
    everything else routes through ``ExecutionCore.put`` /
    ``donating_jit``."""

    name = "engine-residency-seam"
    doc = ("`jax.device_put` call or `donate_argnums=` keyword "
           "outside the residency seam (engine/core.py, "
           "serve/residency.py, parallel/mesh.py) — route through "
           "engine.core.ExecutionCore.put / donating_jit")

    def check(self, ctx: ModuleContext) -> List[Finding]:
        if _in_scope(ctx.path, ctx.config.engine_seam_modules):
            return []
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if _call_name(node.func) == "device_put":
                out.append(Finding(
                    self.name, ctx.path, node.lineno,
                    node.col_offset, "device_put",
                    "jax.device_put outside the residency seam "
                    "bypasses the HBM arbiter ledger — place arrays "
                    "through engine.core.ExecutionCore.put (or "
                    "engine.core.put for one-off host transfers)"))
            for kw in node.keywords:
                if kw.arg == "donate_argnums":
                    out.append(Finding(
                        self.name, ctx.path, kw.value.lineno,
                        kw.value.col_offset, "donate_argnums",
                        "donate_argnums outside the residency seam "
                        "bypasses the core's donation policy — "
                        "compile through engine.core.donating_jit "
                        "(or ExecutionCore.jit(fn, donate=...))"))
        return out


class TracedEnvReadRule:
    """Code that builds traced programs chooses a path from what it
    can observe — platform, shapes, free bytes — never from a user-set
    environment variable: each such switch doubles the programs a
    change to the step has to keep correct, and no cell of the
    benchmark runs its other side (PR 30 deleted four).  Placement
    settings are declared knobs read through ``knobs.get``."""

    name = "traced-env-read"
    doc = ("raw `os.environ` / `os.getenv` access in `veles_tpu/ops/` "
           "or `veles_tpu/engine/` — the code that builds traced "
           "programs selects a path from what it observes (platform, "
           "shapes), not from an environment variable")

    _SCOPE = ["veles_tpu/ops", "veles_tpu/engine"]

    def check(self, ctx: ModuleContext) -> List[Finding]:
        if not _in_scope(ctx.path, self._SCOPE):
            return []
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Attribute) and \
                    node.attr in ("environ", "getenv") and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id == "os":
                out.append(Finding(
                    self.name, ctx.path, node.lineno,
                    node.col_offset, f"os.{node.attr}",
                    f"os.{node.attr} in code that builds traced "
                    "programs: select the path from what the code "
                    "can observe (platform, shapes, free bytes); a "
                    "placement setting is a declared knob read "
                    "through knobs.get"))
        return out


from veles_tpu.analysis.concurrency import (  # noqa: E402 — the
    # concurrency module needs Finding/ModuleContext from engine, so
    # it cannot be imported before them
    PROJECT_RULES,
    ThreadLifecycleRule,
    TraceWireKeyRule,
    WireProtocolRule,
)

RULES = [
    AtomicWriteRule(),
    EnvRegistryRule(),
    EventRegistryRule(),
    TracerHygieneRule(),
    ExitCodeLiteralsRule(),
    LockDisciplineRule(),
    EngineResidencySeamRule(),
    TracedEnvReadRule(),
    ThreadLifecycleRule(),
    WireProtocolRule(),
    TraceWireKeyRule(),
]


def rule_names() -> List[str]:
    """Every rule, per-file and whole-program alike (the CLI's
    --rule choices and the guide's catalog order)."""
    return [r.name for r in RULES] + [r.name for r in PROJECT_RULES]
