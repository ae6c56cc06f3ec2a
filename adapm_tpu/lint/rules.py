"""The adapm-lint rule set (ISSUE 11): one rule per concurrency/plane
discipline, each grounded in a prose contract that used to be enforced
only by randomized storm tests. docs/INVARIANTS.md is the user-facing
catalog — rule ID, rationale, what fires, how to suppress.

| id     | discipline                                                   |
|--------|--------------------------------------------------------------|
| APM001 | gate-coverage: sharded device programs dispatch under the    |
|        | process-wide dispatch_gate() (docs/EXECUTOR.md)              |
| APM002 | no-blocking-under-lock: never .result()/wait/join/sleep/     |
|        | block inside a `with *._lock:` section (lock-narrowing rule) |
| APM003 | skip-wrapper: optional planes are used behind an `is None`   |
|        | guard and register zero metric names at import time (r7)     |
| APM004 | raw-thread ban: threading.Thread only in the executor/       |
|        | launcher/DCN/reporter allowlist (r11 subsumed the rest)      |
| APM005 | donation-after-dispatch: a local passed at a donate_argnums  |
|        | position is dead after the dispatching call                  |
| APM006 | revalidate-before-enqueue: topology read outside the lock +  |
|        | enqueue under it requires an under-lock re-read              |
| APM007 | metric-catalog drift: registered metric names <-> the        |
|        | docs/OBSERVABILITY.md catalog + snapshot schema sections     |
| APM008 | device-API confinement: jax.jit / device_put / pmap /        |
|        | shard_map only under adapm_tpu/device/ (the DevicePort)      |

Rules are LEXICAL: they reason about the AST as written (a `with
dispatch_gate():` block, an `is None` test), not about runtime values.
That is the point — the disciplines were designed to be auditable from
the source ("enqueue under the server lock, dispatch never"), and a
lexical checker runs in milliseconds with zero device stack. The cost
is the occasional intentional exception; those carry a justified
`# apm-lint: disable=` suppression (analyzer.py), never a weakened
rule.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Tuple

from .analyzer import (Finding, ModuleInfo, ProjectContext, Rule,
                       terminal_name)

# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def _iter_functions(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _with_item_is(item: ast.withitem, names) -> bool:
    """True when a with-item's context expression terminates in one of
    `names` — either the object itself (`with _GATE:`) or a zero-ish
    call (`with dispatch_gate():`)."""
    ctx = item.context_expr
    if isinstance(ctx, ast.Call):
        return terminal_name(ctx.func) in names
    return terminal_name(ctx) in names


def _enclosing_with(mod: ModuleInfo, node: ast.AST, names) -> bool:
    for anc in mod.ancestors(node):
        if isinstance(anc, ast.With) and \
                any(_with_item_is(i, names) for i in anc.items):
            return True
    return False


def _callee_program_name(mod: ModuleInfo,
                         call: ast.Call) -> Optional[str]:
    """Name of the called module-level program, for calls that can
    target one: a bare name (`_gather(...)`, `_relocate(...)`) or an
    imported-module attribute (`dequant._write_main_rows_fp16(...)`).
    Method calls (`self._sync_replicas(...)`) return None — Server
    methods legitimately share names with the store programs they
    orchestrate."""
    fn = call.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name) \
            and fn.value.id in mod.imported_names:
        return fn.attr
    return None


def _mentions_handle(node: ast.AST, handle: str) -> bool:
    """True when `node`'s subtree mentions optional-subsystem `handle`:
    an attribute access `x.<handle>`, a bare name `<handle>`, or a
    `getattr(x, "<handle>", ...)` probe."""
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr == handle:
            return True
        if isinstance(n, ast.Name) and n.id == handle:
            return True
        if isinstance(n, ast.Call) and terminal_name(n.func) == "getattr":
            if len(n.args) >= 2 and isinstance(n.args[1], ast.Constant) \
                    and n.args[1].value == handle:
                return True
    return False


def _has_none_compare(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Compare) and \
                any(isinstance(op, (ast.Is, ast.IsNot)) for op in n.ops) \
                and any(isinstance(c, ast.Constant) and c.value is None
                        for c in n.comparators):
            return True
    return False


def _terminates(stmts: List[ast.stmt]) -> bool:
    """A statement list that unconditionally leaves the enclosing block
    (the early-return guard shape: `if x is None: return`)."""
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Continue, ast.Break))


# ---------------------------------------------------------------------------
# APM001 — gate coverage
# ---------------------------------------------------------------------------

# The sharded-program site manifest: module-level jitted programs whose
# dispatch enqueues onto every per-device execution queue. Each is
# defined next to its callers and dispatched by NAME (store/coldpath/
# dequant/promote programs) — fused step fns
# dispatch through runner-held variables and are covered by their own
# `with srv.exec.track("main"), _GATE:` blocks, which this rule cannot
# (and need not) see through. Grow this list when a new program class
# appears; the matching docs section is docs/INVARIANTS.md#apm001.
SHARDED_DISPATCH_SITES = frozenset({
    # core/store.py
    "_gather", "_scatter_add", "_set_rows", "_replica_create",
    "_sync_replicas", "_sync_replicas_compressed",
    "_sync_replicas_thresholded", "_read_rows_at", "_install_rows",
    "_refresh_after_sync", "_relocate",
    # promotion uploads (device/jaxport.py; formerly tier/promote.py +
    # ops/dequant.py)
    "_write_main_rows", "_write_main_rows_fp16", "_write_main_rows_int8",
    # tier/coldpath.py (cold-path programs)
    "_gather_cold", "_gather_cold_fp16", "_gather_cold_int8",
    "_clear_rows", "_install_cache_rows", "_install_cache_rows_resid",
    # fused embedding-bag reads (device/jaxport.py, ISSUE 16)
    "_gather_pool", "_gather_pool_cold", "_gather_pool_cold_fp16",
    "_gather_pool_cold_int8",
})

# context managers that ARE the gate at a dispatch site
_GATE_NAMES = frozenset({"dispatch_gate", "_GATE", "_DISPATCH_GATE"})


class GateCoverageRule(Rule):
    """APM001: every call to a known sharded-dispatch program must sit
    lexically under `with dispatch_gate():` / `with _GATE:` (possibly
    combined: `with srv.exec.track("main"), _GATE:`). Two lock domains
    dispatching sharded programs concurrently land them on the
    per-device execution queues in different orders — the r10 XLA-CPU
    collective-rendezvous deadlock the gate retired by construction
    (docs/EXECUTOR.md)."""

    id = "APM001"
    name = "gate-coverage"
    doc = "sharded program dispatched outside the dispatch gate"

    def check_module(self, mod: ModuleInfo,
                     ctx: ProjectContext) -> List[Finding]:
        out = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _callee_program_name(mod, node)
            if name not in SHARDED_DISPATCH_SITES:
                continue
            if _enclosing_with(mod, node, _GATE_NAMES):
                continue
            out.append(self.finding(
                mod, node.lineno,
                f"[gate-coverage] sharded program {name}() dispatched "
                f"outside `with dispatch_gate():` — two ungated "
                f"dispatch domains can deadlock the per-device "
                f"collective rendezvous (docs/EXECUTOR.md)"))
        return out


# ---------------------------------------------------------------------------
# APM002 — no blocking under the server lock
# ---------------------------------------------------------------------------

# attribute names that identify the guarded mutex in a with-item
_LOCK_ATTRS = frozenset({"_lock"})

# terminal call names that park the calling thread. `wait` on a
# condition variable is exempt below (a condvar RELEASES its lock while
# waiting — that is its contract, not a violation).
_BLOCKING_CALLS = frozenset({
    "result", "wait", "block_until_ready", "join", "sleep", "drain",
    "drain_streams", "block",
})


class NoBlockingUnderLockRule(Rule):
    """APM002: inside a `with <x>._lock:` section, never call
    `.result()`, `.wait()`, `.join()`, `block_until_ready`, `sleep`,
    executor `drain`s, or `.block()`. The lock-narrowing rule
    (docs/EXECUTOR.md): the server lock brackets snapshot +
    revalidation + program ENQUEUE only — a lock held across a device
    wait serializes every producer behind the device, and at
    NestPipe-style scale that is a fleet-wide stall. Condvar waits on
    the lock itself are exempt (they release it)."""

    id = "APM002"
    name = "no-blocking-under-lock"
    doc = "blocking call inside a `with *._lock:` section"

    def check_module(self, mod: ModuleInfo,
                     ctx: ProjectContext) -> List[Finding]:
        out = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = terminal_name(node.func)
            if name not in _BLOCKING_CALLS:
                continue
            if not _enclosing_with(mod, node, _LOCK_ATTRS):
                continue
            # condvar exemption: `self._cond.wait()` under the condvar's
            # own lock is the parking idiom, not a held-lock wait
            recv = node.func.value \
                if isinstance(node.func, ast.Attribute) else None
            rname = terminal_name(recv) if recv is not None else ""
            if name == "wait" and rname and "cond" in rname.lower():
                continue
            out.append(self.finding(
                mod, node.lineno,
                f"[no-blocking-under-lock] {name}() inside a "
                f"`with *._lock:` section — the lock brackets enqueue "
                f"only, never a wait (lock-narrowing rule, "
                f"docs/EXECUTOR.md)"))
        return out


# ---------------------------------------------------------------------------
# APM003 — skip-wrapper discipline for optional planes
# ---------------------------------------------------------------------------

# Optional-subsystem handles (None when the plane is off). The r7
# discipline: feature off = ONE `is None` check on the hot path and
# ZERO registry names — so every call THROUGH one of these attributes
# must sit behind an `is (not) None` guard of that handle (enclosing
# `if`, or a preceding early-return), or bind it to a local first
# (`f = self.fault; if f is not None: f.fire(...)` — the canonical
# form, which this rule never flags).
OPTIONAL_HANDLES = frozenset({
    "fault", "flight", "tracer", "slo", "tier", "prefetch", "recorder",
    "wtrace", "decisions", "policy", "stream",
})

# metric-registry factory methods (import-time registration ban)
_REGISTRY_FACTORIES = frozenset({"counter", "gauge", "histogram"})


class SkipWrapperRule(Rule):
    """APM003: (a) no metric registration at import time — a module
    that registers `flight.*`/`fault.*` names on import makes the
    "off = zero registry names" contract unfalsifiable (the
    metrics_overhead_check pins it at runtime; this pins it in the
    source); (b) a call through an optional-plane handle
    (`srv.fault.fire(...)`) must be guarded by an `is None` check of
    that handle — unguarded uses crash the hot path the moment the
    plane is off."""

    id = "APM003"
    name = "skip-wrapper"
    doc = "optional-plane use without an `is None` guard, or " \
          "import-time metric registration"

    # -- (a) import-time registration ---------------------------------------

    def _import_time_registrations(self, mod: ModuleInfo) -> List[Finding]:
        out = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            is_factory = (isinstance(node.func, ast.Attribute)
                          and node.func.attr in _REGISTRY_FACTORIES)
            is_group = terminal_name(node.func) == "CounterGroup"
            if not (is_factory or is_group):
                continue
            if any(isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda))
                   for a in mod.ancestors(node)):
                continue  # inside a function: runtime registration
            out.append(self.finding(
                mod, node.lineno,
                "[skip-wrapper] metric registered at import time — "
                "registration must happen at construction, behind the "
                "plane's knob, so a disabled plane leaves zero "
                "registry names (docs/OBSERVABILITY.md overhead "
                "contract)"))
        return out

    # -- (b) unguarded handle use -------------------------------------------

    @staticmethod
    def _handle_in_chain(call: ast.Call) -> Optional[str]:
        """The optional-handle attribute a call reaches through, e.g.
        `srv.flight.freshness.note_push(...)` -> "flight". Only the
        RECEIVER chain counts (the callee attr itself is the method)."""
        node = call.func
        if not isinstance(node, ast.Attribute):
            return None
        node = node.value  # skip the method name
        while isinstance(node, ast.Attribute):
            if node.attr in OPTIONAL_HANDLES:
                return node.attr
            node = node.value
        return None

    @staticmethod
    def _guarded(mod: ModuleInfo, call: ast.Call, handle: str) -> bool:
        # enclosing if/while/ternary whose test None-checks the handle
        for anc in mod.ancestors(call):
            test = getattr(anc, "test", None)
            if isinstance(anc, (ast.If, ast.While, ast.IfExp)) and \
                    test is not None and _has_none_compare(test) and \
                    _mentions_handle(test, handle):
                return True
            # preceding early-return guard in any enclosing block:
            # `if x.handle is None: return` before this statement
            for field in ("body", "orelse", "finalbody"):
                block = getattr(anc, field, None)
                if not isinstance(block, list):
                    continue
                for stmt in block:
                    if stmt.lineno >= call.lineno:
                        break
                    if isinstance(stmt, ast.If) and \
                            _has_none_compare(stmt.test) and \
                            _mentions_handle(stmt.test, handle) and \
                            _terminates(stmt.body):
                        return True
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break  # guards don't cross function boundaries
        return False

    def check_module(self, mod: ModuleInfo,
                     ctx: ProjectContext) -> List[Finding]:
        out = self._import_time_registrations(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            handle = self._handle_in_chain(node)
            if handle is None:
                continue
            if self._guarded(mod, node, handle):
                continue
            out.append(self.finding(
                mod, node.lineno,
                f"[skip-wrapper] call through optional handle "
                f"`.{handle}` without an `is None` guard — the plane "
                f"is None when off; bind it to a local and test once "
                f"(`h = x.{handle}` / `if h is not None:`), the r7 "
                f"skip-wrapper discipline"))
        return out


# ---------------------------------------------------------------------------
# APM004 — raw-thread ban
# ---------------------------------------------------------------------------

# Paths (repo-relative prefixes/suffixes) still allowed to own threads:
# the executor's worker pool IS the thread plane; the launcher and the
# DCN van manage process-boundary I/O the executor cannot subsume; the
# metrics reporter predates r11 and is import-gated. Everything else
# runs as executor-stream programs since r11 — a new raw thread is an
# unaccounted, undrained producer.
RAW_THREAD_ALLOWLIST = (
    "adapm_tpu/exec/",
    "adapm_tpu/launcher.py",
    "adapm_tpu/parallel/dcn.py",
    "adapm_tpu/obs/reporter.py",
    # the transport plane's threads are process-boundary I/O by nature
    # (socket readers, membership beats that must outlive the executor
    # into the teardown window, the loopback fallback drainer) — the
    # same exemption the DCN van carries
    "adapm_tpu/net/",
)


class RawThreadBanRule(Rule):
    """APM004: `threading.Thread(...)` outside the allowlist. r11
    subsumed every subsystem thread (sync loop, prefetch pipeline, tier
    maintenance, serve dispatchers, SLO ticks) into executor streams —
    ordered, drained at shutdown, visible in queue/overlap accounting.
    A raw thread has none of that; route the work through
    `Server.exec.submit` instead, or carry a justified suppression."""

    id = "APM004"
    name = "raw-thread-ban"
    doc = "threading.Thread outside the executor/launcher/dcn/reporter " \
          "allowlist"

    def check_module(self, mod: ModuleInfo,
                     ctx: ProjectContext) -> List[Finding]:
        if any(mod.relpath.startswith(p) or mod.relpath == p
               for p in RAW_THREAD_ALLOWLIST):
            return []
        out = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            is_thread = (isinstance(fn, ast.Attribute)
                         and fn.attr == "Thread"
                         and terminal_name(fn.value) == "threading") or \
                        (isinstance(fn, ast.Name) and fn.id == "Thread")
            if not is_thread:
                continue
            out.append(self.finding(
                mod, node.lineno,
                "[raw-thread-ban] threading.Thread outside the "
                "allowlist — background work runs as executor-stream "
                "programs (Server.exec.submit) so it is ordered, "
                "drained at shutdown, and visible in the exec.* "
                "accounting (docs/EXECUTOR.md)"))
        return out


# ---------------------------------------------------------------------------
# APM005 — donation after dispatch
# ---------------------------------------------------------------------------


class DonationAfterDispatchRule(Rule):
    """APM005: a LOCAL variable passed at a `donate_argnums` position
    of a jitted program is consumed by the dispatch — its device buffer
    is invalid the moment the call returns. Reading it afterwards (in
    the same function, before any rebind) intermittently segfaults or
    returns garbage depending on allocator reuse. The donation map is
    derived from the `@partial(jax.jit, donate_argnums=...)` decorators
    across the whole tree, so the rule can never lag the programs."""

    id = "APM005"
    name = "donation-after-dispatch"
    doc = "donated local read after the dispatching call"

    def check_module(self, mod: ModuleInfo,
                     ctx: ProjectContext) -> List[Finding]:
        out = []
        for fn in _iter_functions(mod.tree):
            out.extend(self._check_function(mod, ctx, fn))
        return out

    def _check_function(self, mod: ModuleInfo, ctx: ProjectContext,
                        fn) -> List[Finding]:
        out = []
        # loads/stores of every name in this function (Name NODES, not
        # just lines: a multi-line call's own argument loads must never
        # count as "read after the dispatch")
        loads: Dict[str, List[ast.Name]] = {}
        stores: Dict[str, List[int]] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Load):
                    loads.setdefault(node.id, []).append(node)
                else:
                    stores.setdefault(node.id, []).append(node.lineno)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _callee_program_name(mod, node)
            donated = ctx.donations.get(name or "")
            if not donated:
                continue
            own = {id(n) for n in ast.walk(node)
                   if isinstance(n, ast.Name)}
            end = getattr(node, "end_lineno", node.lineno)
            for idx in donated:
                if idx >= len(node.args):
                    continue
                arg = node.args[idx]
                if not isinstance(arg, ast.Name):
                    continue  # attributes rebind via `self.x = prog(...)`
                # alive again at the first rebind after the call (the
                # `a = prog(a, ...)` idiom rebinds on the same line)
                rebinds = [ln for ln in stores.get(arg.id, ())
                           if ln >= node.lineno]
                horizon = min(rebinds) if rebinds else float("inf")
                bad = [n.lineno for n in loads.get(arg.id, ())
                       if id(n) not in own and end < n.lineno < horizon]
                if bad:
                    out.append(self.finding(
                        mod, min(bad),
                        f"[donation-after-dispatch] `{arg.id}` was "
                        f"donated to {name}() at line {node.lineno} "
                        f"and read again before any rebind — the "
                        f"buffer is consumed by the dispatch; use the "
                        f"program's RESULT or copy before donating"))
        return out


# ---------------------------------------------------------------------------
# APM006 — revalidate before enqueue
# ---------------------------------------------------------------------------

# the versioned placement state the optimistic planners snapshot
_VERSION_ATTRS = frozenset({"topology_version"})

# store/server entry points whose under-lock call constitutes a
# placement-dependent program ENQUEUE
_ENQUEUE_CALLS = frozenset({
    "_pull", "_push", "gather", "stage_gather", "scatter_add",
    "set_rows", "replica_create", "sync_replicas", "relocate_rows",
})


class RevalidateBeforeEnqueueRule(Rule):
    """APM006: a function that snapshots `topology_version` OUTSIDE the
    server lock (optimistic planning) and later enqueues a
    placement-dependent program UNDER the lock must re-read the version
    inside that locked section (`if srv.topology_version != tv: plan =
    None`). Skipping the re-check dispatches a plan computed against a
    topology that may have moved — the staged-pull/plan-cache
    correctness rule from r6, applied at every enqueue site."""

    id = "APM006"
    name = "revalidate-before-enqueue"
    doc = "optimistic topology snapshot without an under-lock re-check"

    def check_module(self, mod: ModuleInfo,
                     ctx: ProjectContext) -> List[Finding]:
        out = []
        for fn in _iter_functions(mod.tree):
            out.extend(self._check_function(mod, fn))
        return out

    def _check_function(self, mod: ModuleInfo, fn) -> List[Finding]:
        version_reads = []   # (line, under_lock)
        lock_blocks = []     # ast.With nodes guarding _lock
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) and \
                    node.attr in _VERSION_ATTRS and \
                    isinstance(node.ctx, ast.Load):
                version_reads.append(
                    (node.lineno,
                     _enclosing_with(mod, node, _LOCK_ATTRS)))
            elif isinstance(node, ast.With) and \
                    any(_with_item_is(i, _LOCK_ATTRS)
                        for i in node.items):
                lock_blocks.append(node)
        outside = [ln for ln, locked in version_reads if not locked]
        if not outside:
            return []
        first_read = min(outside)
        out = []
        for wb in lock_blocks:
            if wb.lineno < first_read:
                continue
            enqueues = [n for n in ast.walk(wb)
                        if isinstance(n, ast.Call)
                        and terminal_name(n.func) in _ENQUEUE_CALLS]
            if not enqueues:
                continue
            revalidated = any(
                isinstance(n, ast.Attribute)
                and n.attr in _VERSION_ATTRS
                and isinstance(n.ctx, ast.Load)
                for n in ast.walk(wb))
            if not revalidated:
                out.append(self.finding(
                    mod, enqueues[0].lineno,
                    f"[revalidate-before-enqueue] enqueue under the "
                    f"lock after an out-of-lock topology_version "
                    f"snapshot (line {first_read}) without re-reading "
                    f"it under the lock — revalidate or drop the "
                    f"optimistic plan (r6 staged-pull discipline)"))
        return out


# ---------------------------------------------------------------------------
# APM007 — metric-catalog drift
# ---------------------------------------------------------------------------

_METRIC_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_<>{}]+)+$")


class _RegistrationScanner(ast.NodeVisitor):
    """Collect metric registrations from one module: literal names,
    dynamic prefixes (f-strings), CounterGroup expansions, and
    one-level registering helpers (`def _hist(name): ...
    registry.histogram(name, ...)` / `mk = lambda n:
    registry.counter(f"plan_cache.{n}")`)."""

    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.literals: List[Tuple[str, int]] = []   # (name, line)
        self.prefixes: List[Tuple[str, int]] = []   # (prefix, line)
        # helper name -> "" (identity: literal arg IS the name) or the
        # f-string's literal prefix (name = prefix + arg)
        self.helpers: Dict[str, str] = {}
        # module-level literal string tuples (incl. class attributes),
        # for `for name in FIELDS:` expansion
        self.str_tuples: Dict[str, Tuple[str, ...]] = {}
        self._collect_tuples()
        self._collect_helpers()

    # -- literal tuple assignments ------------------------------------------

    def _collect_tuples(self):
        for node in ast.walk(self.mod.tree):
            if not isinstance(node, ast.Assign):
                continue
            if not isinstance(node.value, (ast.Tuple, ast.List)):
                continue
            elts = node.value.elts
            if not elts or not all(isinstance(e, ast.Constant)
                                   and isinstance(e.value, str)
                                   for e in elts):
                continue
            vals = tuple(e.value for e in elts)
            for t in node.targets:
                n = terminal_name(t)
                if n:
                    self.str_tuples[n] = vals

    # -- registering helpers -------------------------------------------------

    @staticmethod
    def _fstring_split(js: ast.JoinedStr) -> Optional[Tuple[str, str]]:
        """(prefix, param) for a single-placeholder f-string like
        f"plan_cache.{n}"; None for anything more complex."""
        prefix = ""
        param = None
        for part in js.values:
            if isinstance(part, ast.Constant):
                if param is not None and part.value:
                    return None  # trailing literal: too complex
                prefix += str(part.value)
            elif isinstance(part, ast.FormattedValue):
                if param is not None or \
                        not isinstance(part.value, ast.Name):
                    return None
                param = part.value.id
        return (prefix, param) if param is not None else None

    def _collect_helpers(self):
        for node in ast.walk(self.mod.tree):
            fn_name, params, body_calls = None, None, None
            if isinstance(node, ast.FunctionDef):
                fn_name = node.name
                params = [a.arg for a in node.args.args]
                body_calls = node
            elif isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Lambda):
                fn_name = terminal_name(node.targets[0])
                params = [a.arg for a in node.value.args.args]
                body_calls = node.value
            elif isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.IfExp):
                # `mk = (lambda n: reg...) if use_reg else (lambda n: ...)`
                for half in (node.value.body, node.value.orelse):
                    if isinstance(half, ast.Lambda):
                        self._maybe_helper(
                            terminal_name(node.targets[0]),
                            [a.arg for a in half.args.args], half)
                continue
            if fn_name is None or body_calls is None:
                continue
            self._maybe_helper(fn_name, params, body_calls)

    def _maybe_helper(self, fn_name, params, scope):
        if not fn_name or not params:
            return
        for call in ast.walk(scope):
            if not isinstance(call, ast.Call):
                continue
            if not (isinstance(call.func, ast.Attribute)
                    and call.func.attr in _REGISTRY_FACTORIES):
                continue
            if not call.args:
                continue
            arg = call.args[0]
            if isinstance(arg, ast.Name) and arg.id == params[0]:
                self.helpers.setdefault(fn_name, "")
            elif isinstance(arg, ast.JoinedStr):
                split = self._fstring_split(arg)
                if split is not None and split[1] == params[0]:
                    self.helpers.setdefault(fn_name, split[0])

    # -- call sites ----------------------------------------------------------

    def visit_Call(self, node: ast.Call):
        self.generic_visit(node)
        fn = node.func
        if isinstance(fn, ast.Attribute) and \
                fn.attr in _REGISTRY_FACTORIES and node.args:
            self._record(node.args[0], node)
            return
        tname = terminal_name(fn)
        if tname == "CounterGroup" and len(node.args) >= 3:
            prefix_node, keys_node = node.args[1], node.args[2]
            if isinstance(prefix_node, ast.Constant):
                prefix = str(prefix_node.value)
                keys = None
                if isinstance(keys_node, (ast.Tuple, ast.List)) and \
                        all(isinstance(e, ast.Constant)
                            for e in keys_node.elts):
                    keys = [e.value for e in keys_node.elts]
                elif isinstance(keys_node, ast.Name):
                    keys = self.str_tuples.get(keys_node.id)
                if keys:
                    for k in keys:
                        self.literals.append(
                            (f"{prefix}.{k}", node.lineno))
                else:
                    self.prefixes.append((prefix + ".", node.lineno))
            return
        if tname in self.helpers and node.args:
            prefix = self.helpers[tname]
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and \
                    isinstance(arg.value, str):
                self.literals.append((prefix + arg.value, node.lineno))
            else:
                self._record_dynamic(prefix, arg, node)

    def _record(self, arg: ast.AST, node: ast.Call):
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            self.literals.append((arg.value, node.lineno))
        elif isinstance(arg, ast.JoinedStr):
            prefix = ""
            for part in arg.values:
                if isinstance(part, ast.Constant):
                    prefix += str(part.value)
                else:
                    break
            if prefix:
                self.prefixes.append((prefix, node.lineno))
        elif isinstance(arg, ast.Name):
            # loop variable over a literal tuple in this module:
            # `for name in SyncStats.FIELDS: reg.gauge(f"sync.{name}")`
            # is handled by the f-string branch; a bare Name arg is a
            # helper param (handled in _collect_helpers) or opaque
            pass

    def _record_dynamic(self, prefix: str, arg: ast.AST, node: ast.Call):
        if prefix:
            self.prefixes.append((prefix, node.lineno))


class MetricCatalogRule(Rule):
    """APM007: the metric namespace must agree across three surfaces —
    the registration call sites (`registry.counter("kv.pull_s")`, ...),
    the docs/OBSERVABILITY.md "Metric catalog" table, and the
    `metrics_snapshot()` schema section list. v1->v9 schema churn had
    no mechanical check; this rule is it. Literal registrations must
    appear in the catalog (and their section in the schema block);
    literal catalog rows of registry kinds (counter/gauge/histogram)
    must be registered somewhere (exactly, or under a dynamic
    registration prefix like `fault.injections.`). Rows whose kind is
    derived/merged/snapshot describe computed snapshot surfaces, not
    registry names, and rows with `…` are explicitly non-exhaustive —
    both are exempt from the code-presence direction."""

    id = "APM007"
    name = "metric-catalog-drift"
    doc = "metric names out of sync between code and " \
          "docs/OBSERVABILITY.md"

    # doc rows of these kinds are not registry registrations
    _EXEMPT_KINDS = ("derived", "merged", "snapshot")

    def check_project(self, ctx: ProjectContext) -> List[Finding]:
        doc = ctx.docs.get("observability")
        if doc is None:
            return []
        doc_path, doc_text = doc
        literals: List[Tuple[str, str, int]] = []  # (name, path, line)
        prefixes: List[str] = []
        for mod in ctx.modules:
            if mod.relpath.endswith("obs/metrics.py"):
                continue  # the registry itself, not a call site
            if "/lint/" in mod.relpath:
                continue  # the linter registers nothing
            sc = _RegistrationScanner(mod)
            sc.visit(mod.tree)
            literals.extend((n, mod.relpath, ln) for n, ln in sc.literals)
            prefixes.extend(p for p, _ in sc.prefixes)
        cat_literals, cat_patterns, exempt, row_lines = \
            self._parse_catalog(doc_text)
        sections = self._parse_schema_sections(doc_text)
        out: List[Finding] = []
        # code -> doc
        for name, path, line in sorted(set(literals)):
            sec = name.split(".", 1)[0]
            if sections and sec not in sections:
                out.append(self.finding(
                    path, line,
                    f"[metric-catalog-drift] metric `{name}`'s section "
                    f"`{sec}` is not in the metrics_snapshot() schema "
                    f"block of docs/OBSERVABILITY.md"))
            if name in cat_literals or name in exempt:
                continue
            if any(name.startswith(p) for p in cat_patterns):
                continue
            out.append(self.finding(
                path, line,
                f"[metric-catalog-drift] metric `{name}` is registered "
                f"here but missing from the docs/OBSERVABILITY.md "
                f"catalog table — add a row (name, kind, unit, "
                f"meaning)"))
        # doc -> code
        code_names = {n for n, _, _ in literals}
        for name in sorted(cat_literals - exempt):
            if name in code_names:
                continue
            if any(name.startswith(p) for p in prefixes):
                continue
            out.append(self.finding(
                doc_path, row_lines.get(name, 1),
                f"[metric-catalog-drift] catalog row `{name}` has no "
                f"registration in the code — stale doc (delete the "
                f"row) or a renamed metric (fix the name)"))
        return out

    # -- doc parsing ---------------------------------------------------------

    def _parse_catalog(self, text: str):
        """(literal names, pattern prefixes, exempt names, name->line)
        from the `## Metric catalog` table. A backticked token expands
        on `/` and `,`; fragments without a dot re-prefix with the
        row's section; tokens containing `<`/`{`/`…`/`*` become
        prefix patterns; rows whose kind is derived/merged/snapshot or
        whose name cell carries `…` are exempt from doc->code."""
        lines = text.splitlines()
        in_catalog = False
        literals: set = set()
        patterns: set = set()
        exempt: set = set()
        row_lines: Dict[str, int] = {}
        for i, line in enumerate(lines, start=1):
            if line.startswith("## "):
                in_catalog = line.strip() == "## Metric catalog"
                continue
            if not in_catalog or not line.startswith("|"):
                continue
            cells = [c.strip() for c in line.strip("|").split("|")]
            if len(cells) < 2 or set(cells[0]) <= {"-", " "}:
                continue
            name_cell, kind_cell = cells[0], cells[1]
            row_exempt = any(k in kind_cell.lower()
                             for k in self._EXEMPT_KINDS) or \
                "…" in name_cell or "..." in name_cell
            tokens = re.findall(r"`([^`]+)`", name_cell)
            # tokens like "(+ per-stream `.<stream>`)" are suffix
            # patterns for the preceding name: note the base as a prefix
            section = None
            for tok in tokens:
                tok = tok.strip()
                if tok.startswith("."):
                    if section:
                        patterns.add(section + ".")
                    continue
                for frag in re.split(r"[/,]", tok):
                    frag = frag.strip()
                    if not frag or frag in ("…", "..."):
                        continue
                    if "." not in frag and section:
                        frag = f"{section}.{frag}"
                    if any(c in frag for c in "<{*…"):
                        prefix = re.split(r"[<{*…]", frag)[0]
                        if prefix:
                            patterns.add(prefix)
                        continue
                    if not _METRIC_NAME_RE.match(frag):
                        continue
                    section = frag.split(".", 1)[0]
                    literals.add(frag)
                    row_lines.setdefault(frag, i)
                    if row_exempt:
                        exempt.add(frag)
        return literals, patterns, exempt, row_lines

    @staticmethod
    def _parse_schema_sections(text: str) -> set:
        """Section names from the metrics_snapshot() schema block
        (`"kv": {...}` entries in the first fenced block after the
        heading)."""
        m = re.search(r"##\s*`Server\.metrics_snapshot\(\)`.*?```(.*?)```",
                      text, re.S)
        if m is None:
            return set()
        return set(re.findall(r'"([a-z_]+)":\s*\{', m.group(1)))


# ---------------------------------------------------------------------------
# APM008 — device-API confinement
# ---------------------------------------------------------------------------

# jax program-construction / transfer attributes (`jax.<attr>`) and
# bare names whose use constitutes constructing a device program or
# placing a buffer — the DevicePort surface (adapm_tpu/device/port.py).
_DEVICE_API_ATTRS = frozenset({"jit", "device_put", "pmap"})
_DEVICE_API_NAMES = frozenset({"shard_map"})

# The one place allowed to touch the device APIs directly: the port
# implementations. Everything else reaches the accelerator through a
# DevicePort method (store dispatches, port.compile for fused steps,
# port.compile_collective for exchanges, port.put_* for transfers), so
# a new backend is one new port class — the ISSUE 14 refactor contract.
# device/refport.py (the pure-NumPy reference port, ISSUE 16) sits
# inside the allowlist but deliberately needs none of it: it imports no
# jax at all, which scripts/portdiff_check.py asserts — the existence
# proof that the DevicePort seam is honest (a backend that never
# touches the device APIs still passes every storm bitwise).
DEVICE_PLANE_ALLOWLIST = ("adapm_tpu/device/",)


class DeviceApiConfinementRule(Rule):
    """APM008: `jax.jit` / `jax.device_put` / `jax.pmap` / `shard_map`
    only under `adapm_tpu/device/`. A jit or device_put call anywhere
    else re-opens the tree-wide-edit problem the DevicePort closed:
    the next accelerator backend would have to find and port that site
    too. Route program construction through `port.compile(...)` /
    `port.compile_collective(...)`, transfers through `port.put_*` /
    `port.install_pool`, and data-plane dispatch through the store's
    port methods. Model-math / inherently-backend-specific modules
    (KGE eval programs, Pallas kernels) carry justified suppressions,
    never a widened allowlist (docs/INVARIANTS.md#apm008)."""

    id = "APM008"
    name = "device-api-confinement"
    doc = "jax program-construction API outside adapm_tpu/device/"

    @staticmethod
    def _attr_root(node: ast.AST) -> Optional[str]:
        """Root Name of an attribute chain (`jax.lax.psum` ->
        "jax"); None for non-Name roots."""
        while isinstance(node, ast.Attribute):
            node = node.value
        return node.id if isinstance(node, ast.Name) else None

    def check_module(self, mod: ModuleInfo,
                     ctx: ProjectContext) -> List[Finding]:
        if any(mod.relpath.startswith(p)
               for p in DEVICE_PLANE_ALLOWLIST):
            return []
        banned_attrs = _DEVICE_API_ATTRS | _DEVICE_API_NAMES
        out = []
        seen = set()  # (line, attr): a nested attribute chain ending
        # in `.shard_map.shard_map` matches twice
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Attribute) and \
                    node.attr in banned_attrs and \
                    self._attr_root(node.value) == "jax":
                key = (node.lineno, node.attr)
                if key in seen:
                    continue
                seen.add(key)
                out.append(self.finding(
                    mod, node.lineno,
                    f"[device-api-confinement] jax …{node.attr} outside "
                    f"adapm_tpu/device/ — construct programs through "
                    f"the DevicePort (port.compile / port.put_* / the "
                    f"store's dispatch methods) so a new accelerator "
                    f"backend is one port implementation, not a "
                    f"tree-wide edit (docs/INVARIANTS.md#apm008)"))
            elif isinstance(node, ast.Name) and \
                    node.id in _DEVICE_API_NAMES and \
                    isinstance(node.ctx, ast.Load):
                out.append(self.finding(
                    mod, node.lineno,
                    "[device-api-confinement] shard_map outside "
                    "adapm_tpu/device/ — collective programs are "
                    "constructed by port.compile_collective "
                    "(docs/INVARIANTS.md#apm008)"))
            elif isinstance(node, ast.ImportFrom):
                names = {a.name for a in node.names}
                banned = names & (_DEVICE_API_NAMES |
                                  (_DEVICE_API_ATTRS
                                   if (node.module or "") == "jax"
                                   else frozenset()))
                if banned:
                    out.append(self.finding(
                        mod, node.lineno,
                        f"[device-api-confinement] importing "
                        f"{sorted(banned)} outside adapm_tpu/device/ — "
                        f"reach the device stack through the "
                        f"DevicePort (docs/INVARIANTS.md#apm008)"))
            elif isinstance(node, ast.Import):
                # plain `import jax.<...>.shard_map` — the evasion
                # form the attribute check alone would miss
                mods = [a.name for a in node.names
                        if set(a.name.split(".")) & banned_attrs]
                if mods:
                    out.append(self.finding(
                        mod, node.lineno,
                        f"[device-api-confinement] importing "
                        f"{sorted(mods)} outside adapm_tpu/device/ — "
                        f"reach the device stack through the "
                        f"DevicePort (docs/INVARIANTS.md#apm008)"))
        return out


# ---------------------------------------------------------------------------


def default_rules() -> List[Rule]:
    """The shipping rule set, in ID order (analyzer entry point)."""
    return [
        GateCoverageRule(),
        NoBlockingUnderLockRule(),
        SkipWrapperRule(),
        RawThreadBanRule(),
        DonationAfterDispatchRule(),
        RevalidateBeforeEnqueueRule(),
        MetricCatalogRule(),
        DeviceApiConfinementRule(),
    ]
