"""The acs-lint pass families: lock discipline + hot-path purity.

Everything here is one ``ast`` walk per module (plus a tokenize pass for
comments, annotations.py) — zero runtime dependencies beyond stdlib, so
the analyzer can run in any environment the package imports in,
including CI images without jax.

Rules (names in findings.py, rationale in docs/ANALYSIS.md):

- ``guarded-by``           read/write of an annotated attribute outside
                           a lexical ``with <base>.<lock>`` over the
                           same base (or a ``holds:`` helper)
- ``blocking-under-lock``  RPC / queue / socket / sleep / device-sync
                           call lexically inside a ``with <lock>`` body
- ``wall-clock``           any ``time.time()`` — deadline/TTL math must
                           use ``time.monotonic()`` (PR 5's budgets)
- ``host-only-jax``        ``jax`` import in a module declared
                           ``# acs-lint: host-only``
- ``thread-lifecycle``     a ``threading.Thread`` neither daemonized nor
                           joined anywhere in its module
- ``dispatch-purity``      ``block_until_ready`` / ``np.asarray`` of a
                           dispatch result inside the dispatch half of
                           an ``evaluate_async`` (the materialize thunk
                           — nested def/lambda — is exempt)
"""

from __future__ import annotations

import ast
import re

from .annotations import ModuleComments
from .findings import (
    Finding,
    RULE_BLOCKING_UNDER_LOCK,
    RULE_DISPATCH_PURITY,
    RULE_GUARDED_BY,
    RULE_HOST_ONLY_JAX,
    RULE_THREAD_LIFECYCLE,
    RULE_WALL_CLOCK,
    Suppression,
    dedupe,
)

# with-context names treated as locks for blocking-under-lock: anything
# whose final attribute/name looks lock-ish, plus every lock registered
# through a guarded-by annotation in the module
_LOCKISH = re.compile(r"(?i)(lock|cond|mutex)")

# method names that block the calling thread: device sync, sleeps,
# joins, socket/file-durability I/O, RPC entry points
_BLOCKING_METHODS = {
    "block_until_ready", "sleep", "recv", "recv_into", "sendall",
    "accept", "connect", "readline", "urlopen", "fsync", "with_call",
    "result", "getaddrinfo", "create_connection",
}
# .join blocks only on threads/processes — str.join and os.path.join are
# pure; require a threadish receiver before flagging
_THREADISH = re.compile(r"(?i)(thread|proc|worker|timer|pump|executor)")
# cond.wait/wait_for ON the held condition is the legitimate
# condition-variable pattern; on anything else it's a blocked thread
_WAIT_METHODS = {"wait", "wait_for"}
# .get blocks only on queues — flagged when the receiver looks like a
# queue or the call passes Queue.get's block/timeout kwargs
_QUEUEISH = re.compile(r"(?i)(queue|jobs|inbox|mailbox|\bq\b)")

_TIME_MODULES = {"time", "_time"}


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # noqa: BLE001 — unparse gaps on exotic nodes
        return "<expr>"


class _ModuleIndex(ast.NodeVisitor):
    """Pre-pass: guard registry, holds map, thread join/daemon sites."""

    def __init__(self, comments: ModuleComments):
        self.comments = comments
        # attribute name -> set of lock names that may guard it (union
        # across classes: guarded access requires `with <base>.<lock>`
        # over the SAME base text, so cross-class collisions stay safe)
        self.attr_guards: dict[str, set[str]] = {}
        # module-global name -> set of lock names
        self.name_guards: dict[str, set[str]] = {}
        # id(FunctionDef) -> lock names the caller must hold
        self.holds: dict[int, set[str]] = {}
        # base texts that .join()/daemon-assign somewhere in the module
        self.joined_bases: set[str] = set()
        self.daemonized_bases: set[str] = set()
        self._class_depth = 0

    # ------------------------------------------------------------- guards

    def _register_assign(self, node, targets) -> None:
        lock = self.comments.guarded_by(node.lineno)
        if not lock:
            return
        for target in targets:
            if (isinstance(target, ast.Attribute)
                    and isinstance(target.value, ast.Name)
                    and target.value.id == "self"):
                self.attr_guards.setdefault(target.attr, set()).add(lock)
            elif isinstance(target, ast.Name):
                if self._class_depth:
                    self.attr_guards.setdefault(target.id, set()).add(lock)
                else:
                    self.name_guards.setdefault(target.id, set()).add(lock)

    def visit_Assign(self, node: ast.Assign) -> None:
        self._register_assign(node, node.targets)
        # `t.daemon = True` after construction counts as daemonized
        if (len(node.targets) == 1
                and isinstance(node.targets[0], ast.Attribute)
                and node.targets[0].attr == "daemon"
                and isinstance(node.value, ast.Constant)
                and node.value.value is True):
            self.daemonized_bases.add(_unparse(node.targets[0].value))
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        self._register_assign(node, [node.target])
        self.generic_visit(node)

    # -------------------------------------------------------------- holds

    def _register_def(self, node) -> None:
        locks = self.comments.holds(node.lineno)
        if locks:
            self.holds[id(node)] = locks
        self.generic_visit(node)

    def visit_FunctionDef(self, node):  # noqa: N802
        self._register_def(node)

    def visit_AsyncFunctionDef(self, node):  # noqa: N802
        self._register_def(node)

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._class_depth += 1
        self.generic_visit(node)
        self._class_depth -= 1

    # ------------------------------------------------------------- joins

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr == "join":
            self.joined_bases.add(_unparse(func.value))
        self.generic_visit(node)


def _is_thread_ctor(func: ast.AST) -> bool:
    if isinstance(func, ast.Attribute) and func.attr == "Thread":
        return True
    return isinstance(func, ast.Name) and func.id == "Thread"


def _daemon_kwarg_true(call: ast.Call) -> bool:
    for kw in call.keywords:
        if kw.arg == "daemon" and isinstance(kw.value, ast.Constant):
            return kw.value.value is True
    return False


class ModuleChecker(ast.NodeVisitor):
    """The main walk: lock discipline + purity over one module."""

    def __init__(self, path: str, source: str, tree: ast.Module,
                 comments: ModuleComments):
        self.path = path
        self.tree = tree
        self.comments = comments
        self.index = _ModuleIndex(comments)
        self.index.visit(tree)
        self.findings: list[Finding] = []
        self.suppressions: list[Suppression] = []
        # lexical state
        self._func_stack: list[ast.AST] = []
        self._class_stack: list[str] = []
        # active `with` locks: (base_text or None, lock_name, full_text)
        self._withlocks: list[tuple[str | None, str, str]] = []
        self._known_locks = set()
        for locks in self.index.attr_guards.values():
            self._known_locks |= locks
        for locks in self.index.name_guards.values():
            self._known_locks |= locks
        self._thread_calls_handled: set[int] = set()

    # --------------------------------------------------------------- emit

    def _qualname(self) -> str:
        parts = list(self._class_stack)
        for func in self._func_stack:
            name = getattr(func, "name", "<lambda>")
            parts.append(name)
        return ".".join(parts) or "<module>"

    def _emit(self, rule: str, symbol: str, message: str,
              node: ast.AST) -> None:
        first = getattr(node, "lineno", 1)
        last = getattr(node, "end_lineno", first)
        ignored = self.comments.ignored_rules(first, last)
        if rule in ignored:
            self.suppressions.append(Suppression(
                path=self.path, rule=rule, symbol=symbol,
                line=first, reason=ignored[rule],
            ))
            return
        self.findings.append(Finding(
            path=self.path, rule=rule, symbol=symbol,
            message=message, line=first,
        ))

    # ------------------------------------------------------------ imports

    def _check_import(self, node, modname: str) -> None:
        if not self.comments.host_only:
            return
        if modname == "jax" or modname.startswith("jax."):
            self._emit(
                RULE_HOST_ONLY_JAX,
                f"{self._qualname()}:import {modname}",
                "module is declared `# acs-lint: host-only` but imports "
                "jax — host-only modules must never touch the device "
                "runtime (tests/test_analysis_gate.py host-only modules)",
                node,
            )

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            self._check_import(node, alias.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module:
            self._check_import(node, node.module)
        self.generic_visit(node)

    # ----------------------------------------------------- scope tracking

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._class_stack.append(node.name)
        # a class body opens a fresh lexical scope: with-locks from an
        # enclosing function don't cover a nested class (rare, safe)
        saved, self._withlocks = self._withlocks, []
        self.generic_visit(node)
        self._withlocks = saved
        self._class_stack.pop()

    def _visit_func(self, node) -> None:
        self._func_stack.append(node)
        saved, self._withlocks = self._withlocks, []
        self.generic_visit(node)
        self._withlocks = saved
        self._func_stack.pop()

    def visit_FunctionDef(self, node):  # noqa: N802
        if node.name == "evaluate_async":
            self._check_dispatch_purity(node)
        self._visit_func(node)

    def visit_AsyncFunctionDef(self, node):  # noqa: N802
        self._visit_func(node)

    def visit_Lambda(self, node: ast.Lambda) -> None:
        # lambdas KEEP the enclosing with-lock context: predicates like
        # `cond.wait_for(lambda: token in self._released)` evaluate with
        # the condition held — clearing the context would flag the
        # canonical condition-variable pattern
        self._func_stack.append(node)
        self.generic_visit(node)
        self._func_stack.pop()

    def visit_With(self, node: ast.With) -> None:
        pushed = 0
        for item in node.items:
            ce = item.context_expr
            if isinstance(ce, ast.Attribute):
                self._withlocks.append(
                    (_unparse(ce.value), ce.attr, _unparse(ce)))
                pushed += 1
            elif isinstance(ce, ast.Name):
                self._withlocks.append((None, ce.id, ce.id))
                pushed += 1
            for expr in filter(None, (item.context_expr,
                                      item.optional_vars)):
                self.visit(expr)
        for stmt in node.body:
            self.visit(stmt)
        del self._withlocks[len(self._withlocks) - pushed:]

    visit_AsyncWith = visit_With

    # ------------------------------------------------------ lock discipline

    def _holds_any(self, locks: set[str]) -> bool:
        for func in self._func_stack:
            if self.index.holds.get(id(func), set()) & locks:
                return True
        return False

    def _in_init_of_self(self, base: str) -> bool:
        if base != "self":
            return False
        return any(getattr(f, "name", "") in ("__init__", "__new__")
                   for f in self._func_stack)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        locks = self.index.attr_guards.get(node.attr)
        if locks:
            base = _unparse(node.value)
            held = any(
                lock in locks and base_text == base
                for base_text, lock, _full in self._withlocks
            )
            if (not held and not self._holds_any(locks)
                    and not self._in_init_of_self(base)):
                want = " or ".join(sorted(locks))
                self._emit(
                    RULE_GUARDED_BY,
                    f"{self._qualname()}:{base}.{node.attr}",
                    f"`{base}.{node.attr}` is guarded-by `{want}` but "
                    f"accessed outside `with {base}.{want}` (and no "
                    "enclosing `# holds:` annotation)",
                    node,
                )
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name) -> None:
        locks = self.index.name_guards.get(node.id)
        if locks and self._func_stack:
            held = any(
                base_text is None and lock in locks
                for base_text, lock, _full in self._withlocks
            )
            if not held and not self._holds_any(locks):
                want = " or ".join(sorted(locks))
                self._emit(
                    RULE_GUARDED_BY,
                    f"{self._qualname()}:{node.id}",
                    f"global `{node.id}` is guarded-by `{want}` but "
                    f"accessed outside `with {want}`",
                    node,
                )
        self.generic_visit(node)

    # ----------------------------------------------------------- blocking

    def _lockish_withs(self) -> list[tuple[str | None, str, str]]:
        return [
            entry for entry in self._withlocks
            if _LOCKISH.search(entry[1]) or entry[1] in self._known_locks
        ]

    def _holds_locks(self) -> set[str]:
        out: set[str] = set()
        for func in self._func_stack:
            out |= self.index.holds.get(id(func), set())
        return out

    def _check_blocking(self, node: ast.Call) -> None:
        # a ``# holds:`` helper runs with the named lock held by contract,
        # so its blocking calls stall contenders exactly like a lexical
        # ``with`` — both count as held context here
        held = self._lockish_withs()
        held += [(None, lock, f"{lock} (held per # holds:)")
                 for lock in sorted(self._holds_locks())]
        if not held:
            return
        func = node.func
        if not isinstance(func, ast.Attribute):
            return
        method = func.attr
        base_text = _unparse(func.value)
        blocking = False
        if method in _BLOCKING_METHODS:
            blocking = True
        elif method == "join":
            blocking = bool(_THREADISH.search(base_text))
        elif method in _WAIT_METHODS:
            # cond.wait()/wait_for() ON a held condition is the pattern
            # that releases the lock while waiting — anything else
            # blocks with the lock held
            blocking = all(base_text != full for _b, _l, full in held)
        elif method == "get":
            has_block_kwargs = any(
                kw.arg in ("timeout", "block") for kw in node.keywords
            )
            blocking = has_block_kwargs or bool(_QUEUEISH.search(base_text))
        if blocking:
            inside = ", ".join(full for _b, _l, full in held)
            self._emit(
                RULE_BLOCKING_UNDER_LOCK,
                f"{self._qualname()}:{base_text}.{method}",
                f"blocking call `{base_text}.{method}(...)` lexically "
                f"inside `with {inside}` — holders stall every thread "
                "contending for the lock",
                node,
            )

    # --------------------------------------------------------- wall clock

    def _check_wall_clock(self, node: ast.Call) -> None:
        func = node.func
        if (isinstance(func, ast.Attribute) and func.attr == "time"
                and isinstance(func.value, ast.Name)
                and func.value.id in _TIME_MODULES):
            self._emit(
                RULE_WALL_CLOCK,
                f"{self._qualname()}:time.time",
                "wall-clock time.time() jumps under NTP slew — use "
                "time.monotonic() (or srv/clock.monotonic_wall for "
                "epoch-anchored stamps); suppress only for human-facing "
                "display values",
                node,
            )

    # ------------------------------------------------------ thread rules

    def _check_thread(self, node: ast.Call,
                      target_text: str | None) -> None:
        if _daemon_kwarg_true(node):
            return
        if target_text and (
                target_text in self.index.joined_bases
                or target_text in self.index.daemonized_bases):
            return
        what = target_text or "<unassigned>"
        self._emit(
            RULE_THREAD_LIFECYCLE,
            f"{self._qualname()}:Thread({what})",
            f"threading.Thread bound to `{what}` is neither "
            "daemon=True nor .join()ed anywhere in this module — "
            "non-daemon threads outlive stop() and hang interpreter "
            "shutdown",
            node,
        )

    def visit_Assign(self, node: ast.Assign) -> None:
        if (isinstance(node.value, ast.Call)
                and _is_thread_ctor(node.value.func)
                and len(node.targets) == 1):
            self._thread_calls_handled.add(id(node.value))
            self._check_thread(node.value, _unparse(node.targets[0]))
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        if (_is_thread_ctor(node.func)
                and id(node) not in self._thread_calls_handled):
            self._check_thread(node, None)
        self._check_blocking(node)
        self._check_wall_clock(node)
        self.generic_visit(node)

    # -------------------------------------------------- dispatch purity

    def _check_dispatch_purity(self, node) -> None:
        """The dispatch half of evaluate_async must only enqueue device
        work; materialization belongs in the returned thunk (nested
        def/lambda), or the pipeline's overlap collapses to sync."""

        def body_nodes(root):
            """Walk excluding nested function bodies (the thunk)."""
            stack = list(ast.iter_child_nodes(root))
            while stack:
                child = stack.pop()
                yield child
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                stack.extend(ast.iter_child_nodes(child))

        call_bound: set[str] = set()
        for child in body_nodes(node):
            if (isinstance(child, ast.Assign)
                    and isinstance(child.value, ast.Call)):
                for target in child.targets:
                    if isinstance(target, ast.Name):
                        call_bound.add(target.id)
        qual = ".".join(self._class_stack + [node.name])
        for child in body_nodes(node):
            if not isinstance(child, ast.Call):
                continue
            func = child.func
            if (isinstance(func, ast.Attribute)
                    and func.attr == "block_until_ready"):
                self._sync_finding(qual, "block_until_ready", child)
            if (isinstance(func, ast.Attribute) and func.attr == "asarray"
                    and isinstance(func.value, ast.Name)
                    and func.value.id == "np"
                    and child.args
                    and isinstance(child.args[0], ast.Name)
                    and child.args[0].id in call_bound):
                self._sync_finding(
                    qual, f"np.asarray({child.args[0].id})", child)

    def _sync_finding(self, qual: str, what: str, node: ast.AST) -> None:
        self._emit(
            RULE_DISPATCH_PURITY,
            f"{qual}:{what}",
            f"`{what}` in the dispatch half of evaluate_async forces a "
            "device sync before the thunk runs — materialization "
            "belongs in the returned thunk (docs/PIPELINE.md)",
            node,
        )


def check_module(path: str, source: str) -> tuple[list[Finding],
                                                  list[Suppression]]:
    """Run every pass over one module's source; returns (findings,
    counted inline suppressions).  ``path`` is the repo-relative posix
    path used in finding identity."""
    tree = ast.parse(source, filename=path)
    comments = ModuleComments(source)
    checker = ModuleChecker(path, source, tree, comments)
    checker.visit(tree)
    return dedupe(checker.findings), checker.suppressions
