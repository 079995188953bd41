"""Engine-discipline lint.

The engine orders operations by declared data dependencies
(``const_vars``/``mutable_vars``); host state touched by a pushed closure
but *not* declared is invisible to the scheduler and races with every
other pushed op. Rules:

- ``push-missing-vars``            an engine ``push``/``push_async`` call
                                   site declares neither ``const_vars``
                                   nor ``mutable_vars``
- ``push-async-undeclared-mutable`` the pushed closure mutates host state
                                   it closes over (subscript/attribute
                                   stores, mutating method calls,
                                   ``nonlocal``/``global`` rebinds) whose
                                   names do not appear in the call's
                                   ``mutable_vars``/``const_vars``
- ``waitall-as-fence``             ``waitall()`` after a push in the same
                                   function: ``waitall`` drains the device
                                   queue but is NOT a happens-before edge
                                   for host ``on_complete`` callbacks (the
                                   documented footgun) — use
                                   ``engine.fence(vars).wait()``
- ``drain-as-fence``               a bare loop whose body only calls
                                   ``wait_for_var``/``wait_to_read`` per
                                   element, i.e. a hand-rolled multi-var
                                   fence — ``engine.fence(vars)`` is one
                                   pushed op and also fences callbacks

Only *engine* pushes are matched (``push_async`` anywhere; ``push`` only
via an engine module alias / ``self._engine`` / an import from engine) so
``KVStore.push`` and friends are not confused with engine ops.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import Finding, SourceModule, dotted, import_aliases, unparse

#: method calls that mutate their receiver in place
_MUTATORS = {"append", "extend", "insert", "add", "update", "setdefault",
             "pop", "popleft", "appendleft", "clear", "remove", "fill",
             "sort", "put"}
_WAIT_CALLS = {"wait_for_var", "wait_to_read"}


def _is_engine_push(call: ast.Call, aliases: Dict[str, str]
                    ) -> Optional[str]:
    d = dotted(call.func)
    if d is None:
        return None
    tail = d.split(".")[-1]
    if tail == "push_async":
        return "push_async"
    if tail == "push":
        head = d.split(".")[0]
        if d == "push" and aliases.get("push", "").endswith("engine.push"):
            return "push"
        if head != "self" and aliases.get(head, "").endswith("engine"):
            return "push"
        if "._engine." in d or d.startswith("_engine."):
            return "push"
    return None


def _declared_names(call: ast.Call) -> Set[str]:
    """Every identifier mentioned in const_vars/mutable_vars expressions
    (positional slots 1/2 or keywords)."""
    exprs: List[ast.AST] = list(call.args[1:3])
    for kw in call.keywords:
        if kw.arg in ("const_vars", "mutable_vars"):
            exprs.append(kw.value)
    names: Set[str] = set()
    for e in exprs:
        if e is None:
            continue
        for node in ast.walk(e):
            if isinstance(node, ast.Name):
                names.add(node.id)
            elif isinstance(node, ast.Attribute):
                names.add(node.attr)
    return names


def _has_var_decl(call: ast.Call) -> bool:
    if len(call.args) >= 2:
        return True
    return any(kw.arg in ("const_vars", "mutable_vars")
               for kw in call.keywords)


def _store_base(node: ast.AST) -> Optional[str]:
    """Innermost Name of a subscript/attribute store target."""
    seen_deref = False
    while isinstance(node, (ast.Subscript, ast.Attribute, ast.Starred)):
        seen_deref = True
        node = node.value
    if seen_deref and isinstance(node, ast.Name):
        return node.id
    return None


def _closure_mutations(fn: ast.AST) -> List[Tuple[str, int]]:
    """(name, line) for every free name the closure mutates."""
    if isinstance(fn, ast.Lambda):
        params = {a.arg for a in fn.args.args}
        body: List[ast.AST] = [fn.body]
    else:
        args = fn.args
        params = {a.arg for a in
                  args.posonlyargs + args.args + args.kwonlyargs}
        if args.vararg:
            params.add(args.vararg.arg)
        if args.kwarg:
            params.add(args.kwarg.arg)
        body = list(fn.body)
    local: Set[str] = set()
    rebound: Set[str] = set()     # nonlocal/global names
    muts: List[Tuple[str, int]] = []
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, (ast.Nonlocal, ast.Global)):
                rebound.update(node.names)
            elif isinstance(node, (ast.Assign, ast.AugAssign, ast.For,
                                   ast.AnnAssign)):
                targets = getattr(node, "targets", None) or \
                    [getattr(node, "target")]
                for t in targets:
                    if isinstance(t, ast.Name):
                        if t.id in rebound:
                            muts.append((t.id, node.lineno))
                        else:
                            local.add(t.id)
                    elif isinstance(t, ast.Tuple):
                        for e in t.elts:
                            if isinstance(e, ast.Name):
                                local.add(e.id)
                    else:
                        base = _store_base(t)
                        if base is not None:
                            muts.append((base, node.lineno))
            elif isinstance(node, ast.withitem) and \
                    isinstance(node.optional_vars, ast.Name):
                local.add(node.optional_vars.id)
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _MUTATORS and \
                    isinstance(node.func.value, ast.Name):
                muts.append((node.func.value.id, node.lineno))
    return [(n, ln) for n, ln in muts
            if n not in params and n not in local and n != "self"]


class _FnLint:
    def __init__(self, mod: SourceModule, aliases: Dict[str, str],
                 qualname: str, fn: ast.AST, findings: List[Finding]):
        self.mod = mod
        self.aliases = aliases
        self.qualname = qualname
        self.fn = fn
        self.findings = findings
        # local defs/lambdas by name, for resolving the pushed closure
        self.local_fns: Dict[str, ast.AST] = {}
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn:
                self.local_fns[node.name] = node
            elif isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Lambda) and \
                    len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name):
                self.local_fns[node.targets[0].id] = node.value

    def run(self):
        calls = [n for n in ast.walk(self.fn) if isinstance(n, ast.Call)]
        push_lines = []
        for node in calls:
            kind = _is_engine_push(node, self.aliases)
            if kind is not None:
                push_lines.append(node.lineno)
                self._check_push(node, kind)
        for node in calls:
            d = dotted(node.func)
            if d is not None and d.split(".")[-1] == "waitall" and \
                    push_lines and node.lineno > min(push_lines):
                self.findings.append(Finding(
                    "engine", "waitall-as-fence", self.mod.relpath,
                    node.lineno, self.qualname, d,
                    "waitall() after an engine push in the same "
                    "function: it drains the queue but is not a "
                    "happens-before edge for host callbacks — use "
                    "engine.fence(vars).wait()"))
        self._check_drain_loops()

    def _check_push(self, call: ast.Call, kind: str):
        if not _has_var_decl(call):
            self.findings.append(Finding(
                "engine", "push-missing-vars", self.mod.relpath,
                call.lineno, self.qualname,
                "%s:%s" % (kind, unparse(call.func)),
                "%s call declares neither const_vars nor mutable_vars — "
                "the engine cannot order this op against anything" % kind))
        has_mutable = len(call.args) >= 3 or any(
            kw.arg == "mutable_vars" for kw in call.keywords)
        if has_mutable:
            # the op owns a write-var; host state it mutates is assumed to
            # be covered by it (name-level matching can't see through var
            # indirection without drowning correct sites in noise)
            return
        closure = self._resolve_closure(call)
        if closure is None:
            return
        # one level transitive: the closure may delegate the mutation to a
        # sibling local helper (lambda: fetch(i, a) style)
        reach = [closure]
        for node in ast.walk(closure):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name) and \
                    node.func.id in self.local_fns and \
                    self.local_fns[node.func.id] not in reach:
                reach.append(self.local_fns[node.func.id])
        for fn in reach:
            for name, line in _closure_mutations(fn):
                self.findings.append(Finding(
                    "engine", "push-async-undeclared-mutable",
                    self.mod.relpath, line, self.qualname,
                    "%s:%s" % (kind, name),
                    "pushed closure mutates '%s' but the %s declares no "
                    "mutable_vars — the engine cannot serialize this "
                    "against other ops touching it" % (name, kind)))

    def _resolve_closure(self, call: ast.Call) -> Optional[ast.AST]:
        if not call.args:
            return None
        fn = call.args[0]
        if isinstance(fn, ast.Lambda):
            return fn
        if isinstance(fn, ast.Name):
            return self.local_fns.get(fn.id)
        return None

    def _check_drain_loops(self):
        for node in ast.walk(self.fn):
            if not isinstance(node, (ast.For, ast.AsyncFor)):
                continue
            if len(node.body) != 1 or node.orelse:
                continue
            st = node.body[0]
            if not (isinstance(st, ast.Expr)
                    and isinstance(st.value, ast.Call)):
                continue
            func = st.value.func
            tail = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else None)
            if tail not in _WAIT_CALLS:
                continue
            self.findings.append(Finding(
                "engine", "drain-as-fence", self.mod.relpath, node.lineno,
                self.qualname,
                "%s<-%s" % (tail, unparse(node.iter)),
                "per-element %s loop used as a multi-var fence — "
                "engine.fence(vars) is one pushed op and also fences "
                "host callbacks" % tail))


def check(modules: Sequence[SourceModule]) -> List[Finding]:
    findings: List[Finding] = []
    for m in modules:
        aliases = import_aliases(m.tree)
        # module-level statements + every def (methods get Class.method)
        _FnLint(m, aliases, "%s:" % m.modname,
                ast.Module(body=[s for s in m.tree.body
                                 if not isinstance(s, (ast.FunctionDef,
                                                       ast.AsyncFunctionDef,
                                                       ast.ClassDef))],
                           type_ignores=[]),
                findings).run()
        for node in m.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                _FnLint(m, aliases, "%s:%s" % (m.modname, node.name),
                        node, findings).run()
            elif isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        _FnLint(m, aliases,
                                "%s:%s.%s" % (m.modname, node.name,
                                              sub.name),
                                sub, findings).run()
    return findings
