"""Happens-before race checker — the static half of the engine sanitizer.

The engine orders pushed ops ONLY by their declared ``const_vars`` /
``mutable_vars``. Host state a pushed closure touches beyond that
declaration is invisible to the scheduler: two such ops race, and a
host-side read of it is unsynchronized unless a fence intervenes. This
checker tracks state provenance into pushed closures — through lambdas,
local helper defs, module/method helpers one call level deep, and
container aliasing (``alias = results``) — and across host calls via the
same interprocedural fixpoint style as :mod:`.lockorder`, whose
``_Index`` / ``_collect_summaries`` call graph it reuses. Rules:

- ``undeclared-var-access``   two push sites touch the same host state
  (at least one writing it) while sharing no declared var identifier —
  the engine cannot order them: a silent WW/RW race. Both sites are
  named in the finding.
- ``unfenced-host-read``      host code reads (dereferences) state that
  an earlier push in the same function — direct, or through a may-push
  callee — writes, with no ``engine.fence(vars).wait()`` /
  ``wait_to_read`` / may-sync call between push and read.
- ``var-use-after-delete``    an engine var is named in a push/fence/
  wait var list (or deleted again) after ``delete_variable(v)`` with no
  rebinding of ``v`` in between.

Resolution is conservative in the same way as the lock-order pass:
unresolvable receivers create no events and no findings, and any
``.wait()``-shaped call suppresses ``unfenced-host-read`` (an unknown
wait can only hide findings, never invent them). The dynamic complement
is ``MXNET_ENGINE_SANITIZER=1`` (per-var epoch tracking in
``engine.py``); see docs/static_analysis.md and docs/concurrency.md.
"""
from __future__ import annotations

import ast
import builtins
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import Finding, SourceModule, dotted, import_aliases
from .engine_lint import _MUTATORS, _declared_names, _is_engine_push
from .lockorder import FuncKey, _Index, _collect_summaries

#: call tails that establish a happens-before edge for host reads
_SYNC_TAILS = {"wait", "wait_for_var", "wait_for_all", "wait_to_read",
               "wait_for_file", "join"}

#: builtins whose call dereferences (reads the contents of) an argument
_CONTENT_FNS = {"len", "list", "tuple", "dict", "set", "frozenset", "sum",
                "sorted", "min", "max", "any", "all", "iter", "next",
                "enumerate", "zip", "str", "repr", "bool", "float", "int"}

#: state keys never treated as engine-managed host state
_IGNORED_STATES = {"self"}
_BUILTIN_NAMES = frozenset(dir(builtins))


def _base_key(node: ast.AST) -> Optional[str]:
    """Storage base of a target/receiver chain: bare name ``x`` or
    ``self.attr``; ``None`` when unresolvable."""
    while isinstance(node, (ast.Subscript, ast.Starred)):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        if isinstance(node.value, ast.Name):
            if node.value.id == "self":
                return "self.%s" % node.attr
            return node.value.id
        return _base_key(node.value)
    return None


def _fn_params(fn: ast.AST) -> Set[str]:
    args = fn.args
    params = {a.arg for a in args.posonlyargs + args.args + args.kwonlyargs}
    if args.vararg:
        params.add(args.vararg.arg)
    if args.kwarg:
        params.add(args.kwarg.arg)
    return params


def _closure_touches(fn: ast.AST) -> Dict[str, Tuple[str, int]]:
    """state key -> ("write"|"read", line) for every free piece of host
    state the closure touches (write dominates read)."""
    params = _fn_params(fn)
    body: List[ast.AST] = [fn.body] if isinstance(fn, ast.Lambda) \
        else list(fn.body)
    local: Set[str] = set()
    rebound: Set[str] = set()
    writes: Dict[str, int] = {}
    reads: Dict[str, int] = {}
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
                            writes.setdefault(t.id, node.lineno)
                        else:
                            local.add(t.id)
                    elif isinstance(t, ast.Tuple):
                        for e in t.elts:
                            if isinstance(e, ast.Name):
                                local.add(e.id)
                    else:
                        key = _base_key(t) if t is not None else None
                        if key:
                            writes.setdefault(key, node.lineno)
            elif isinstance(node, ast.withitem) and \
                    isinstance(node.optional_vars, ast.Name):
                local.add(node.optional_vars.id)
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _MUTATORS:
                key = _base_key(node.func.value)
                if key:
                    writes.setdefault(key, node.lineno)
            elif isinstance(node, ast.Name) and \
                    isinstance(node.ctx, ast.Load):
                reads.setdefault(node.id, node.lineno)
            elif isinstance(node, ast.Attribute) and \
                    isinstance(node.ctx, ast.Load) and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id == "self":
                reads.setdefault("self.%s" % node.attr, node.lineno)

    def _free(key: str) -> bool:
        base = key.split(".")[0]
        return base not in params and base not in local and \
            key not in _IGNORED_STATES

    out: Dict[str, Tuple[str, int]] = {}
    for k, ln in writes.items():
        if _free(k):
            out[k] = ("write", ln)
    for k, ln in reads.items():
        if _free(k) and k not in out:
            out[k] = ("read", ln)
    return out


def _var_keys(expr: Optional[ast.AST]) -> Set[str]:
    """Dotted keys of every var reference in a const/mutable-vars (or
    fence/wait argument) expression."""
    keys: Set[str] = set()
    if expr is None:
        return keys
    for node in ast.walk(expr):
        if isinstance(node, (ast.Name, ast.Attribute)):
            d = dotted(node)
            if d and d not in _IGNORED_STATES:
                keys.add(d)
    return keys


def _decl_exprs(call: ast.Call) -> List[ast.AST]:
    exprs: List[ast.AST] = [a for a in call.args[1:3] if a is not None]
    for kw in call.keywords:
        if kw.arg in ("const_vars", "mutable_vars"):
            exprs.append(kw.value)
    return exprs


class _Site:
    """One engine push site and what its closure touches."""

    __slots__ = ("fnkey", "cls", "qualname", "relpath", "line", "name",
                 "declared", "touched")

    def __init__(self, fnkey: FuncKey, cls: Optional[Tuple[str, str]],
                 qualname: str, relpath: str, line: int, name: str,
                 declared: Set[str], touched: Dict[str, Tuple[str, int]]):
        self.fnkey = fnkey
        self.cls = cls
        self.qualname = qualname
        self.relpath = relpath
        self.line = line
        self.name = name
        self.declared = declared
        self.touched = touched


class _Facts:
    """Per-host-function events in source-line order."""

    def __init__(self, key: FuncKey, cls_key: Optional[Tuple[str, str]],
                 qualname: str, relpath: str, nested: bool):
        self.key = key
        self.cls_key = cls_key
        self.qualname = qualname
        self.relpath = relpath
        self.nested = nested
        self.pushes: List[_Site] = []
        self.sync_lines: List[int] = []
        self.reads: List[Tuple[int, str]] = []        # (line, state key)
        self.deletes: List[Tuple[int, str]] = []      # (line, var key)
        self.var_uses: List[Tuple[int, str]] = []     # (line, var key)
        self.assign_lines: Dict[str, List[int]] = {}  # name -> lines
        self.params: Set[str] = set()


def _op_name(call: ast.Call) -> str:
    for kw in call.keywords:
        if kw.arg == "name" and isinstance(kw.value, ast.Constant) and \
                isinstance(kw.value.value, str):
            return kw.value.value
    return "op"


class _HostScanner:
    """Walks one function body WITHOUT descending into nested defs or
    lambdas (those run later, on the engine worker) and records pushes,
    sync points, dereferencing reads, deletes, and var uses."""

    def __init__(self, index: _Index, modname: str, facts: _Facts):
        self.ix = index
        self.modname = modname
        self.facts = facts
        self.aliases = index.aliases.get(modname, {})
        self.local_fns: Dict[str, ast.AST] = {}
        self.alias_map: Dict[str, str] = {}

    def scan(self, fn: ast.AST):
        a = getattr(fn, "args", None)
        if a is not None:
            for grp in (a.posonlyargs, a.args, a.kwonlyargs):
                self.facts.params.update(p.arg for p in grp)
            for va in (a.vararg, a.kwarg):
                if va is not None:
                    self.facts.params.add(va.arg)
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn:
                self.local_fns[node.name] = node
            elif isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Lambda) and \
                    len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name):
                self.local_fns[node.targets[0].id] = node.value
        # pass 1: aliases and assignment lines (the walk below is not in
        # source order, and canonicalization needs the full alias map)
        for node in self._walk_host(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.facts.assign_lines.setdefault(
                            t.id, []).append(node.lineno)
                        if isinstance(node.value, ast.Name):
                            self.alias_map[t.id] = node.value.id
        for node in self._walk_host(fn):
            self._visit(node)

    def _walk_host(self, fn: ast.AST):
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            stack.extend(ast.iter_child_nodes(node))

    def _canon(self, key: str) -> str:
        seen: Set[str] = set()
        while key in self.alias_map and key not in seen:
            seen.add(key)
            key = self.alias_map[key]
        return key

    def _is_noise(self, key: str) -> bool:
        """Names that are never host *state*: builtins, imported modules/
        symbols, module functions/classes, local helper defs."""
        if key in _IGNORED_STATES or key in _BUILTIN_NAMES:
            return True
        base = key.split(".")[0]
        if base in self.aliases or base in self.local_fns:
            return True
        return (self.modname, base) in self.ix.mod_funcs or \
            (self.modname, base) in self.ix.classes

    # --- node dispatch ----------------------------------------------------
    def _visit(self, node: ast.AST):
        if isinstance(node, ast.Subscript) and \
                isinstance(node.ctx, ast.Load):
            self._read(_base_key(node), node.lineno)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            self._read(_base_key(node.iter), node.lineno)
        elif isinstance(node, ast.Call):
            self._visit_call(node)

    def _read(self, key: Optional[str], line: int):
        if key and not self._is_noise(key):
            self.facts.reads.append((line, self._canon(key)))

    def _visit_call(self, call: ast.Call):
        f = call.func
        if _is_engine_push(call, self.aliases) is not None:
            self._record_push(call)
            return
        tail = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else None)
        if tail in _SYNC_TAILS:
            self.facts.sync_lines.append(call.lineno)
            if tail in ("wait_for_var", "wait_to_read") and call.args:
                for k in _var_keys(call.args[0]):
                    self.facts.var_uses.append((call.lineno, k))
        elif tail == "fence" and call.args:
            for k in _var_keys(call.args[0]):
                self.facts.var_uses.append((call.lineno, k))
        elif tail == "delete_variable" and call.args:
            key = dotted(call.args[0])
            if key:
                self.facts.deletes.append((call.lineno, key))
        elif tail in _CONTENT_FNS and isinstance(f, ast.Name):
            for a in call.args:
                if isinstance(a, (ast.Name, ast.Attribute, ast.Subscript)):
                    self._read(_base_key(a), call.lineno)
        if isinstance(f, ast.Attribute):
            # method call on state is a dereference of the receiver
            self._read(_base_key(f.value), call.lineno)

    # --- push handling ----------------------------------------------------
    def _record_push(self, call: ast.Call):
        declared = {n for n in _declared_names(call)
                    if n not in _IGNORED_STATES}
        for e in _decl_exprs(call):
            for k in _var_keys(e):
                self.facts.var_uses.append((call.lineno, k))
        touched: Dict[str, Tuple[str, int]] = {}
        closure = self._resolve_closure(call)
        if closure is not None:
            for fn in self._reach(closure):
                for key, (mode, line) in _closure_touches(fn).items():
                    key = self._canon(key)
                    if self._is_noise(key):
                        continue
                    if key in touched and touched[key][0] == "write":
                        continue
                    if key in touched and mode == "read":
                        continue
                    touched[key] = (mode, line)
        self.facts.pushes.append(_Site(
            self.facts.key, self.facts.cls_key, self.facts.qualname,
            self.facts.relpath, call.lineno, _op_name(call), declared,
            touched))

    def _resolve_closure(self, call: ast.Call) -> Optional[ast.AST]:
        if not call.args:
            return None
        fn = call.args[0]
        if isinstance(fn, ast.Lambda):
            return fn
        if isinstance(fn, ast.Name):
            hit = self.local_fns.get(fn.id)
            if hit is not None:
                return hit
            mf = self.ix.mod_funcs.get((self.modname, fn.id))
            if mf is not None:
                return mf
        if isinstance(fn, ast.Attribute) and \
                isinstance(fn.value, ast.Name) and fn.value.id == "self" \
                and self.facts.cls_key is not None:
            hit = self.ix.lookup_method(self.facts.cls_key, fn.attr)
            if hit is not None:
                return hit[1]
        return None

    def _reach(self, closure: ast.AST) -> List[ast.AST]:
        """The closure plus helpers it calls, one level deep."""
        out = [closure]
        for node in ast.walk(closure):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            target: Optional[ast.AST] = None
            if isinstance(f, ast.Name):
                target = self.local_fns.get(f.id) or \
                    self.ix.mod_funcs.get((self.modname, f.id))
            elif isinstance(f, ast.Attribute) and \
                    isinstance(f.value, ast.Name) and f.value.id == "self" \
                    and self.facts.cls_key is not None:
                hit = self.ix.lookup_method(self.facts.cls_key, f.attr)
                if hit is not None:
                    target = hit[1]
            if target is not None and target is not closure and \
                    target not in out:
                out.append(target)
        return out


def _same_object(index: _Index, caller: FuncKey, callee: FuncKey) -> bool:
    """``self.X`` facts flow from callee to caller only when the call is
    a method call on the same instance (``self.m()``): the callee must be
    what ``lookup_method`` finds on the caller's own class."""
    if caller[1] is None or callee[1] is None:
        return False
    hit = index.lookup_method((caller[0], caller[1]), callee[2])
    return hit is not None and hit[0] == (callee[0], callee[1])


def check(modules: Sequence[SourceModule]) -> List[Finding]:
    index = _Index(modules)
    summaries = _collect_summaries(index)
    facts: Dict[FuncKey, _Facts] = {}

    def scan(fn: ast.AST, key: FuncKey, cls_key, modname: str,
             relpath: str, nested: bool):
        qual = "%s:%s" % (key[0], ("%s.%s" % (key[1], key[2]))
                          if key[1] else key[2])
        fx = _Facts(key, cls_key, qual, relpath, nested)
        sc = _HostScanner(index, modname, fx)
        sc.scan(fn)
        facts[key] = fx
        # nested defs contribute sync facts (matching lockorder's nested
        # summary keys) but are never themselves host functions: their
        # reads happen on the engine worker
        for name, sub in sc.local_fns.items():
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nkey = (key[0], key[1], "%s.%s" % (key[2], name))
                if nkey not in facts:
                    scan(sub, nkey, cls_key, modname, relpath, True)

    for (mod, name), fn in sorted(index.mod_funcs.items()):
        scan(fn, (mod, None, name), None, mod, index.relpath[mod], False)
    for (mod, cname), ci in sorted(index.classes.items()):
        for mname, fn in sorted(ci.methods.items()):
            scan(fn, (mod, cname, mname), (mod, cname), mod,
                 index.relpath[mod], False)

    # --- interprocedural fixpoint: may-sync and may-push-writes ----------
    may_sync: Dict[FuncKey, bool] = {
        k: bool(f.sync_lines) for k, f in facts.items()}
    may_push_writes: Dict[FuncKey, Set[str]] = {}
    for k, f in facts.items():
        writes: Set[str] = set()
        if not f.nested:
            for site in f.pushes:
                writes |= {s for s, (m, _) in site.touched.items()
                           if m == "write"}
        may_push_writes[k] = writes
    changed = True
    while changed:
        changed = False
        for k, s in summaries.items():
            for _, callee, _ in s.calls:
                if may_sync.get(callee) and not may_sync.get(k, False):
                    may_sync[k] = True
                    changed = True
                add = may_push_writes.get(callee)
                if add and k in may_push_writes:
                    filt = {st for st in add if st.startswith("self.")
                            and _same_object(index, k, callee)}
                    if not filt <= may_push_writes[k]:
                        may_push_writes[k] |= filt
                        changed = True

    findings: List[Finding] = []

    # --- rule: undeclared-var-access (cross-site, per module) ------------
    sites_by_mod: Dict[str, List[_Site]] = {}
    for f in facts.values():
        if f.nested:
            continue
        for site in f.pushes:
            sites_by_mod.setdefault(site.fnkey[0], []).append(site)
    for mod in sorted(sites_by_mod):
        sites = sorted(sites_by_mod[mod], key=lambda s: s.line)
        for i, s1 in enumerate(sites):
            for s2 in sites[i + 1:]:
                shared = sorted(
                    st for st in s1.touched
                    if st in s2.touched
                    and (s1.touched[st][0] == "write"
                         or s2.touched[st][0] == "write"))
                if not shared or (s1.declared & s2.declared):
                    continue
                states = [st for st in shared
                          if not (st.startswith("self.")
                                  and s1.cls != s2.cls)]
                if s1.fnkey != s2.fnkey:
                    # a bare name that is a local or parameter of either
                    # host function is function-scoped state: the two
                    # sites hold DIFFERENT objects, not a shared race
                    def _fn_scoped(st: str) -> bool:
                        base = st.split(".")[0]
                        if base == "self":
                            return False
                        for fk in (s1.fnkey, s2.fnkey):
                            fx = facts[fk]
                            if base in fx.assign_lines or base in fx.params:
                                return True
                        return False
                    states = [st for st in states if not _fn_scoped(st)]
                if not states:
                    continue
                if s1.fnkey == s2.fnkey:
                    lo, hi = sorted((s1.line, s2.line))
                    fx = facts[s1.fnkey]
                    if any(lo < ls < hi for ls in fx.sync_lines):
                        continue  # fence-ordered pair
                findings.append(Finding(
                    "racecheck", "undeclared-var-access", s2.relpath,
                    s2.line, s2.qualname,
                    "%s~%s" % (",".join(states), s1.qualname),
                    "pushed op '%s' touches %s, also written by op '%s' "
                    "pushed at %s:%d (%s), but the two sites share no "
                    "declared var — the engine cannot order them "
                    "(undeclared WW/RW race)" %
                    (s2.name, ",".join(states), s1.name, s1.relpath,
                     s1.line, s1.qualname)))

    for f in facts.values():
        if f.nested:
            continue
        s = summaries.get(f.key)
        calls = s.calls if s is not None else []

        # --- rule: unfenced-host-read --------------------------------
        push_events: List[Tuple[int, Set[str]]] = []
        for site in f.pushes:
            w = {st for st, (m, _) in site.touched.items() if m == "write"}
            if w:
                push_events.append((site.line, w))
        for _, callee, line in calls:
            w = may_push_writes.get(callee)
            if w:
                filt = {st for st in w if st.startswith("self.")
                        and _same_object(index, f.key, callee)}
                if filt:
                    push_events.append((line, filt))
        sync_events = sorted(set(f.sync_lines) | {
            line for _, callee, line in calls if may_sync.get(callee)})
        flagged: Set[str] = set()
        for lr, state in sorted(f.reads):
            if state in flagged:
                continue
            lps = [lp for lp, ws in push_events if state in ws and lp < lr]
            if not lps:
                continue
            lp = max(lps)
            if any(lp < ls <= lr for ls in sync_events):
                continue
            flagged.add(state)
            findings.append(Finding(
                "racecheck", "unfenced-host-read", f.relpath, lr,
                f.qualname, state,
                "host read of '%s' at line %d races the op pushed at "
                "line %d that writes it — no engine.fence(vars).wait() / "
                "wait_to_read on the path between push and read" %
                (state, lr, lp)))

        # --- rule: var-use-after-delete ------------------------------
        seen_del: Set[str] = set()
        for ld, key in sorted(f.deletes):
            if key in seen_del:
                continue
            base = key.split(".")[0]
            resets = [la for la in f.assign_lines.get(base, []) if la > ld]
            uses = sorted(
                [(lu, k) for lu, k in f.var_uses if k == key and lu > ld] +
                [(lu, k) for lu, k in f.deletes if k == key and lu > ld])
            for lu, _k in uses:
                if any(ld < la <= lu for la in resets):
                    continue
                seen_del.add(key)
                findings.append(Finding(
                    "racecheck", "var-use-after-delete", f.relpath, lu,
                    f.qualname, key,
                    "engine var '%s' used at line %d after "
                    "delete_variable at line %d with no rebinding in "
                    "between — the engine has already dropped its "
                    "dependency record" % (key, lu, ld)))
                break
    return findings
