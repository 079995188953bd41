"""Lock-order analyzer.

Builds a global lock-acquisition graph over the package: every
``threading.Lock/RLock/Condition`` assigned to a module global or a
``self.<attr>`` is a node; acquiring B (``with``-block or ``acquire()``)
while holding A is an edge A -> B, including edges discovered
*interprocedurally* (holding A and calling a function that may acquire B).
Findings:

- ``lock-cycle``          the edge graph has a cycle (the ABBA shape)
- ``lock-hierarchy``      an edge contradicts the declared hierarchy
                          (:data:`LOCK_HIERARCHY`): acquiring a lower- or
                          equal-ranked lock while holding a higher one.
                          Equal ranks declare PEER locks — no nesting in
                          either direction (the serving former/metrics
                          contract from PR 2).
- ``callback-under-lock`` a value called while a lock/condition is held
                          resolves to *user-supplied code* (a callable
                          attribute, parameter, or local non-def), directly
                          or through callees — the exact shape of both PR 2
                          serving deadlocks.
- ``lock-self-deadlock``  re-acquiring a held non-reentrant Lock/Condition
                          (directly or through a callee)
- ``lock-group-multi-acquire``  acquiring members of a lock *group* (a
                          list of locks under one attribute) in a loop —
                          safe only under a total order; must be justified
                          in the baseline.

Resolution is deliberately conservative: ``self.x.m()`` only creates call
edges when ``x``'s class is known (ctor assignment, parameter annotation,
or the assigning method's return annotation); unknown receivers create no
edges and no findings, keeping false positives near zero at the cost of
missing exotic aliasing.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import Finding, SourceModule, dotted, import_aliases, unparse

LOCK_CTORS = {
    "threading.Lock": "lock", "threading.RLock": "rlock",
    "threading.Condition": "cond",
    "Lock": "lock", "RLock": "rlock", "Condition": "cond",
}
#: methods on a lock object that are lock protocol, not user callbacks
LOCK_METHODS = {"acquire", "release", "wait", "wait_for", "notify",
                "notify_all", "locked", "__enter__", "__exit__"}

#: Declared lock hierarchy for the package (docs/concurrency.md). Ids are
#: package-root-relative (``mxnet_tpu.`` prefix is stripped before lookup).
#: Acquiring B while holding A requires rank(B) > rank(A); EQUAL ranks
#: declare peer locks that must never nest in either direction; rank 100
#: marks leaf locks (nothing ranked may be acquired under them).
LOCK_HIERARCHY: Dict[str, int] = {
    # engine: the file-write table may create engine vars (engine singleton
    # lock) while holding _file_lock; never the reverse.
    "engine._file_lock": 10,
    "engine._engine_lock": 20,
    "engine.NativeEngine._pending_lock": 100,
    # in-flight gauge table: leaf — the begin/end hooks run inside engine
    # worker callbacks and must never wait on anything ranked.
    "engine._inflight_lock": 100,
    # happens-before sanitizer shadow tables: leaf — epoch/guard bookkeeping
    # only; report logging and the telemetry counter inc happen after release.
    "engine._san_lock": 100,
    # serving: former condition and metrics lock are PEERS — the PR 2 ABBA
    # contract: neither side calls into the other under its own lock.
    "serving.batcher.BatchFormer._cond": 50,
    "serving.metrics.ServingMetrics._lock": 50,
    "serving.bucket_cache.BucketCache._lock": 100,
    # staging pool buffer table: leaf — fill()/retain() touch only numpy
    # buffers under it.
    "serving.staging.StagingPool._lock": 100,
    # decode scheduler condition: same stratum as the former — engine
    # pushes/fences (rank 20) NEVER happen under it; stream/kv leaf locks
    # may be taken under it.
    "serving.generate.scheduler.DecodeScheduler._cond": 50,
    # decode leaves: slot bookkeeping and per-stream token delivery only.
    "serving.generate.kv_cache.KVCacheManager._lock": 100,
    # paged block-table lock: leaf — block/refcount/prefix-registry
    # bookkeeping only; engine pushes, device calls, and telemetry all
    # happen outside the hold.
    "serving.generate.paged.PagedKVCacheManager._lock": 100,
    "serving.generate.stream.TokenStream._cond": 100,
    # HTTP admission gate: leaf — in-flight counter + draining flag only;
    # the queue-depth policy reads (former._cond, rank 50) happen strictly
    # outside the hold.
    "serving.frontend.admission.AdmissionController._lock": 100,
    # frontend stop() one-shot guard: leaf — a single flag flip under it.
    "serving.frontend.server.HttpFrontend._stop_once": 100,
    # predictor run path: leaf — forward() holds it across the compiled
    # call but never acquires anything ranked inside.
    "predict.Predictor._run_lock": 100,
    # kvstore PS client: per-address data locks and the control-channel
    # lock are peers — liveness RPCs must work while data RPCs block.
    "kvstore_server.PSClient._locks[*]": 60,
    "kvstore_server.PSClient._ctrl_lock": 60,
    "kvstore.PSKVStore._errs_lock": 100,
    # fault-injection plan table: leaf — match/fire bookkeeping only; the
    # telemetry counter inc happens after release (docs/fault_tolerance.md).
    "resilience.faults._lock": 100,
    # persistent program cache: leaf — guards manifest read-modify-write
    # and the session stat dict only; executable serialization, entry
    # commits, and telemetry increments happen outside holds of it.
    "progcache._lock": 100,
    # compile witness record tables: leaf — dict bookkeeping only; the
    # telemetry counter increments happen after release. May nest under
    # other leaves (BucketCache._lock builds programs under its hold) —
    # safe because nothing is ever acquired under THIS lock.
    "analysis.compile_witness._lock": 100,
    "torch._TH_LOCK": 90,
    "io.DevicePrefetchIter._lock": 100,
    "random._lock": 100,
    "filesystem._MEMORY_LOCK": 100,
}

FuncKey = Tuple[str, Optional[str], str]  # (module, class|None, func)


def _ctor_kind(call: ast.AST, aliases: Dict[str, str]) -> Optional[str]:
    """'lock'/'rlock'/'cond' if ``call`` constructs a threading lock."""
    if not isinstance(call, ast.Call):
        return None
    d = dotted(call.func)
    if d is None:
        return None
    if d in LOCK_CTORS:
        # bare names must come from threading (import-aware)
        if "." not in d and aliases.get(d, "") != "threading.%s" % d:
            return None
        return LOCK_CTORS[d]
    return None


def _group_kind(value: ast.AST, aliases) -> Optional[str]:
    """Lock kind if ``value`` is a list/comprehension of lock ctors."""
    if isinstance(value, ast.ListComp):
        return _ctor_kind(value.elt, aliases)
    if isinstance(value, (ast.List, ast.Tuple)) and value.elts:
        kinds = {_ctor_kind(e, aliases) for e in value.elts}
        if len(kinds) == 1 and None not in kinds:
            return kinds.pop()
    return None


class _ClassInfo:
    def __init__(self, modname: str, name: str):
        self.modname = modname
        self.name = name
        self.bases: List[str] = []          # dotted base exprs
        self.methods: Dict[str, ast.FunctionDef] = {}
        self.lock_attrs: Dict[str, Tuple[str, str]] = {}  # attr -> (id, kind)
        self.attr_types: Dict[str, Tuple[str, str]] = {}  # attr -> class key


class _Index:
    """Package-wide symbol index built before summarization."""

    def __init__(self, modules: Sequence[SourceModule]):
        self.modules = modules
        self.aliases: Dict[str, Dict[str, str]] = {}
        self.classes: Dict[Tuple[str, str], _ClassInfo] = {}
        self.class_by_name: Dict[str, List[Tuple[str, str]]] = {}
        self.mod_funcs: Dict[Tuple[str, str], ast.FunctionDef] = {}
        self.mod_locks: Dict[str, Dict[str, Tuple[str, str]]] = {}
        self.lock_kinds: Dict[str, str] = {}  # lock id -> kind
        self.relpath: Dict[str, str] = {}     # modname -> relpath
        for m in modules:
            self._index_module(m)
        self._resolve_attr_types()

    def _index_module(self, m: SourceModule):
        al = import_aliases(m.tree)
        self.aliases[m.modname] = al
        self.relpath[m.modname] = m.relpath
        self.mod_locks[m.modname] = {}
        for node in m.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.mod_funcs[(m.modname, node.name)] = node
            elif isinstance(node, ast.ClassDef):
                ci = _ClassInfo(m.modname, node.name)
                ci.bases = [dotted(b) or "" for b in node.bases]
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        ci.methods[sub.name] = sub
                self.classes[(m.modname, node.name)] = ci
                self.class_by_name.setdefault(node.name, []).append(
                    (m.modname, node.name))
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        kind = _ctor_kind(node.value, al)
                        gkind = _group_kind(node.value, al)
                        if kind:
                            lid = "%s.%s" % (m.modname, t.id)
                            self.mod_locks[m.modname][t.id] = (lid, kind)
                            self.lock_kinds[lid] = kind
                        elif gkind:
                            lid = "%s.%s[*]" % (m.modname, t.id)
                            self.mod_locks[m.modname][t.id] = (lid, "group")
                            self.lock_kinds[lid] = "group"
        # second pass: self.<attr> assignments inside methods
        for (mod, cname), ci in list(self.classes.items()):
            if mod != m.modname:
                continue
            for meth in ci.methods.values():
                self._index_self_attrs(m, ci, meth)

    def _index_self_attrs(self, m: SourceModule, ci: _ClassInfo,
                          meth: ast.FunctionDef):
        al = self.aliases[m.modname]
        ann: Dict[str, ast.AST] = {
            a.arg: a.annotation for a in meth.args.args if a.annotation}
        for node in ast.walk(meth):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            t = node.targets[0]
            if not (isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"):
                continue
            kind = _ctor_kind(node.value, al)
            gkind = _group_kind(node.value, al)
            if kind:
                lid = "%s.%s.%s" % (ci.modname, ci.name, t.attr)
                ci.lock_attrs[t.attr] = (lid, kind)
                self.lock_kinds[lid] = kind
            elif gkind:
                lid = "%s.%s.%s[*]" % (ci.modname, ci.name, t.attr)
                ci.lock_attrs[t.attr] = (lid, "group")
                self.lock_kinds[lid] = "group"
            else:
                # remember the raw value for attr typing (resolved later,
                # once every class is indexed)
                ci.attr_types.setdefault(
                    t.attr, ("__raw__", (node.value, ann, ci)))  # type: ignore

    # --- class/type resolution -------------------------------------------
    def resolve_class(self, modname: str, ref) -> Optional[Tuple[str, str]]:
        """Resolve a class reference (dotted string or annotation AST) to a
        class key, searching the defining module, import aliases, then a
        package-unique bare name."""
        if ref is None:
            return None
        if isinstance(ref, ast.AST):
            if isinstance(ref, ast.Constant) and isinstance(ref.value, str):
                ref = ref.value
            else:
                ref = dotted(ref)
        if not isinstance(ref, str) or not ref:
            return None
        ref = ref.strip("'\"")
        name = ref.split(".")[-1]
        if (modname, name) in self.classes and ref == name:
            return (modname, name)
        al = self.aliases.get(modname, {})
        target = al.get(ref.split(".")[0])
        if target is not None:
            cands = self.class_by_name.get(name, [])
            for key in cands:
                if key[0].endswith(target.split(".")[0]) or \
                        target.endswith(key[0].split(".")[-1]):
                    return key
        cands = self.class_by_name.get(name, [])
        if len(cands) == 1:
            return cands[0]
        return None

    def _resolve_attr_types(self):
        for ci in self.classes.values():
            resolved: Dict[str, Tuple[str, str]] = {}
            for attr, val in ci.attr_types.items():
                if not (isinstance(val, tuple) and val[0] == "__raw__"):
                    continue
                value, ann, _ = val[1]
                key = None
                if isinstance(value, ast.Call):
                    d = dotted(value.func)
                    if d is not None:
                        # self.x = self._make()  ->  return annotation
                        if d.startswith("self."):
                            meth = self.lookup_method(
                                (ci.modname, ci.name), d.split(".", 1)[1])
                            if meth is not None and meth[1].returns \
                                    is not None:
                                key = self.resolve_class(
                                    meth[0][0], meth[1].returns)
                        else:
                            key = self.resolve_class(ci.modname, d)
                elif isinstance(value, ast.Name):
                    if value.id in ann:  # self.x = param  (annotated)
                        key = self.resolve_class(ci.modname, ann[value.id])
                    else:
                        # self.x = module_alias  (e.g. self._engine = engine)
                        al = self.aliases.get(ci.modname, {})
                        tgt = al.get(value.id)
                        if tgt is not None and tgt in self.relpath:
                            key = (tgt, None)  # module, not class
                if key is not None:
                    resolved[attr] = key
            ci.attr_types = resolved

    def lookup_method(self, cls_key: Tuple[str, str], name: str,
                      _seen=None) -> Optional[Tuple[Tuple[str, str],
                                                    ast.FunctionDef]]:
        """Find ``name`` on the class or its package bases (class key of
        the DEFINING class is returned)."""
        _seen = _seen or set()
        if cls_key in _seen or cls_key not in self.classes:
            return None
        _seen.add(cls_key)
        ci = self.classes[cls_key]
        if name in ci.methods:
            return cls_key, ci.methods[name]
        for b in ci.bases:
            bkey = self.resolve_class(ci.modname, b)
            if bkey is not None:
                hit = self.lookup_method(bkey, name, _seen)
                if hit is not None:
                    return hit
        return None

    def lookup_lock_attr(self, cls_key: Tuple[str, str], attr: str,
                         _seen=None) -> Optional[Tuple[str, str]]:
        _seen = _seen or set()
        if cls_key in _seen or cls_key not in self.classes:
            return None
        _seen.add(cls_key)
        ci = self.classes[cls_key]
        if attr in ci.lock_attrs:
            return ci.lock_attrs[attr]
        for b in ci.bases:
            bkey = self.resolve_class(ci.modname, b)
            if bkey is not None:
                hit = self.lookup_lock_attr(bkey, attr, _seen)
                if hit is not None:
                    return hit
        return None


class _Summary:
    """Per-function facts feeding the interprocedural fixpoint."""

    def __init__(self, key: FuncKey, relpath: str):
        self.key = key
        self.relpath = relpath
        self.direct_acquires: Set[str] = set()
        # (held frozenset, callee key, line)
        self.calls: List[Tuple[frozenset, FuncKey, int]] = []
        # (held frozenset, callback desc, line)
        self.callbacks: List[Tuple[frozenset, str, int]] = []
        # (src, dst, line)
        self.nest_edges: List[Tuple[str, str, int]] = []
        self.reacquires: List[Tuple[str, int]] = []
        self.group_loop_acquires: List[Tuple[str, int]] = []

    @property
    def qualname(self) -> str:
        mod, cls, fn = self.key
        return "%s:%s" % (mod, ("%s.%s" % (cls, fn)) if cls else fn)


class _FnScanner:
    """Linear scan of one function body tracking the held-lock stack."""

    def __init__(self, index: _Index, summary: _Summary,
                 cls_key: Optional[Tuple[str, str]], modname: str):
        self.ix = index
        self.s = summary
        self.cls_key = cls_key
        self.modname = modname
        self.held: List[str] = []
        self.loop_depth = 0
        self.params: Set[str] = set()
        self.local_defs: Dict[str, FuncKey] = {}
        self.local_types: Dict[str, Tuple[str, str]] = {}
        self.assigned: Set[str] = set()

    # --- lock expression resolution --------------------------------------
    def resolve_lock(self, node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Name):
            hit = self.ix.mod_locks.get(self.modname, {}).get(node.id)
            return hit[0] if hit else None
        if isinstance(node, ast.Subscript):
            return self.resolve_lock(node.value)
        if isinstance(node, ast.Attribute):
            if isinstance(node.value, ast.Name) and node.value.id == "self" \
                    and self.cls_key is not None:
                hit = self.ix.lookup_lock_attr(self.cls_key, node.attr)
                if hit:
                    return hit[0]
                return None
            # obj.attr where obj's class is known
            ckey = self._type_of(node.value)
            if ckey is not None and ckey[1] is not None:
                hit = self.ix.lookup_lock_attr(ckey, node.attr)
                if hit:
                    return hit[0]
        return None

    def _type_of(self, node: ast.AST) -> Optional[Tuple[str, str]]:
        """Class (or (module, None)) of an expression, where inferable."""
        if isinstance(node, ast.Name):
            if node.id in self.local_types:
                return self.local_types[node.id]
            al = self.ix.aliases.get(self.modname, {})
            tgt = al.get(node.id)
            if tgt is not None and tgt in self.ix.relpath:
                return (tgt, None)
            return None
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and node.value.id == "self" \
                and self.cls_key is not None:
            ci = self.ix.classes.get(self.cls_key)
            if ci is not None:
                return ci.attr_types.get(node.attr)
        return None

    # --- held-state events ------------------------------------------------
    def on_acquire(self, lid: str, line: int, via_with: bool):
        kind = self.ix.lock_kinds.get(lid, "lock")
        if lid in self.held:
            if kind == "group":
                self.s.group_loop_acquires.append((lid, line))
            elif kind != "rlock":
                self.s.reacquires.append((lid, line))
        elif kind == "group" and self.loop_depth > 0 and not via_with:
            self.s.group_loop_acquires.append((lid, line))
        for h in self.held:
            if h != lid:
                self.s.nest_edges.append((h, lid, line))
        self.s.direct_acquires.add(lid)
        self.held.append(lid)

    def on_release(self, lid: str):
        if lid in self.held:
            self.held.reverse()
            self.held.remove(lid)
            self.held.reverse()

    # --- statements -------------------------------------------------------
    def scan_function(self, fn: ast.FunctionDef):
        args = fn.args
        for a in (args.posonlyargs + args.args + args.kwonlyargs):
            self.params.add(a.arg)
        if args.vararg:
            self.params.add(args.vararg.arg)
        if args.kwarg:
            self.params.add(args.kwarg.arg)
        for node in ast.walk(fn):
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.For,
                                 ast.withitem, ast.AnnAssign)):
                tgt = getattr(node, "targets", None) or \
                    [getattr(node, "target", None) or
                     getattr(node, "optional_vars", None)]
                for t in tgt:
                    if isinstance(t, ast.Name):
                        self.assigned.add(t.id)
                    elif isinstance(t, ast.Tuple):
                        for e in t.elts:
                            if isinstance(e, ast.Name):
                                self.assigned.add(e.id)
        # local var types from annotated/ctor assignments
        for node in fn.body:
            self._maybe_local_type(node)
        self.scan_block(fn.body)

    def _maybe_local_type(self, node):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                isinstance(node.value, ast.Call):
            d = dotted(node.value.func)
            key = self.ix.resolve_class(self.modname, d) if d else None
            if key is not None:
                self.local_types[node.targets[0].id] = key
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name):
            key = self.ix.resolve_class(self.modname, node.annotation)
            if key is not None:
                self.local_types[node.target.id] = key

    def scan_block(self, stmts: Sequence[ast.stmt]):
        for st in stmts:
            self.scan_stmt(st)

    def scan_stmt(self, st: ast.stmt):
        if isinstance(st, (ast.With, ast.AsyncWith)):
            acquired = []
            for item in st.items:
                lid = self.resolve_lock(item.context_expr)
                if lid is not None:
                    self.on_acquire(lid, st.lineno, via_with=True)
                    acquired.append(lid)
                else:
                    self.scan_expr(item.context_expr)
            self.scan_block(st.body)
            for lid in reversed(acquired):
                self.on_release(lid)
        elif isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            mod, cls, fn = self.s.key
            self.local_defs[st.name] = (mod, cls, "%s.%s" % (fn, st.name))
        elif isinstance(st, (ast.For, ast.AsyncFor, ast.While)):
            for field in ("iter", "test"):
                val = getattr(st, field, None)
                if val is not None:
                    self.scan_expr(val)
            self.loop_depth += 1
            self.scan_block(st.body)
            self.scan_block(st.orelse)
            self.loop_depth -= 1
        elif isinstance(st, ast.If):
            self.scan_expr(st.test)
            self.scan_block(st.body)
            self.scan_block(st.orelse)
        elif isinstance(st, ast.Try):
            self.scan_block(st.body)
            for h in st.handlers:
                self.scan_block(h.body)
            self.scan_block(st.orelse)
            self.scan_block(st.finalbody)
        elif isinstance(st, ast.ClassDef):
            pass  # nested classes: out of scope
        else:
            for child in ast.iter_child_nodes(st):
                if isinstance(child, ast.expr):
                    self.scan_expr(child)

    # --- expressions ------------------------------------------------------
    def scan_expr(self, expr: ast.AST):
        """Find calls, skipping lambda/def bodies (they run later, not
        under the current held set)."""
        for node in self._walk_expr(expr):
            if isinstance(node, ast.Call):
                self.handle_call(node)

    def _walk_expr(self, expr):
        stack = [expr]
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.Lambda, ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                continue
            stack.extend(ast.iter_child_nodes(node))

    def handle_call(self, call: ast.Call):
        f = call.func
        held = frozenset(self.held)
        line = call.lineno
        if isinstance(f, ast.Attribute):
            lid = self.resolve_lock(f.value)
            if lid is not None and f.attr in LOCK_METHODS:
                if f.attr == "acquire":
                    self.on_acquire(lid, line, via_with=False)
                elif f.attr == "release":
                    self.on_release(lid)
                return
            if isinstance(f.value, ast.Name) and f.value.id == "self" and \
                    self.cls_key is not None:
                hit = self.ix.lookup_method(self.cls_key, f.attr)
                if hit is not None:
                    dkey, _ = hit
                    self.s.calls.append(
                        (held, (dkey[0], dkey[1], f.attr), line))
                    return
                ci = self.ix.classes.get(self.cls_key)
                tkey = ci.attr_types.get(f.attr) if ci else None
                if tkey is not None and tkey[1] is not None:
                    # callable class instance: route to __call__
                    hit = self.ix.lookup_method(tkey, "__call__")
                    if hit is not None:
                        self.s.calls.append(
                            (held, (tkey[0], tkey[1], "__call__"), line))
                        return
                # unresolvable callable attribute: user-supplied callback —
                # unless the class has an external (unresolvable) base, in
                # which case the attr may be an inherited library method
                # (e.g. BytesIO.getvalue) and flagging it would be noise.
                # Recorded even with nothing held: a CALLER holding a lock
                # inherits this via may_callback (the _fail/_error_hook
                # shape); direct findings are emitted only for held != {}.
                if ci is not None and all(
                        self.ix.resolve_class(self.modname, b) is not None
                        for b in ci.bases if b and b != "object"):
                    self.s.callbacks.append(
                        (held, "self.%s" % f.attr, line))
                return
            tkey = self._type_of(f.value)
            if tkey is not None:
                if tkey[1] is None:  # module reference
                    fn = self.ix.mod_funcs.get((tkey[0], f.attr))
                    if fn is not None:
                        self.s.calls.append(
                            (held, (tkey[0], None, f.attr), line))
                    return
                hit = self.ix.lookup_method(tkey, f.attr)
                if hit is not None:
                    dkey, _ = hit
                    self.s.calls.append(
                        (held, (dkey[0], dkey[1], f.attr), line))
                return
            # module-alias function call: engine.push(...)
            d = dotted(f)
            if d is not None and "." in d:
                head, rest = d.split(".", 1)
                al = self.ix.aliases.get(self.modname, {})
                tgt = al.get(head)
                if tgt is not None and tgt in self.ix.relpath and \
                        "." not in rest:
                    if (tgt, rest) in self.ix.mod_funcs:
                        self.s.calls.append((held, (tgt, None, rest), line))
            return
        if isinstance(f, ast.Name):
            if f.id in self.local_defs:
                self.s.calls.append((held, self.local_defs[f.id], line))
                return
            if (self.modname, f.id) in self.ix.mod_funcs:
                self.s.calls.append((held, (self.modname, None, f.id), line))
                return
            ckey = self.ix.resolve_class(self.modname, f.id)
            al = self.ix.aliases.get(self.modname, {})
            if ckey is not None and (f.id in al or
                                     (self.modname, f.id) in self.ix.classes):
                init = self.ix.lookup_method(ckey, "__init__")
                if init is not None:
                    dkey, _ = init
                    self.s.calls.append(
                        (held, (dkey[0], dkey[1], "__init__"), line))
                return
            if f.id in self.params or (f.id in self.assigned and
                                       f.id not in self.local_defs):
                # calling a parameter / untyped local: user-supplied code
                self.s.callbacks.append((held, f.id, line))


def _collect_summaries(index: _Index) -> Dict[FuncKey, _Summary]:
    summaries: Dict[FuncKey, _Summary] = {}

    def scan(fn: ast.FunctionDef, key: FuncKey,
             cls_key: Optional[Tuple[str, str]], modname: str,
             relpath: str):
        s = _Summary(key, relpath)
        sc = _FnScanner(index, s, cls_key, modname)
        sc.scan_function(fn)
        summaries[key] = s
        # nested defs become their own summaries (executed later — fresh
        # held state), reachable through local_defs call edges
        for st in ast.walk(fn):
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and st is not fn and st.name in sc.local_defs:
                nkey = sc.local_defs[st.name]
                if nkey not in summaries:
                    scan(st, nkey, cls_key, modname, relpath)

    for (mod, name), fn in list(index.mod_funcs.items()):
        scan(fn, (mod, None, name), None, mod, index.relpath[mod])
    for (mod, cname), ci in list(index.classes.items()):
        for mname, fn in ci.methods.items():
            scan(fn, (mod, cname, mname), (mod, cname), mod,
                 index.relpath[mod])
    return summaries


def _fixpoint(summaries: Dict[FuncKey, _Summary]):
    may_acquire: Dict[FuncKey, Set[str]] = {
        k: set(s.direct_acquires) for k, s in summaries.items()}
    may_callback: Dict[FuncKey, Set[str]] = {
        k: {d for _, d, _ in s.callbacks} for k, s in summaries.items()}
    changed = True
    while changed:
        changed = False
        for k, s in summaries.items():
            for _, callee, _ in s.calls:
                if callee not in summaries:
                    continue
                if not may_acquire[callee] <= may_acquire[k]:
                    may_acquire[k] |= may_acquire[callee]
                    changed = True
                if not may_callback[callee] <= may_callback[k]:
                    may_callback[k] |= may_callback[callee]
                    changed = True
    return may_acquire, may_callback


def _norm(lock_id: str) -> str:
    return lock_id[len("mxnet_tpu."):] if lock_id.startswith("mxnet_tpu.") \
        else lock_id


def _find_cycles(edges: Dict[str, Set[str]]) -> List[List[str]]:
    """Strongly connected components with >1 node (Tarjan, iterative)."""
    idx, low, on, order, stack = {}, {}, set(), [], []
    sccs, counter = [], [0]

    def strongconnect(v):
        work = [(v, iter(sorted(edges.get(v, ()))))]
        idx[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in idx:
                    idx[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on.add(w)
                    work.append((w, iter(sorted(edges.get(w, ())))))
                    advanced = True
                    break
                elif w in on:
                    low[node] = min(low[node], idx[w])
            if advanced:
                continue
            work.pop()
            if work:
                low[work[-1][0]] = min(low[work[-1][0]], low[node])
            if low[node] == idx[node]:
                scc = []
                while True:
                    w = stack.pop()
                    on.discard(w)
                    scc.append(w)
                    if w == node:
                        break
                if len(scc) > 1:
                    sccs.append(sorted(scc))

    nodes = set(edges)
    for ds in edges.values():
        nodes |= ds
    for v in sorted(nodes):
        if v not in idx:
            strongconnect(v)
    return sccs


def check(modules: Sequence[SourceModule],
          hierarchy: Optional[Dict[str, int]] = None) -> List[Finding]:
    hierarchy = LOCK_HIERARCHY if hierarchy is None else hierarchy
    index = _Index(modules)
    summaries = _collect_summaries(index)
    may_acquire, may_callback = _fixpoint(summaries)

    findings: List[Finding] = []
    # (src, dst) -> (relpath, line, qualname) of first witness
    edge_where: Dict[Tuple[str, str], Tuple[str, int, str]] = {}

    for k, s in summaries.items():
        for src, dst, line in s.nest_edges:
            edge_where.setdefault((src, dst), (s.relpath, line, s.qualname))
        for held, callee, line in s.calls:
            if callee not in summaries:
                continue
            for h in sorted(held):
                for a in sorted(may_acquire[callee]):
                    if a == h:
                        kind = index.lock_kinds.get(h, "lock")
                        callee_q = summaries[callee].qualname
                        if kind == "group":
                            findings.append(Finding(
                                "lockorder", "lock-group-multi-acquire",
                                s.relpath, line, s.qualname,
                                "%s via %s" % (_norm(h), callee_q),
                                "lock group %s re-acquired through call to "
                                "%s while a member is already held" %
                                (_norm(h), callee_q)))
                        elif kind != "rlock":
                            findings.append(Finding(
                                "lockorder", "lock-self-deadlock",
                                s.relpath, line, s.qualname,
                                "%s via %s" % (_norm(h), callee_q),
                                "%s (non-reentrant) may be re-acquired "
                                "through call to %s while held — "
                                "self-deadlock" % (_norm(h), callee_q)))
                    else:
                        edge_where.setdefault(
                            (h, a), (s.relpath, line, s.qualname))
            if held and may_callback[callee]:
                callee_q = summaries[callee].qualname
                for h in sorted(held):
                    for desc in sorted(may_callback[callee]):
                        findings.append(Finding(
                            "lockorder", "callback-under-lock",
                            s.relpath, line, s.qualname,
                            "%s->%s->%s" % (_norm(h), callee_q, desc),
                            "callback %s (via %s) runs while %s is held — "
                            "arbitrary user code under a lock is the PR 2 "
                            "deadlock shape" %
                            (desc, callee_q, _norm(h))))
        for held, desc, line in s.callbacks:
            for h in sorted(held):
                findings.append(Finding(
                    "lockorder", "callback-under-lock", s.relpath, line,
                    s.qualname, "%s->%s" % (_norm(h), desc),
                    "callback %s invoked while %s is held — arbitrary "
                    "user code under a lock is the PR 2 deadlock shape" %
                    (desc, _norm(h))))
        for lid, line in s.reacquires:
            findings.append(Finding(
                "lockorder", "lock-self-deadlock", s.relpath, line,
                s.qualname, _norm(lid),
                "%s (non-reentrant) acquired while already held" %
                _norm(lid)))
        for lid, line in s.group_loop_acquires:
            findings.append(Finding(
                "lockorder", "lock-group-multi-acquire", s.relpath, line,
                s.qualname, _norm(lid),
                "multiple members of lock group %s acquired without "
                "releasing — correct only under a total acquisition "
                "order; justify in the baseline" % _norm(lid)))

    # hierarchy violations on the witnessed edge set
    for (src, dst), (relpath, line, qual) in sorted(edge_where.items()):
        rs, rd = hierarchy.get(_norm(src)), hierarchy.get(_norm(dst))
        if rs is None or rd is None:
            continue
        if rd < rs:
            findings.append(Finding(
                "lockorder", "lock-hierarchy", relpath, line, qual,
                "%s->%s" % (_norm(src), _norm(dst)),
                "%s (rank %d) acquired while holding %s (rank %d) — "
                "violates the declared hierarchy (docs/concurrency.md)" %
                (_norm(dst), rd, _norm(src), rs)))
        elif rd == rs:
            findings.append(Finding(
                "lockorder", "lock-hierarchy", relpath, line, qual,
                "%s-><-%s" % (_norm(src), _norm(dst)),
                "%s and %s are declared PEER locks (equal rank %d) — they "
                "must never nest (docs/concurrency.md)" %
                (_norm(src), _norm(dst), rs)))

    # global cycles
    graph: Dict[str, Set[str]] = {}
    for (src, dst) in edge_where:
        graph.setdefault(src, set()).add(dst)
    for scc in _find_cycles(graph):
        witnesses = sorted(
            (edge_where[(a, b)] + (a, b))
            for a in scc for b in graph.get(a, ()) if b in scc)
        relpath, line, qual = witnesses[0][:3]
        detail = "; ".join("%s->%s at %s:%d" % (_norm(a), _norm(b), p, ln)
                           for (p, ln, _q, a, b) in witnesses)
        findings.append(Finding(
            "lockorder", "lock-cycle", relpath, line, qual,
            "->".join(_norm(x) for x in scc),
            "lock acquisition cycle (ABBA deadlock): %s" % detail))
    return findings
