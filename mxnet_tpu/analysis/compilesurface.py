"""Compile-surface analyzer — the static half of the bounded-program guard.

The framework's production claim is a *bounded program set*: weights are
program arguments (weight-independent progcache keys), every compile
surface has a declared ladder+k bound, donated buffers are never touched
after the call, and steady state compiles nothing. This checker enforces
the shape of that invariant over the whole tree, pure-``ast`` (nothing is
imported), reusing :mod:`.lockorder`'s package index + per-function call
summaries for the interprocedural caller map. Rules:

- ``weight-as-closure-constant``  a fn traced by ``jax.jit``/``pjit``
  closes over param/weight/aux state instead of taking it as an argument
  — the weights get baked into the executable, so the progcache key must
  hash param BYTES and a warm restart or weight swap recompiles (the
  invariant quant/PR 14 states explicitly: weights ride as arguments).
- ``stray-jit``  a jit call site outside the sanctioned surfaces
  (:data:`SANCTIONED_SURFACES`), interprocedural one helper level deep: a
  helper whose resolvable callers are ALL sanctioned inherits their
  sanction. New surfaces are allowlisted in ``ci/analysis_baseline.json``
  with a written justification — or properly sanctioned + budgeted.
- ``donated-arg-reuse``  a host reference passed at a ``donate_argnums``
  position of a jit-compiled callable and dereferenced later in the same
  block — XLA invalidated that buffer at the call.
- ``undeclared-program-budget``  every sanctioned surface that owns a
  jit site must declare its ladder+k bound in :data:`PROGRAM_BUDGETS`,
  so a new compile surface fails the gate until its bound is written
  down.

The dynamic half is :mod:`.compile_witness`
(``MXNET_COMPILE_WITNESS=1``).
"""
from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import Finding, SourceModule, dotted, import_aliases, unparse
from .lockorder import FuncKey, _Index, _collect_summaries
from .trace_purity import _fn_params, _local_names, _walk_stop_at_defs

#: call tails that trigger an XLA compile surface (shard_map alone does
#: not compile — it surfaces through the jit that wraps it)
_COMPILE_TAILS = {"jit", "pjit"}

#: Sanctioned compile surfaces, matched on dotted-segment boundaries
#: against ``module.Class.func`` ids (so ``DecodePrograms`` covers every
#: method, and ``Executor.make_train_step`` covers the nested
#: ``_run_impl``). A jit site inside one of these — or inside a helper
#: whose resolvable callers are all sanctioned — is legal IF the matched
#: surface declares its bound in :data:`PROGRAM_BUDGETS`.
SANCTIONED_SURFACES: Tuple[str, ...] = (
    "Predictor._compile",
    "QuantizedPredictor._compile",
    "BucketCache",
    "DecodePrograms",
    "PagedDecodePrograms",
    "Executor._get_fwd",
    "Executor._get_fwd_bwd",
    "Executor.make_train_step",
)

#: Declared program budgets: sanctioned surface id -> the ladder+k bound
#: CI gates (docs/static_analysis.md has the rendered table). A
#: sanctioned surface owning a jit site but missing here fails the
#: ``undeclared-program-budget`` rule.
PROGRAM_BUDGETS: Dict[str, str] = {
    "predict.Predictor._compile":
        "1 per bound input signature; serving bounds signatures via the "
        "BucketCache ladder. The traced fn closes over weights BY DESIGN "
        "(baselined) — compensated by a weight-DEPENDENT progcache key "
        "(model_fingerprint hashes param bytes).",
    "quant.QuantizedPredictor._compile":
        "1 per bound input signature — weights/scales are program "
        "arguments, key is weight-independent lowered text.",
    "serving.bucket_cache.BucketCache":
        "len(buckets) programs, ever — one per ladder rung; set_ladder "
        "enforces the program budget on swaps. (Owns no jit site itself; "
        "compiles route through Predictor._compile under its witness "
        "scope.)",
    "serving.generate.programs.DecodePrograms":
        "ladder + 3: one prefill per rung + ONE decode step + ONE admit "
        "(+ ONE spec verify when enabled; the draft step replaces the "
        "vanilla step, keeping spec at ladder + 2 extra).",
    "serving.generate.programs.PagedDecodePrograms":
        "ladder + 2: one paged-prefill per rung (admit folded in) + ONE "
        "paged decode step (+ ONE spec verify when enabled).",
    "executor.Executor._get_fwd":
        "<= 2 (is_train in {False, True}) per executor bind.",
    "executor.Executor._get_fwd_bwd":
        "1 per executor bind.",
    "executor.Executor.make_train_step":
        "1 per (update_fn, chain, avals) — the fused train step; "
        "chain-K folds K sub-steps into the one program.",
}

#: names whose presence as a traced-fn FREE variable means weights are
#: closure constants; attribute loads of these on free receivers too
_WEIGHT_NAME_RE = re.compile(r"(^|_)(param|params|weight|weights|qval|"
                             r"qvals)($|_|s$)")
_WEIGHT_ATTRS = {"params", "_arg_params", "_aux_params", "arg_params",
                 "aux_params", "weights", "_qvals"}


def _weighty_name(name: str) -> bool:
    return bool(_WEIGHT_NAME_RE.search(name)) or name.startswith("aux_")


def _compile_like(node: ast.AST, aliases: Dict[str, str]) -> bool:
    """True for a reference to ``jax.jit``/``pjit`` (import-alias aware)."""
    d = dotted(node)
    if d is None:
        return False
    tail = d.split(".")[-1]
    if tail not in _COMPILE_TAILS:
        return False
    head = d.split(".")[0]
    if "." in d:
        src = aliases.get(head, head)
        return src.split(".")[0] == "jax"
    src = aliases.get(d, "")
    return src.split(".")[0] == "jax" or src.endswith(".%s" % tail)


def _match_surface(cand: str, pattern: str) -> Optional[str]:
    """The surface id (prefix of ``cand`` through ``pattern``) when
    ``pattern`` matches ``cand`` on dotted-segment boundaries."""
    wrapped = "." + cand + "."
    pos = wrapped.find("." + pattern + ".")
    if pos < 0:
        return None
    return cand[:pos + len(pattern)]


def _key_candidate(key: FuncKey) -> str:
    mod, cls, fn = key
    return ".".join(p for p in (mod, cls, fn) if p)


def _surface_of(key: FuncKey) -> Optional[str]:
    cand = _key_candidate(key)
    for p in SANCTIONED_SURFACES:
        s = _match_surface(cand, p)
        if s is not None:
            return s
    return None


def _qualname(key: FuncKey) -> str:
    mod, cls, fn = key
    if not fn:
        return "%s:" % mod
    return "%s:%s" % (mod, ("%s.%s" % (cls, fn)) if cls else fn)


def _functions(tree: ast.Module):
    """Every def in the module as ``(cls_name, dotted_fn_name, node)``,
    nested defs dotted like lockorder's summary keys
    (``make_train_step._run_impl``)."""
    out: List[Tuple[Optional[str], str, ast.AST]] = []

    def rec(node, cls, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                name = prefix + child.name
                out.append((cls, name, child))
                rec(child, cls, name + ".")
            elif isinstance(child, ast.ClassDef):
                rec(child, child.name, "")
            else:
                rec(child, cls, prefix)

    rec(tree, None, "")
    return out


# --- weight-as-closure-constant ----------------------------------------------
def _traced_target(call: ast.Call, local_defs: Dict[str, ast.AST]
                   ) -> Tuple[Optional[ast.AST], str]:
    """(fn ast, display name) for the traced callable of a jit call, when
    it resolves to an inline lambda or a local def."""
    if not call.args:
        return None, ""
    target = call.args[0]
    if isinstance(target, ast.Lambda):
        return target, "<lambda>"
    if isinstance(target, ast.Name) and target.id in local_defs:
        return local_defs[target.id], target.id
    return None, unparse(target)


def _check_weight_closure(mod: SourceModule, qual: str, fn: ast.AST,
                          fn_name: str, line: int,
                          findings: List[Finding]):
    params = _fn_params(fn) if not isinstance(fn, ast.Lambda) \
        else {a.arg for a in fn.args.args}
    local = _local_names(fn)
    body = [fn.body] if isinstance(fn, ast.Lambda) else fn.body
    # a free name used only as a call TARGET is a helper function, not
    # weight state (dequantize_weight(...) is fine; weights(...) is not a
    # shape that occurs)
    call_funcs: Set[int] = set()
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name):
                call_funcs.add(id(node.func))
    flagged: Set[str] = set()
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
                n = node.id
                if n in params or n in local or n in flagged or \
                        id(node) in call_funcs:
                    continue
                if _weighty_name(n):
                    flagged.add(n)
                    findings.append(Finding(
                        "compilesurface", "weight-as-closure-constant",
                        mod.relpath, getattr(node, "lineno", line), qual,
                        "%s:%s" % (fn_name, n),
                        "traced fn %s closes over weight-like state %r — "
                        "weights baked into the executable break "
                        "weight-independent progcache keys; pass them as "
                        "program arguments (the quant/PR 14 invariant)"
                        % (fn_name, n)))
            elif isinstance(node, ast.Attribute) and \
                    isinstance(node.ctx, ast.Load) and \
                    node.attr in _WEIGHT_ATTRS:
                base = node.value
                while isinstance(base, ast.Attribute):
                    base = base.value
                if not isinstance(base, ast.Name):
                    continue
                if base.id in params or base.id in local:
                    continue
                subj = "%s:%s.%s" % (fn_name, base.id, node.attr)
                if subj in flagged:
                    continue
                flagged.add(subj)
                findings.append(Finding(
                    "compilesurface", "weight-as-closure-constant",
                    mod.relpath, getattr(node, "lineno", line), qual,
                    subj,
                    "traced fn %s reads %s.%s through its closure — "
                    "weights baked into the executable break "
                    "weight-independent progcache keys; pass them as "
                    "program arguments" % (fn_name, base.id, node.attr)))


# --- donated-arg-reuse -------------------------------------------------------
def _donate_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
        if isinstance(v, (ast.Tuple, ast.List)):
            out = []
            for e in v.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    out.append(e.value)
            return tuple(out)
    return None


def _jit_call_in(value: ast.AST, aliases) -> Optional[ast.Call]:
    """The jit ctor call inside an assignment value (unwraps IfExp)."""
    if isinstance(value, ast.IfExp):
        return _jit_call_in(value.body, aliases) or \
            _jit_call_in(value.orelse, aliases)
    if isinstance(value, ast.Call) and _compile_like(value.func, aliases):
        return value
    return None


def _check_donated_reuse(mod: SourceModule, qual_for, top_fn: ast.AST,
                         aliases, findings: List[Finding]):
    """Linear same-block scan over a top-level def's subtree: names
    assigned from ``jax.jit(..., donate_argnums=...)``, then called with
    Name args at donated positions, kill those names; a later load in the
    same statement block (no rebind between) is a dangling-buffer read."""
    donated_fns: Dict[str, Tuple[int, ...]] = {}
    for node in ast.walk(top_fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            call = _jit_call_in(node.value, aliases)
            if call is not None:
                pos = _donate_positions(call)
                if pos:
                    donated_fns[node.targets[0].id] = pos
    if not donated_fns:
        return

    def scan_block(stmts: Sequence[ast.stmt]):
        dead: Dict[str, int] = {}  # name -> line it was donated at
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            loads, dons, stores = [], [], []
            for node in _walk_stop_at_defs(st):
                if isinstance(node, ast.Name):
                    if isinstance(node.ctx, ast.Load):
                        loads.append(node)
                    elif isinstance(node.ctx, ast.Store):
                        stores.append(node.id)
                elif isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Name) and \
                        node.func.id in donated_fns:
                    for p in donated_fns[node.func.id]:
                        if p < len(node.args) and \
                                isinstance(node.args[p], ast.Name):
                            dons.append((node.args[p].id, node.lineno))
            for nd in loads:
                if nd.id in dead:
                    findings.append(Finding(
                        "compilesurface", "donated-arg-reuse",
                        mod.relpath, nd.lineno, qual_for,
                        nd.id,
                        "%r was passed at a donate_argnums position "
                        "(line %d) and is dereferenced after the call — "
                        "XLA invalidated that buffer; rebind the name to "
                        "the program's output or drop the donation"
                        % (nd.id, dead[nd.id])))
                    dead.pop(nd.id, None)  # one finding per donation
            for name, line in dons:
                dead[name] = line
            for name in stores:
                dead.pop(name, None)

    for node in ast.walk(top_fn):
        for field in ("body", "orelse", "finalbody"):
            blk = getattr(node, field, None)
            if isinstance(blk, list) and blk and \
                    isinstance(blk[0], ast.stmt):
                scan_block(blk)


# --- the checker -------------------------------------------------------------
def check(modules: Sequence[SourceModule]) -> List[Finding]:
    index = _Index(modules)
    summaries = _collect_summaries(index)
    callers: Dict[FuncKey, Set[FuncKey]] = {}
    for k, s in summaries.items():
        for _held, callee, _line in s.calls:
            callers.setdefault(callee, set()).add(k)

    findings: List[Finding] = []
    budget_flagged: Set[str] = set()

    def check_budget(surface: str, mod: SourceModule, line: int,
                     qual: str):
        if surface in PROGRAM_BUDGETS or surface in budget_flagged:
            return
        budget_flagged.add(surface)
        findings.append(Finding(
            "compilesurface", "undeclared-program-budget", mod.relpath,
            line, qual, surface,
            "sanctioned compile surface %s owns a jit site but declares "
            "no bound in analysis.PROGRAM_BUDGETS — register its "
            "ladder+k program budget (docs/static_analysis.md)"
            % surface))

    for m in modules:
        aliases = index.aliases.get(m.modname) or import_aliases(m.tree)
        fns = _functions(m.tree)
        # local defs per top-level def subtree, for traced-fn resolution
        for cls, fname, fn in fns:
            key: FuncKey = (m.modname, cls, fname)
            qual = _qualname(key)
            local_defs: Dict[str, ast.AST] = {}
            for node in ast.walk(fn):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) and \
                        node is not fn:
                    local_defs[node.name] = node
                elif isinstance(node, ast.Assign) and \
                        isinstance(node.value, ast.Lambda) and \
                        len(node.targets) == 1 and \
                        isinstance(node.targets[0], ast.Name):
                    local_defs[node.targets[0].id] = node.value
            for node in _walk_stop_at_defs(fn):
                if not (isinstance(node, ast.Call) and
                        _compile_like(node.func, aliases)):
                    continue
                traced, tname = _traced_target(node, local_defs)
                # rule: stray-jit / undeclared-program-budget
                surface = _surface_of(key)
                if surface is not None:
                    check_budget(surface, m, node.lineno, qual)
                else:
                    csurf = [_surface_of(c)
                             for c in sorted(callers.get(key, ()))]
                    if csurf and all(csurf):
                        for s in sorted(set(csurf)):
                            check_budget(s, m, node.lineno, qual)
                    else:
                        findings.append(Finding(
                            "compilesurface", "stray-jit", m.relpath,
                            node.lineno, qual,
                            "jit(%s)" % (tname or "<expr>"),
                            "jit call site outside the sanctioned compile "
                            "surfaces (%s is not sanctioned and neither "
                            "are all its callers) — route it through a "
                            "budgeted surface or baseline it with a "
                            "justification" % (qual,)))
                # rule: weight-as-closure-constant
                if traced is not None:
                    _check_weight_closure(m, qual, traced, tname,
                                          node.lineno, findings)
            # rule: donated-arg-reuse (whole top-level subtree once)
            if "." not in fname:
                _check_donated_reuse(m, qual, fn, aliases, findings)
        # module-scope jit sites (outside any def) are always stray
        for st in m.tree.body:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            for node in _walk_stop_at_defs(st):
                if isinstance(node, ast.Call) and \
                        _compile_like(node.func, aliases):
                    findings.append(Finding(
                        "compilesurface", "stray-jit", m.relpath,
                        node.lineno, "%s:" % m.modname,
                        "jit(%s)" % (unparse(node.args[0])
                                     if node.args else "<expr>"),
                        "module-scope jit call site — compile surfaces "
                        "must live inside a sanctioned, budgeted "
                        "surface"))
    return findings
