"""Host-side dependency engine.

Python surface over the native scheduler (native/engine.cc) — the
TPU-native counterpart of the reference's Engine singleton
(include/mxnet/engine.h:75-250, src/engine/threaded_engine*.cc,
SURVEY §2.1 #1-5).

Division of labor (SURVEY §7): *device* work is ordered by XLA's async
runtime — jax.Array dispatch is already the reference NDArray's
engine-var pipelining (`.block_until_ready()` ≡ WaitToRead). This engine
orders the HOST work XLA cannot see: checkpoint/file IO, data-pipeline
stages, parameter-server-style updates, metric sinks. Semantics are the
reference's: closures tagged with const (read) / mutable (write) variable
sets; conflicting ops serialize in push order, independent ops run
concurrently on a native worker pool.

Selection mirrors MXNET_ENGINE_TYPE (src/engine/engine.cc:13-38):
``ThreadedEngine`` (default) or ``NaiveEngine`` (fully synchronous, for
debugging — the reference's own advice, threaded_engine.h:326-338).

    from mxnet_tpu import engine
    v = engine.new_variable()
    engine.push(lambda: write_file(...), mutable_vars=[v])
    engine.push(lambda: read_file(...), const_vars=[v])   # ordered after
    engine.wait_for_all()
"""
from __future__ import annotations

import ctypes
import functools
import json
import logging
import os
import threading
import traceback
import types
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import telemetry as _telemetry
from .base import MXNetError

_log = logging.getLogger("mxnet_tpu")

_OPR_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_void_p)
_DEL_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)

# --- op-error observation -----------------------------------------------------
# The engine NEVER lets an op exception escape the worker (it would cross the
# C boundary / kill the worker loop); by default a failed op prints its
# traceback and the run continues. A process-wide handler lets supervision
# layers (mxnet_tpu.resilience) OBSERVE those swallowed failures — e.g. to
# count injected faults or trigger a restore — without changing engine
# semantics. Plain module global, set once at startup: no lock needed.
_op_error_handler: Optional[Callable[[str, BaseException], None]] = None


def set_error_handler(fn: Optional[Callable[[str, BaseException], None]]):
    """Install ``fn(op_name, exc)`` to observe engine-op exceptions (which
    are otherwise only printed). Pass ``None`` to reset. Returns the
    previously installed handler. The handler runs ON the engine worker —
    it must be fast and must not raise (a raising handler is swallowed)."""
    global _op_error_handler
    prev = _op_error_handler
    _op_error_handler = fn
    return prev


def _notify_op_error(name: str, exc: BaseException):
    h = _op_error_handler
    if h is not None:
        try:
            h(name, exc)
        except Exception:  # an observing hook must never break dispatch
            traceback.print_exc()


def _load_native() -> Optional[ctypes.CDLL]:
    from . import native as _native

    # reuse the shared build machinery; the engine lib sits next to the io lib
    so = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "native", "libmxtpu_engine.so")
    if not os.path.exists(so):
        import subprocess

        try:
            _native.build("libmxtpu_engine.so")
        except (OSError, subprocess.SubprocessError):
            return None
    try:
        lib = ctypes.CDLL(so)
    except OSError:
        return None
    lib.mxe_create.restype = ctypes.c_void_p
    lib.mxe_create.argtypes = [ctypes.c_int, ctypes.c_int]
    lib.mxe_destroy.argtypes = [ctypes.c_void_p]
    lib.mxe_new_var.restype = ctypes.c_int64
    lib.mxe_new_var.argtypes = [ctypes.c_void_p]
    lib.mxe_delete_var.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.mxe_push.argtypes = [
        ctypes.c_void_p, _OPR_FN, ctypes.c_void_p, _DEL_FN,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
    lib.mxe_opr_complete.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.mxe_wait_for_var.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.mxe_wait_for_all.argtypes = [ctypes.c_void_p]
    lib.mxe_pending.restype = ctypes.c_int
    lib.mxe_pending.argtypes = [ctypes.c_void_p]
    lib.mxe_set_profiling.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.mxe_dump_profile.restype = ctypes.c_int64
    lib.mxe_dump_profile.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_int64]
    return lib


class NativeEngine:
    """ctypes wrapper over native/engine.cc."""

    def __init__(self, num_workers=0, engine_type="ThreadedEngine"):
        self._lib = _load_native()
        if self._lib is None:
            raise MXNetError("native engine library unavailable")
        etype = 1 if engine_type == "NaiveEngine" else 0
        self._h = self._lib.mxe_create(num_workers, etype)
        self._pending: Dict[int, tuple] = {}
        self._pending_lock = threading.Lock()
        self._next_key = [1]
        # single C trampoline for every op; param = key into _pending
        self._trampoline = _OPR_FN(self._dispatch)
        self._no_del = ctypes.cast(None, _DEL_FN)

    def _dispatch(self, param, on_complete):
        key = int(param)
        with self._pending_lock:
            fn, is_async, name, t_q, const_vars, mutable_vars = \
                self._pending.pop(key)
        # t_q was stamped at push time iff the engine span domain was on;
        # queue wait = dispatch time - push time. Worker thread identity
        # rides for free on the per-thread telemetry buffer; an async op's
        # end() records the completing thread as end_tid.
        span_args = None
        if t_q and _telemetry.enabled("engine"):
            span_args = {"queue_us": (_telemetry.clock_ns() - t_q) // 1000,
                         "const_vars": list(const_vars),
                         "mutable_vars": list(mutable_vars)}
        tok = None
        try:
            if is_async:
                h = ctypes.c_void_p(on_complete)
                if span_args is not None:
                    tok = _telemetry.begin(name, domain="engine", **span_args)

                def complete(_h=h, _tok=tok):
                    _telemetry.end(_tok)
                    self._lib.mxe_opr_complete(self._h, _h)

                fn(complete)
            else:
                if span_args is not None:
                    with _telemetry.span(name, domain="engine", **span_args):
                        fn()
                else:
                    fn()
        except Exception as e:  # never let an exception cross the C boundary
            traceback.print_exc()
            _notify_op_error(name, e)
            if is_async:
                _telemetry.end(tok, error=True)
                self._lib.mxe_opr_complete(self._h, ctypes.c_void_p(on_complete))

    def new_variable(self) -> int:
        return self._lib.mxe_new_var(self._h)

    def delete_variable(self, var: int):
        self._lib.mxe_delete_var(self._h, var)

    def _push(self, fn, const_vars, mutable_vars, priority, name, is_async):
        const_vars, mutable_vars = _dedup(const_vars, mutable_vars)
        t_q = _telemetry.clock_ns() if _telemetry.enabled("engine") else 0
        with self._pending_lock:
            key = self._next_key[0]
            self._next_key[0] += 1
            self._pending[key] = (fn, is_async, name, t_q,
                                  tuple(const_vars), tuple(mutable_vars))
        c = (ctypes.c_int64 * max(len(const_vars), 1))(*const_vars)
        m = (ctypes.c_int64 * max(len(mutable_vars), 1))(*mutable_vars)
        self._lib.mxe_push(self._h, self._trampoline, ctypes.c_void_p(key),
                           self._no_del, c, len(const_vars), m,
                           len(mutable_vars), priority, name.encode(),
                           1 if is_async else 0)

    def push(self, fn: Callable[[], None], const_vars: Sequence[int] = (),
             mutable_vars: Sequence[int] = (), priority: int = 0,
             name: str = "op"):
        """PushSync (engine.h:198-208): fn runs on a worker; completion is
        automatic on return."""
        self._push(fn, const_vars, mutable_vars, priority, name, False)

    def push_async(self, fn: Callable[[Callable[[], None]], None],
                   const_vars: Sequence[int] = (),
                   mutable_vars: Sequence[int] = (), priority: int = 0,
                   name: str = "op"):
        """PushAsync (engine.h:158-170): fn receives an ``on_complete``
        callable it must invoke (from any thread) when the op finishes."""
        self._push(fn, const_vars, mutable_vars, priority, name, True)

    def wait_for_var(self, var: int):
        self._lib.mxe_wait_for_var(self._h, var)

    def wait_for_all(self):
        self._lib.mxe_wait_for_all(self._h)

    def pending(self) -> int:
        return self._lib.mxe_pending(self._h)

    def set_profiling(self, on: bool):
        self._lib.mxe_set_profiling(self._h, int(on))

    def dump_profile(self) -> dict:
        n = self._lib.mxe_dump_profile(self._h, None, 0)
        buf = ctypes.create_string_buffer(n + 16)
        self._lib.mxe_dump_profile(self._h, buf, n + 16)
        return json.loads(buf.value.decode())

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.mxe_destroy(self._h)
                self._h = None
        except Exception:
            pass


class PythonEngine:
    """Pure-Python fallback honoring the API. ``NaiveEngine`` (the default
    here) runs everything inline, like naive_engine.cc. ``ThreadedEngine``
    drains a FIFO on one daemon worker: ops still run in push order
    (conservative — as if every op conflicted on a variable), but the
    pushing thread is NOT blocked, so host pipelines (async checkpoint
    writes, the serving batcher/dispatch split) overlap with the caller
    even when the native library is unavailable."""

    def __init__(self, num_workers=0, engine_type="NaiveEngine"):
        self._next = 1
        self._prof = []
        self._profiling = False
        self._queue = None
        if engine_type != "NaiveEngine":
            import queue

            self._queue = queue.Queue()
            threading.Thread(target=self._worker, daemon=True,
                             name="mxtpu-py-engine").start()

    def _worker(self):
        while True:
            fn, name = self._queue.get()
            try:
                fn()
            except Exception as e:  # never kill the worker loop
                traceback.print_exc()
                _notify_op_error(name, e)
            finally:
                self._queue.task_done()

    def new_variable(self):
        self._next += 1
        return self._next - 1

    def delete_variable(self, var):
        pass

    def _run_profiled(self, fn, name, t_q=0):
        import time

        t0 = time.time()
        if t_q and _telemetry.enabled("engine"):
            with _telemetry.span(
                    name, domain="engine",
                    queue_us=(_telemetry.clock_ns() - t_q) // 1000):
                fn()
        else:
            fn()
        if self._profiling:
            self._prof.append({"name": name, "ph": "X", "pid": 0, "tid": 0,
                               "ts": int(t0 * 1e6),
                               "dur": int((time.time() - t0) * 1e6)})

    def push(self, fn, const_vars=(), mutable_vars=(), priority=0, name="op"):
        t_q = _telemetry.clock_ns() if _telemetry.enabled("engine") else 0
        if self._queue is not None:
            self._queue.put((lambda: self._run_profiled(fn, name, t_q), name))
        else:
            self._run_profiled(fn, name, t_q)

    def push_async(self, fn, const_vars=(), mutable_vars=(), priority=0,
                   name="op"):
        t_q = _telemetry.clock_ns() if _telemetry.enabled("engine") else 0

        def run():
            done = threading.Event()
            fn(done.set)
            done.wait()  # hold the FIFO slot until on_complete fires

        if self._queue is not None:
            self._queue.put((lambda: self._run_profiled(run, name, t_q), name))
        else:
            self._run_profiled(run, name, t_q)

    def wait_for_var(self, var):
        # conservative: the FIFO admits no reordering, so draining it is a
        # correct (if coarse) WaitForVar
        if self._queue is not None:
            self._queue.join()

    def wait_for_all(self):
        if self._queue is not None:
            self._queue.join()

    def pending(self):
        return self._queue.unfinished_tasks if self._queue is not None else 0

    def set_profiling(self, on):
        self._profiling = bool(on)

    def dump_profile(self):
        return {"traceEvents": list(self._prof)}


def _dedup(const_vars, mutable_vars):
    """DeduplicateVarHandle (engine.h:231-249): drop repeats; a var that is
    both read and mutated is tracked as mutable only."""
    mut = list(dict.fromkeys(mutable_vars))
    mset = set(mut)
    const = [v for v in dict.fromkeys(const_vars) if v not in mset]
    return const, mut


_engine = None
_engine_lock = threading.Lock()


def get() -> "NativeEngine | PythonEngine":
    """Engine.Get() singleton (engine.h:211). Type from MXNET_ENGINE_TYPE."""
    global _engine
    with _engine_lock:
        if _engine is None:
            etype = os.environ.get("MXNET_ENGINE_TYPE", "ThreadedEngine")
            workers = int(os.environ.get("MXNET_CPU_WORKER_NTHREADS", "0"))
            try:
                _engine = NativeEngine(workers, etype)
            except MXNetError:
                _log.warning(
                    "native engine library unavailable (make -C native "
                    "failed or no toolchain): host ops run on the "
                    "pure-Python engine")
                _engine = PythonEngine(workers, etype)
        return _engine


# module-level conveniences mirroring the reference's C API surface
def new_variable():
    v = get().new_variable()
    if _san is not None:
        _san.on_new(v)
    return v


def delete_variable(var):
    if _san is not None:
        _san.on_delete(var)
    get().delete_variable(var)


def push(fn, const_vars=(), mutable_vars=(), priority=0, name="op"):
    if _san is not None:
        _san.on_push(fn, const_vars, mutable_vars, name)
    counted = _inflight_begin(tuple(const_vars) + tuple(mutable_vars))
    if counted:
        fn = _wrap_inflight_sync(fn, counted)
    get().push(fn, const_vars, mutable_vars, priority, name)


def push_async(fn, const_vars=(), mutable_vars=(), priority=0, name="op"):
    if _san is not None:
        _san.on_push(fn, const_vars, mutable_vars, name)
    counted = _inflight_begin(tuple(const_vars) + tuple(mutable_vars))
    if counted:
        fn = _wrap_inflight_async(fn, counted)
    get().push_async(fn, const_vars, mutable_vars, priority, name)


def wait_for_var(var):
    get().wait_for_var(var)
    if _san is not None:
        _san.on_sync((int(var),))


def wait_for_all():
    with _telemetry.span("engine.wait_for_all", domain="engine"):
        get().wait_for_all()
    if _san is not None:
        _san.on_sync(None)
    _raise_pending_file_error()


class Fence:
    """Handle returned by :func:`fence` — a pushed barrier op.

    ``wait()`` blocks until every op enqueued BEFORE the fence on the
    fenced vars has fully completed — including async ops, whose
    completion is their host ``on_complete`` callback firing. That is the
    happens-before edge ``nd.waitall()`` does NOT provide (it drains the
    device queue; host callbacks may still be in flight) and that a
    per-var ``wait_for_var`` loop provides only one var at a time.
    """

    def __init__(self, event: threading.Event, n_vars: int,
                 fence_vars: Sequence[int] = ()):
        self._event = event
        self.n_vars = n_vars
        self._fence_vars = tuple(fence_vars)

    def done(self) -> bool:
        """True once the barrier op has run (non-blocking probe)."""
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> "Fence":
        """Block for the barrier; raises MXNetError on timeout."""
        with _telemetry.span("engine.fence.wait", domain="engine",
                             n_vars=self.n_vars):
            reached = self._event.wait(timeout)
        if not reached:
            raise MXNetError(
                "engine fence over %d var(s) not reached after %.3fs"
                % (self.n_vars, timeout))
        if _san is not None and self._fence_vars:
            # the fence completed: every DECLARED access enqueued before it
            # on these vars happened-before this point
            _san.on_sync(self._fence_vars)
        return self


def fence(vars: Sequence[int], priority: int = 0,
          name: str = "fence") -> Fence:
    """Push a barrier op ordered after everything enqueued on ``vars``.

    The barrier reads every var (``const_vars``), so the engine schedules
    it only once all prior writers — sync or async — have completed.
    Returns immediately with a :class:`Fence`; call ``.wait()`` for the
    blocking edge, or poll ``.done()`` to overlap host work::

        f = engine.fence([var_a, var_b], name="ckpt_fence")
        ...                      # overlapped host work
        f.wait()                 # ops on var_a/var_b happened-before here
    """
    ev = threading.Event()
    vs = list(vars)
    if _san is not None:
        _san.on_fence(vs, name)
    get().push(ev.set, const_vars=vs, priority=priority, name=name)
    return Fence(ev, len(vs), fence_vars=vs)


# --- happens-before sanitizer (MXNET_ENGINE_SANITIZER) -----------------------
# Dynamic half of mxnet_tpu.analysis.racecheck: with MXNET_ENGINE_SANITIZER=1
# (or sanitizer_enable()), every module-level push is checked against shadow
# epochs per engine var. Host state registered with guard_state(obj, var) is
# found by a bounded reachability scan over the pushed fn (closure cells,
# defaults, functools.partial, bound-method instances — one helper level
# deep); reaching it without declaring its var, while a prior access is not
# yet settled by a fence/wait on that var, is a race: the engine has no edge
# ordering the two ops. Checks run at push time only — op fns execute exactly
# as without the sanitizer (so MXNET_FAULT_PLAN composes untouched).
#
# Disabled path: `_san` stays None and every hook is one global load + branch.
_san = None
_san_lock = threading.Lock()  # leaf (rank 100): guards shadow tables only


def _san_site() -> str:
    """First stack frame outside this file — the user-visible push site."""
    for fr in reversed(traceback.extract_stack(limit=12)[:-2]):
        if not fr.filename.endswith("engine.py"):
            return "%s:%d" % (os.path.basename(fr.filename), fr.lineno)
    return "<engine>"


class _ShadowVar:
    __slots__ = ("epoch", "decl_epoch", "synced", "last", "deleted")

    def __init__(self):
        self.epoch = 0       # every tracked access, declared or undeclared
        self.decl_epoch = 0  # high-water mark of declared accesses only
        self.synced = 0      # decl_epoch as of the last fence/wait on the var
        self.last = None     # (op, site, mode, declared-var frozenset)
        self.deleted = None  # site of delete_variable once deleted

    def settled(self) -> bool:
        return self.epoch <= self.synced


class _Sanitizer:
    """Shadow-state tracker behind the module-level engine API."""

    MAX_REPORTS = 1000

    def __init__(self):
        self._vars: Dict[int, _ShadowVar] = {}
        # id(obj) -> (obj, var, desc); strong refs so ids are never reused
        self._guards: Dict[int, Tuple[object, int, str]] = {}
        self.reports: List[dict] = []

    # -- guard registry ------------------------------------------------------
    def guard(self, obj, var, desc):
        with _san_lock:
            self._guards[id(obj)] = (obj, int(var), desc)

    def unguard(self, obj):
        with _san_lock:
            self._guards.pop(id(obj), None)

    def _reachable_guards(self, fn):
        """Guarded objects reachable from a pushed callable. Lock-free: only
        dict probes on the guard registry (GIL-atomic)."""
        found, seen = [], set()
        stack = [(fn, 2)]
        budget = 256
        while stack and budget:
            obj, depth = stack.pop()
            oid = id(obj)
            if oid in seen:
                continue
            seen.add(oid)
            budget -= 1
            hit = self._guards.get(oid)
            if hit is not None and hit[0] is obj:
                found.append((hit[1], hit[2]))
                continue
            if depth <= 0:
                continue
            if isinstance(obj, functools.partial):
                stack.append((obj.func, depth))
                stack.extend((a, depth) for a in obj.args)
                stack.extend((v, depth) for v in obj.keywords.values())
            elif isinstance(obj, (list, tuple, set, frozenset)):
                stack.extend((e, depth) for e in list(obj)[:32])
            elif isinstance(obj, dict):
                stack.extend((v, depth) for v in list(obj.values())[:32])
            elif isinstance(obj, (types.ModuleType, type)):
                pass  # never walk module/class namespaces
            else:
                inst = getattr(obj, "__self__", None)
                if inst is not None and not isinstance(
                        inst, (types.ModuleType, type)):
                    stack.append((inst, depth - 1))
                f = getattr(obj, "__func__", obj)
                cells = getattr(f, "__closure__", None)
                if cells:
                    for c in cells:
                        try:
                            stack.append((c.cell_contents, depth - 1))
                        except ValueError:  # empty cell
                            pass
                dfl = getattr(f, "__defaults__", None)
                if dfl:
                    stack.extend((v, depth - 1) for v in dfl)
                code = getattr(f, "__code__", None)
                gl = getattr(f, "__globals__", None)
                if code is not None and gl is not None:
                    # module-global state (and global helpers) the fn names
                    for nm in code.co_names[:32]:
                        if nm in gl:
                            stack.append((gl[nm], depth - 1))
                if not callable(obj):
                    d = getattr(obj, "__dict__", None)
                    if isinstance(d, dict):
                        stack.extend(
                            (v, depth - 1) for v in list(d.values())[:64])
        return found

    # -- hooks (called from the module-level wrappers) -----------------------
    def on_new(self, var):
        with _san_lock:
            self._vars.pop(int(var), None)

    def on_delete(self, var):
        site = _san_site()
        with _san_lock:
            self._vars.setdefault(int(var), _ShadowVar()).deleted = site

    def on_sync(self, vars):
        """A wait completed: declared accesses on `vars` (all vars if None)
        happened-before this point. Undeclared epochs stay unsettled — a
        fence only covers ops the engine knew about."""
        with _san_lock:
            if vars is None:
                cells = list(self._vars.values())
            else:
                cells = [self._vars[v] for v in (int(x) for x in vars)
                         if v in self._vars]
            for cell in cells:
                cell.synced = cell.decl_epoch

    def on_fence(self, vars, name):
        site = _san_site()
        out = []
        with _san_lock:
            for v in (int(x) for x in vars):
                cell = self._vars.get(v)
                if cell is not None and cell.deleted is not None:
                    out.append(self._mk(
                        "var-use-after-delete", v, name, site,
                        "delete_variable", cell.deleted,
                        detail="fence names var %d after deletion" % v))
        for rep in out:
            self._emit(rep)

    def on_push(self, fn, const_vars, mutable_vars, name):
        site = _san_site()
        mut = {int(v) for v in mutable_vars}
        declared = {int(v) for v in const_vars} | mut
        touched = self._reachable_guards(fn)
        out = []
        with _san_lock:
            for v in sorted(declared):
                cell = self._vars.get(v)
                if cell is not None and cell.deleted is not None:
                    out.append(self._mk(
                        "var-use-after-delete", v, name, site,
                        "delete_variable", cell.deleted,
                        detail="op declares var %d after deletion" % v))
            for v, desc in touched:
                if v in declared:
                    continue  # ordered: the engine sees this access
                cell = self._vars.setdefault(v, _ShadowVar())
                last = cell.last
                # a shared declared var with the previous access orders the
                # two ops even though this one skips the guard var
                if not cell.settled() and last is not None \
                        and not (declared & last[3]):
                    out.append(self._mk(
                        "undeclared-var-access", v, name, site,
                        last[0], last[1],
                        detail="op reaches state %r guarded by var %d "
                               "without declaring it" % (desc, v)))
                cell.epoch += 1
                cell.last = (name, site, "undeclared", frozenset(declared))
            for v in sorted(declared):
                cell = self._vars.setdefault(v, _ShadowVar())
                last = cell.last
                if not cell.settled() and last is not None \
                        and last[2] == "undeclared" \
                        and not (declared & last[3]):
                    out.append(self._mk(
                        "undeclared-var-access", v, name, site,
                        last[0], last[1],
                        detail="declared access races the earlier "
                               "undeclared access to var %d" % v))
                cell.epoch += 1
                cell.decl_epoch = cell.epoch
                cell.last = (name, site,
                             "write" if v in mut else "read",
                             frozenset(declared))
        for rep in out:
            self._emit(rep)

    # -- reporting -----------------------------------------------------------
    @staticmethod
    def _mk(rule, var, op, site, other_op, other_site, detail=""):
        return {"rule": rule, "var": int(var), "op": op, "site": site,
                "other_op": other_op, "other_site": other_site,
                "detail": detail,
                "stack": "".join(traceback.format_stack(limit=8)[:-2])}

    def _emit(self, rep):
        with _san_lock:
            if len(self.reports) < self.MAX_REPORTS:
                self.reports.append(rep)
        # counter/log have their own locking: keep them OUTSIDE _san_lock
        _san_counter.inc()
        _log.error(
            "engine sanitizer [%s] var %d: op '%s' at %s vs op '%s' at %s"
            " — %s", rep["rule"], rep["var"], rep["op"], rep["site"],
            rep["other_op"], rep["other_site"], rep["detail"])


_san_counter = _telemetry.registry.counter(
    "engine_sanitizer_reports_total",
    help="Races reported by the engine happens-before sanitizer")


def sanitizer_enabled() -> bool:
    return _san is not None


def sanitizer_enable(on: bool = True):
    """Turn the happens-before sanitizer on (fresh shadow state) or off at
    runtime; the import-time switch is MXNET_ENGINE_SANITIZER=1."""
    global _san
    _san = _Sanitizer() if on else None


def sanitizer_reports() -> List[dict]:
    """Snapshot of race reports since the sanitizer was (re-)enabled."""
    if _san is None:
        return []
    with _san_lock:
        return list(_san.reports)


def sanitizer_clear():
    """Drop accumulated reports; shadow epochs and guards are kept."""
    if _san is not None:
        with _san_lock:
            del _san.reports[:]


def guard_state(obj, var, name: Optional[str] = None):
    """Register ``obj`` (host container/buffer) as engine state ordered by
    ``var``: any pushed fn that can reach ``obj`` without declaring ``var``
    races every unsettled access. No-op while the sanitizer is off."""
    if _san is not None:
        _san.guard(obj, var, name or type(obj).__name__)
    return obj


def unguard_state(obj):
    if _san is not None:
        _san.unguard(obj)


if os.environ.get("MXNET_ENGINE_SANITIZER", "0").strip().lower() \
        not in ("", "0", "false", "off"):
    _san = _Sanitizer()


# --- per-var in-flight accounting --------------------------------------------
# Opt-in queued-or-running op counts per engine variable, the signal a
# load-aware dispatcher needs (serving's least-outstanding-work router reads
# its replica vars through this): a var registered with track_inflight() has
# every module-level push/push_async mentioning it counted at push time and
# released when the op completes (sync: fn returned; async: on_complete
# fired). Untracked vars pay nothing — one dict probe per push.
_inflight: Dict[int, int] = {}
_inflight_lock = threading.Lock()


def track_inflight(var: int):
    """Register ``var`` for in-flight accounting (idempotent)."""
    with _inflight_lock:
        _inflight.setdefault(int(var), 0)


def untrack_inflight(var: int):
    """Stop accounting for ``var`` and drop its counter."""
    with _inflight_lock:
        _inflight.pop(int(var), None)


def var_inflight(var: int) -> int:
    """Ops queued or running that mention ``var`` (0 if untracked)."""
    with _inflight_lock:
        return _inflight.get(int(var), 0)


def _inflight_begin(vars) -> tuple:
    """Count the push against every tracked var; returns the vars counted
    (empty tuple => nothing tracked, no completion bookkeeping needed)."""
    if not _inflight:  # racy read is fine: tracking starts before pushing
        return ()
    counted = []
    with _inflight_lock:
        for v in vars:
            if v in _inflight:
                _inflight[v] += 1
                counted.append(v)
    return tuple(counted)


def _inflight_end(counted: tuple):
    with _inflight_lock:
        for v in counted:
            if v in _inflight:
                _inflight[v] -= 1


def _wrap_inflight_sync(fn, counted):
    def run():
        try:
            fn()
        finally:
            _inflight_end(counted)
    return run


def _wrap_inflight_async(fn, counted):
    def run(on_complete):
        released = []  # once-guard: the engine's error path may re-complete

        def done():
            if not released:
                released.append(1)
                _inflight_end(counted)
            on_complete()

        try:
            fn(done)
        except BaseException:
            # the engine completes an op whose fn raised without calling
            # our done(); release here so the counter can never leak high
            if not released:
                released.append(1)
                _inflight_end(counted)
            raise
    return run


# --- file-write routing ------------------------------------------------------
# Checkpoint/state blob writes ride the engine with one write-var per file
# path (the reference's NDArray save-through-engine: every host mutation of
# a named resource is an engine op, kvstore_dist.h:233-241 being the PS
# analogue). Writers push with the path's var mutable; readers wait on the
# var, so an in-flight async checkpoint is never half-read.
_file_vars: Dict[str, int] = {}
_file_pending: Dict[str, int] = {}  # writes queued-or-running per path
_file_waiting: Dict[str, int] = {}  # waiters pinning the var per path
_file_errs: Dict[str, BaseException] = {}
_file_lock = threading.Lock()


def file_var(path: str) -> int:
    """The engine write-var owning ``path`` (created on first use)."""
    path = os.path.abspath(path)
    with _file_lock:
        v = _file_vars.get(path)
        if v is None:
            v = get().new_variable()
            _file_vars[path] = v
        return v


def push_file_write(path: str, fn: Callable[[], None], wait: bool = True,
                    name: Optional[str] = None,
                    after_paths: Sequence[str] = ()):
    """Run ``fn`` (which writes ``path``) as an engine op holding the
    path's write-var. ``wait=False`` returns immediately — the write
    overlaps whatever the caller does next. A failed async write
    surfaces at the next ``wait_for_file(path)``, OR at the next
    ``push_file_write``/``wait_for_all`` on ANY path (per-epoch
    checkpoints use distinct filenames, so surfacing must not be
    per-path-only — a full disk would otherwise lose every later
    checkpoint silently).

    ``after_paths`` orders this write AFTER every previously enqueued
    write on those paths (their file-vars become const deps): the
    commit-manifest-after-all-shards edge sharded checkpoints need —
    the manifest op cannot run until every shard op finished, so a
    crash at any point leaves either no manifest or a manifest whose
    shards are all fully on disk."""
    apath = os.path.abspath(path)
    _raise_pending_file_error()
    eng = get()
    deps = []
    with _file_lock:
        var = _file_vars.get(apath)
        if var is None:
            var = eng.new_variable()
            _file_vars[apath] = var
        # counted under the SAME lock acquisition that resolved the var,
        # so wait_for_file can never retire a var with a write en route
        _file_pending[apath] = _file_pending.get(apath, 0) + 1
        dep_paths = []
        for p in after_paths:
            ap = os.path.abspath(p)
            if ap == apath:
                continue
            dv = _file_vars.get(ap)
            if dv is None:
                continue  # nothing ever written there: no edge needed
            deps.append(dv)
            dep_paths.append(ap)
            # pin the dep vars against retirement until this op completes
            # (a const reader is invisible to _file_pending otherwise)
            _file_pending[ap] = _file_pending.get(ap, 0) + 1

    def run():
        try:
            fn()
        except BaseException as e:  # surface at the next sync point
            with _file_lock:
                _file_errs[apath] = e
        finally:
            with _file_lock:
                _file_pending[apath] -= 1
                for ap in dep_paths:
                    _file_pending[ap] -= 1

    eng.push(run, const_vars=deps, mutable_vars=[var],
             name=name or ("file_write:%s" % os.path.basename(apath)))
    if wait:
        wait_for_file(apath)


def _raise_pending_file_error():
    with _file_lock:
        if not _file_errs:
            return
        path, err = next(iter(_file_errs.items()))
        del _file_errs[path]
    raise err


def _retire_file_var(apath: str, var: int):
    """Drop the path's var ONLY if no write is queued/in flight, no other
    waiter holds it, and the mapping is unchanged (guards the concurrent
    writer AND concurrent waiter races); the native delete is itself
    ordered after the var's enqueued ops."""
    with _file_lock:
        if (_file_pending.get(apath, 0) != 0
                or _file_waiting.get(apath, 0) != 0
                or _file_vars.get(apath) is not var):
            return
        del _file_vars[apath]
        _file_pending.pop(apath, None)
    get().delete_variable(var)


def wait_for_file(path: str):
    """Block until every pending engine op on ``path`` finished; re-raise
    the first failure recorded for it. Once drained (and only if no new
    write or other waiter raced in), the path's engine var is retired so
    long runs with per-epoch filenames don't grow the var table without
    bound."""
    apath = os.path.abspath(path)
    with _file_lock:
        var = _file_vars.get(apath)
        if var is not None:
            # pin: a concurrent wait_for_file must not retire+delete the
            # var between our lookup and the native wait
            _file_waiting[apath] = _file_waiting.get(apath, 0) + 1
    if var is not None:
        try:
            get().wait_for_var(var)
        finally:
            with _file_lock:
                _file_waiting[apath] -= 1
                if _file_waiting[apath] == 0:
                    del _file_waiting[apath]  # no unbounded per-path table
        _retire_file_var(apath, var)
    with _file_lock:
        err = _file_errs.pop(apath, None)
    if err is not None:
        raise err


def wait_for_all_files():
    """Drain every pending file write and surface the first failure —
    call at end-of-training when using async_write."""
    with _file_lock:
        pending = list(_file_vars)
    first_err = None
    for apath in pending:
        try:
            wait_for_file(apath)
        except BaseException as e:
            # drain EVERY path before surfacing: a caller that catches the
            # error must still find the other checkpoints fully written
            if first_err is None:
                first_err = e
    if first_err is not None:
        raise first_err
    _raise_pending_file_error()


# queue depth for the metrics registry — the callback reads the module
# global at scrape time and never instantiates an engine itself
_telemetry.registry.gauge(
    "engine_pending_ops",
    fn=lambda: _engine.pending() if _engine is not None else 0,
    help="ops queued or running on the host dependency engine")
