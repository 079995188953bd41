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
                logging.getLogger("mxnet_tpu").warning(
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


# --- capture/replay of steady-state dispatch sequences -----------------------
# PyGraph-style (PAPERS.md): the per-op host cost of dynamic dispatch —
# _dedup, the pending-table lock, the ctypes marshalling, the native
# scheduler walk — is paid once during a short warmup, then the whole
# sequence replays as ONE engine submission whose internal ordering comes
# from a precomputed edge list.

_log = logging.getLogger("mxnet_tpu")


def capture_enabled() -> bool:
    """True when ``MXNET_ENGINE_CAPTURE`` opts steady-state callers
    (``Module.fit_step``, serving dispatch) into capture/replay. Read at
    point of use so tests and dryruns can flip it mid-process."""
    return os.environ.get("MXNET_ENGINE_CAPTURE", "0").lower() \
        not in ("0", "", "false", "off")


def capture_warmup() -> int:
    """Warmup iterations before a sequence is eligible to replay
    (``MXNET_ENGINE_CAPTURE_WARMUP``, default 3, floor 2 — stability is
    meaningless with a single observation)."""
    try:
        n = int(os.environ.get("MXNET_ENGINE_CAPTURE_WARMUP", "3"))
    except ValueError:
        n = 3
    return max(2, n)


def fuse_enabled() -> bool:
    """True when ``MXNET_ENGINE_FUSE`` opts stable captured sequences into
    trace-and-fuse: the recorded op stream is lowered into ONE jitted XLA
    program (requires capture — a sequence that never stabilizes has
    nothing to fuse). Read at point of use, like :func:`capture_enabled`."""
    return os.environ.get("MXNET_ENGINE_FUSE", "0").lower() \
        not in ("0", "", "false", "off")


class _FuseBail(Exception):
    """Per-iteration fuse bail (feed drift, executable failure before any
    side effect): the iteration falls back to replay-style execution."""


class _FuseIneligible(Exception):
    """The recorded sequence cannot be fused at all (an op lacks traceable
    metadata, or the metadata contradicts the declared var sets)."""


class FuseOp:
    """Traceable metadata for one captured push (trace-and-fuse).

    A push site that wants its op fused passes ``fuse=FuseOp(...)`` to
    :meth:`CapturedSequence.push`/``push_async``. The eager closure still
    runs during warmup/replay/bail; once the sequence stabilizes with
    every slot carrying a FuseOp, :class:`FusedSequence` stages the
    ``jax_fn``s into one jitted program and the closures stop running.

    - ``jax_fn(*registers, *feeds) -> tuple(out registers)``: pure,
      traceable. Registers are arbitrary pytrees keyed by engine var —
      the op consumes its ``in_vars``' registers (in order) plus the
      per-iteration ``feed`` values, and produces one register per
      ``out_vars`` entry.
    - ``in_vars``/``out_vars``: engine vars read/written. Must be covered
      by the push's declared const/mutable sets (the pre-resolved
      RAW/WAR/WAW edges are the fused program's dependency structure).
    - ``feed``: per-iteration concrete inputs — a tuple, or a zero-arg
      callable returning one (evaluated inside the fused engine op).
      Shapes/dtypes must stay stable; drift bails the iteration to
      replay.
    - ``init``: dict var -> value-or-callable seeding the register of a
      var that is read before it is written (live-in). Evaluated once at
      staging time, after a quiescing fence.
    - ``writeback``: host callable receiving ``{var: final value}`` for
      this op's out_vars after each fused iteration — the hook that keeps
      consumer-visible state (param snapshots, serving responses) in sync
      so a later bail resumes correctly. Runs on the engine worker,
      inside the fused push.
    - ``fingerprint``: stable content hash of the computation for the
      progcache key; ``None`` means "hash the lowered program text".
    """

    __slots__ = ("jax_fn", "in_vars", "out_vars", "feed", "init",
                 "writeback", "fingerprint")

    def __init__(self, jax_fn, in_vars: Sequence[int] = (),
                 out_vars: Sequence[int] = (), feed=(), init=None,
                 writeback=None, fingerprint: Optional[str] = None):
        self.jax_fn = jax_fn
        self.in_vars = tuple(int(v) for v in in_vars)
        self.out_vars = tuple(int(v) for v in out_vars)
        self.feed = feed
        self.init = init or {}
        self.writeback = writeback
        self.fingerprint = fingerprint


# process-wide trace-and-fuse accounting: the dict is the test/dryrun
# surface (always on), the registry counters the telemetry export
_fuse_stats = {"runs": 0, "bails": 0, "ineligible": 0, "compiles": 0,
               "disk_loads": 0}
_fused_runs_counter = _telemetry.registry.counter(
    "engine_fused_runs_total",
    help="captured-sequence iterations executed as one fused XLA program")
_fuse_bails_counter = _telemetry.registry.counter(
    "engine_fuse_bails_total",
    help="trace-and-fuse bails back to replay (ineligible sequence, "
         "staging failure, feed drift, runtime error)")


def fused_stats() -> Dict[str, int]:
    """Snapshot of trace-and-fuse counters (runs, bails, ineligible,
    compiles, disk_loads) since process start / last reset."""
    return dict(_fuse_stats)


def fused_stats_reset():
    for k in _fuse_stats:
        _fuse_stats[k] = 0


def _count_fuse_bail(kind: str):
    _fuse_stats["bails"] += 1
    if kind == "ineligible":
        _fuse_stats["ineligible"] += 1
    _fuse_bails_counter.inc()


def _sharding_sig(leaf):
    """Stable signature of a committed jax.sharding, or None.

    Sharded carries (MXNET_SHARDED_UPDATE stages 1-3) lower into the
    fused program with their NamedSharding baked into the executable, so
    the placement must be part of the staging aval: a progcache entry
    serialized for one mesh/spec must never be handed a differently
    placed carry, and a placement change must re-stage rather than feed
    a stale program. Single-device / uncommitted / non-jax leaves all
    map to None so the unsharded path's keys are unchanged.
    """
    sh = getattr(leaf, "sharding", None)
    if sh is None:
        return None
    try:
        import jax
        if not isinstance(sh, jax.sharding.NamedSharding):
            return None
        mesh = sh.mesh
        return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
                str(sh.spec))
    except Exception:
        return None


class FusedSequence:
    """One stable :class:`CapturedSequence` lowered into ONE jitted XLA
    program (``MXNET_ENGINE_FUSE``; ROADMAP trace-and-fuse).

    Construction runs on the sequence's driving thread at the first ready
    ``end_step`` and performs the whole staging pipeline:

    1. **Quiesce**: fence the union var set so every warmup iteration's
       effects are settled before live-in registers are seeded.
    2. **Liveness** over the per-op ``in_vars``/``out_vars``: a var read
       before its first write is *carried* (live-in AND live-out — its
       register threads across iterations and is seeded from
       ``FuseOp.init``); a var written then only consumed inside the
       iteration is an *intermediate* (donated, dead at iteration end,
       DCE'd by XLA unless a writeback needs it). Var ids are normalized
       to sequence-local indices so the staged program — and its cache
       key — are process-independent.
    3. **Stitch**: each op's ``jax_fn`` is staged in recorded order,
       consuming registers exactly along the pre-resolved RAW/WAR/WAW
       edges, into one function ``(carry, feeds) -> (carry', mats)``
       jitted with the carry donated.
    4. **Cache**: the executable is keyed in progcache by the capture
       signature — sha1 over per-op fingerprints, the edge set and in/out
       avals (plus the lowered text when an op has no explicit
       fingerprint) — so a warm restart disk-loads it with zero fresh
       compiles (``kind="fused"`` in the entry meta).

    Per iteration, :meth:`run_iteration` (on the engine worker, inside
    the single ``fused:<name>`` push) evaluates the fresh ``FuseOp``
    feeds, checks their avals against the staged ones (drift raises
    :class:`_FuseBail` BEFORE the executable runs — the iteration is then
    replayed untouched), executes the program, and runs the writebacks.
    """

    def __init__(self, name: str, ops: List[tuple], fuses: List[FuseOp],
                 union: Tuple[tuple, tuple]):
        import jax  # deferred: the engine itself must import without jax

        self.name = name
        u_const, u_mut = union
        # 1. quiesce: warmup iterations still in flight wrote the state
        # the init callables are about to read
        fence(list(u_const) + list(u_mut),
              name="fuse_stage:%s" % name).wait(120)
        declared_mut = [set(int(v) for v in sig[4]) for sig, _ in ops]
        declared_all = [set(int(v) for v in sig[3]) | declared_mut[i]
                        for i, (sig, _) in enumerate(ops)]
        for i, f in enumerate(fuses):
            if not set(f.in_vars) <= declared_all[i]:
                raise _FuseIneligible(
                    "op %d (%s) fuse metadata reads vars outside its "
                    "declared set" % (i, ops[i][0][1]))
            if not set(f.out_vars) <= declared_mut[i]:
                raise _FuseIneligible(
                    "op %d (%s) fuse metadata writes vars outside its "
                    "declared mutable set" % (i, ops[i][0][1]))
        # 2. liveness under normalized (process-independent) var indices
        var_idx: Dict[int, int] = {}
        for v in list(u_const) + list(u_mut):
            var_idx[int(v)] = len(var_idx)
        first: Dict[int, str] = {}
        order: List[int] = []
        for f in fuses:
            for v in f.in_vars:
                if v not in first:
                    first[v] = "r"
                    order.append(v)
            for v in f.out_vars:
                if v not in first:
                    first[v] = "w"
                    order.append(v)
        carried = tuple(v for v in order if first[v] == "r")
        wb_ops = tuple(i for i, f in enumerate(fuses)
                       if f.writeback is not None)
        mat_vars = tuple(v for i in wb_ops for v in fuses[i].out_vars
                         if v not in carried)
        carry0 = {}
        for v in carried:
            src = None
            for f in fuses:
                if v in f.init:
                    src = f.init[v]
                    break
            if src is None:
                raise _FuseIneligible(
                    "live-in var %d has no FuseOp.init seed" % v)
            carry0[var_idx[v]] = src() if callable(src) else src
        self._var_idx = var_idx
        self._carried_idx = tuple(var_idx[v] for v in carried)
        self._mat_idx = tuple(sorted(var_idx[v] for v in set(mat_vars)))
        self._wb_ops = wb_ops
        self._in_idx = tuple(tuple(var_idx[v] for v in f.in_vars)
                             for f in fuses)
        self._out_idx = tuple(tuple(var_idx[v] for v in f.out_vars)
                              for f in fuses)
        self._out_vars = tuple(f.out_vars for f in fuses)
        # 3. staged feeds: evaluated once here (they double as the lowering
        # example args and the aval reference for drift checks), then the
        # first run_iteration consumes them instead of re-evaluating
        feeds0, defs, avals = [], [], []
        for i, f in enumerate(fuses):
            fv = tuple(f.feed()) if callable(f.feed) else tuple(f.feed)
            leaves, treedef = jax.tree_util.tree_flatten(fv)
            feeds0.append(fv)
            defs.append(treedef)
            avals.append(tuple(self._aval(l) for l in leaves))
        self._feed_defs = tuple(defs)
        self._feed_avals = tuple(avals)
        self._pending_feeds: Optional[tuple] = tuple(feeds0)
        jax_fns = tuple(f.jax_fn for f in fuses)
        in_idx, out_idx = self._in_idx, self._out_idx
        carried_idx, mat_idx = self._carried_idx, self._mat_idx
        names = tuple(sig[1] for sig, _ in ops)

        def fused(carry, feeds):
            regs = dict(carry)
            for i, fn in enumerate(jax_fns):
                res = fn(*[regs[k] for k in in_idx[i]], *feeds[i])
                if not isinstance(res, (tuple, list)):
                    res = (res,)
                if len(res) != len(out_idx[i]):
                    raise _FuseIneligible(
                        "op %d (%s) jax_fn returned %d value(s) for %d "
                        "out var(s)" % (i, names[i], len(res),
                                        len(out_idx[i])))
                for k, val in zip(out_idx[i], res):
                    regs[k] = val
            # materialized registers BEFORE the carry: with the carry
            # donated, XLA pairs donated buffers to outputs in flattened
            # output order, and the unfused step emits its outputs ahead
            # of the updated params/states — matching that order keeps
            # the fused program's buffer aliasing (and therefore its CPU
            # SPMD codegen) bitwise-identical to the replay arm's.
            return ({k: regs[k] for k in mat_idx},
                    {k: regs[k] for k in carried_idx})

        # 4. lower + compile-or-disk-load, keyed by the capture signature
        jitted = jax.jit(fused, donate_argnums=(0,))
        lowered = jitted.lower(dict(carry0), tuple(feeds0))
        sigparts = []
        for i, (sig, deps) in enumerate(ops):
            sigparts.append((sig[1], sig[0], fuses[i].fingerprint,
                             in_idx[i], out_idx[i], deps, avals[i]))
        carry_avals = tuple(
            (k, tuple(self._aval(l)
                      for l in jax.tree_util.tree_leaves(carry0[k])))
            for k in sorted(carry0))
        from . import progcache as _progcache
        from .analysis import compile_witness as _witness
        need_text = any(f.fingerprint is None for f in fuses)
        key = _progcache.fused_key(
            repr((sigparts, carry_avals)),
            lowered.as_text() if need_text else None)
        self.signature = key
        exe = (_progcache.load(key, kind="fused")
               if _progcache.enabled() else None)
        if exe is not None:
            _fuse_stats["disk_loads"] += 1
        else:
            exe = lowered.compile()
            _fuse_stats["compiles"] += 1
            _witness.record_compile("fused", key=key[:16])
            if _progcache.enabled():
                _progcache.store(key, exe, note="fused:%s" % name,
                                 kind="fused")
        self._exe = exe
        self._carry = carry0
        self._san_seen = None
        _log.info("engine fuse '%s': staged %d op(s) into one program "
                  "(%d live-in, %d materialized, key %s…)", name,
                  len(ops), len(carried), len(mat_vars), key[:12])

    @staticmethod
    def _aval(leaf):
        # (shape, dtype, sharding) — the sharding leg keys the staged
        # program (and its progcache entry) to the carry placement so
        # ZeRO stage-1/2/3 runs fuse instead of bailing; see
        # ``_sharding_sig``. None everywhere on the unsharded path.
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return (tuple(leaf.shape), str(leaf.dtype),
                    _sharding_sig(leaf))
        import numpy as np
        a = np.asarray(leaf)
        return (tuple(a.shape), str(a.dtype), None)

    def _eval_feeds(self, fuses) -> tuple:
        import jax
        vals = []
        for i, f in enumerate(fuses):
            fv = tuple(f.feed()) if callable(f.feed) else tuple(f.feed)
            leaves, treedef = jax.tree_util.tree_flatten(fv)
            if treedef != self._feed_defs[i] or \
                    tuple(self._aval(l) for l in leaves) \
                    != self._feed_avals[i]:
                raise _FuseBail(
                    "feed for op %d drifted from the staged shapes/dtypes"
                    % i)
            vals.append(fv)
        return tuple(vals)

    def san_check(self, ops):
        """Sanitizer validation: the declared edge set (transitively, with
        program order inside the one fused push) must dominate the full
        conflict-predecessor map — the same contract replay's
        ``on_replay_child`` enforces dynamically."""
        san = _san
        if san is None or self._san_seen is san:
            return
        self._san_seen = san
        conf = _Sanitizer.replay_conflicts(ops)
        reach: List[set] = []
        for i, (_sig, deps) in enumerate(ops):
            r = set(deps)
            for d in deps:
                r |= reach[d]
            reach.append(r)
        for i, cset in enumerate(conf):
            for j in cset:
                if j not in reach[i]:
                    sig_i, sig_j = ops[i][0], ops[j][0]
                    shared = sorted(
                        ({int(v) for v in sig_i[3]}
                         | {int(v) for v in sig_i[4]})
                        & ({int(v) for v in sig_j[3]}
                           | {int(v) for v in sig_j[4]}))
                    san._emit(san._mk(
                        "fused-edge-violation",
                        shared[0] if shared else -1, sig_i[1],
                        "%s[%d]" % (self.name, i), sig_j[1],
                        "%s[%d]" % (self.name, j),
                        detail="fused program's declared edge set does "
                               "not dominate the conflict between ops "
                               "%d and %d (shared vars %r)"
                               % (i, j, shared)))

    def run_iteration(self, fuses):
        """Execute one iteration (engine worker, inside the fused push).
        Raises :class:`_FuseBail` before any side effect when the
        iteration can still be replayed; lets writeback errors propagate
        (results are already partially published — replaying would
        double-apply)."""
        feeds = self._pending_feeds
        if feeds is not None:
            self._pending_feeds = None
        else:
            feeds = self._eval_feeds(fuses)
        try:
            mats, new_carry = self._exe(self._carry, feeds)
        except Exception as e:
            raise _FuseBail("fused executable failed: %s" % e)
        self._carry = new_carry
        regs = dict(new_carry)
        regs.update(mats)
        for i in self._wb_ops:
            wb = fuses[i].writeback
            if wb is not None:
                wb({v: regs[self._var_idx[v]] for v in self._out_vars[i]
                    if self._var_idx[v] in regs})


class CapturedSequence:
    """Record a steady-state push sequence once, replay it with near-zero
    host overhead.

    Protocol — the owning thread brackets each iteration::

        cs = engine.CapturedSequence(name="fit_step")
        for batch in loader:
            cs.begin_step()
            cs.push(load_fn, mutable_vars=[data_var], name="load")
            cs.push(step_fn, const_vars=[data_var],
                    mutable_vars=[step_var], name="step")
            cs.end_step()

    For the first ``warmup`` iterations every push forwards eagerly
    through the module-level :func:`push`/:func:`push_async` (so behavior
    is identical to not capturing) while the ``(is_async, name, priority,
    const_vars, mutable_vars)`` signature stream is recorded. If all
    warmup iterations produced the SAME signature stream, the sequence
    compiles: per-op ``_dedup`` runs once, RAW/WAR/WAW edges between the
    recorded ops are resolved into a static dependency list, and the
    union of all vars becomes the replay submission's var set. If the
    stream was unstable (different ops or different var topology across
    iterations) the sequence **bails to eager** with a logged reason and
    stays eager until :meth:`invalidate` is called.

    A compiled iteration is submitted by ``end_step()`` as ONE
    module-level :func:`push_async` — so per-var in-flight accounting
    counts the replay's vars exactly once per replay, :func:`fence` over
    any of the union vars orders after the whole replay (including its
    async children's ``on_complete``), and file vars in the recorded
    signatures keep their write ordering. Inside the replay op the
    recorded ops run in recorded order on one engine worker, waiting only
    on precomputed edges to async predecessors — no per-op ``_dedup``, no
    scheduler-queue lock, no ctypes marshalling.

    If a replayed iteration deviates from the recording (different op at
    slot i, or fewer/more ops), the already-matched prefix is flushed
    eagerly in order, the rest of the iteration runs eagerly, and the
    sequence returns to capturing — a mismatch never loses or reorders
    an op.

    Threading: one thread drives ``begin_step``/``push``/``end_step``;
    :meth:`invalidate` may be called from any thread (e.g. a retune op on
    an engine worker) — it sets a flag consumed at the next
    ``begin_step``. ``_lock`` is a declared leaf (rank 100): no call
    leaves the package while it is held.
    """

    def __init__(self, name: str = "seq", warmup: Optional[int] = None,
                 fuse: Optional[bool] = None):
        self._name = name
        self._warmup = max(2, warmup) if warmup is not None \
            else capture_warmup()
        self._lock = threading.Lock()
        # state: "capture" (recording + eager), "ready" (replaying),
        # "flush" (mid-step after a mismatch: eager, not recording),
        # "eager" (bailed on unstable warmup: eager until invalidate())
        self._state = "capture"
        self._iters: List[list] = []     # signature stream per warmup iter
        self._cur: Optional[list] = None
        self._ops: Optional[List[tuple]] = None  # [(sig, deps), ...]
        self._union: Tuple[tuple, tuple] = ((), ())
        self._slots: List[Callable] = []
        self._invalid_reason: Optional[str] = None
        self.replays = 0
        self.bails = 0
        # trace-and-fuse (MXNET_ENGINE_FUSE; None = read env at use time):
        # _fuse_state is None (unstaged) / "staged" / "ineligible" / "dead";
        # _fused holds the staged FusedSequence while "staged"
        self._fuse_opt = fuse
        self._fuse_state: Optional[str] = None
        self._fused: Optional[FusedSequence] = None
        self._fuse_slots: List[Optional[FuseOp]] = []
        self.fused_runs = 0
        self.fuse_bails = 0

    @property
    def name(self) -> str:
        return self._name

    @property
    def warmup(self) -> int:
        return self._warmup

    @property
    def state(self) -> str:
        return self._state

    def invalidate(self, reason: str):
        """Discard the recording at the next ``begin_step`` (thread-safe;
        an already-submitted replay is unaffected — its vars and closures
        were frozen at submission)."""
        with self._lock:
            if self._invalid_reason is None:
                self._invalid_reason = reason

    # -- step bracketing ------------------------------------------------

    def begin_step(self):
        reason = None
        with self._lock:
            if self._invalid_reason is not None:
                reason = self._invalid_reason
                self._invalid_reason = None
                self._reset_locked()
            elif self._state == "flush":  # caller skipped end_step
                self._reset_locked()
            if self._state == "ready":
                self._slots = []
                self._fuse_slots = []
            elif self._state == "capture":
                self._cur = []
        if reason is not None:
            _log.info("engine capture '%s': invalidated (%s), recapturing",
                      self._name, reason)

    def end_step(self):
        st = self._state
        if st == "ready":
            with self._lock:
                slots, self._slots = self._slots, []
                fuses, self._fuse_slots = self._fuse_slots, []
            if len(slots) != len(self._ops):
                self._flush_eager(
                    slots, "iteration ended after %d of %d recorded ops"
                    % (len(slots), len(self._ops)))
                with self._lock:
                    self._reset_locked()
                return
            if self._fuse_wanted():
                with self._lock:
                    fstate = self._fuse_state
                if fstate is None:
                    self._stage_fuse(fuses)
                    with self._lock:
                        fstate = self._fuse_state
                if fstate == "staged":
                    if all(f is not None for f in fuses):
                        self._submit_fused(slots, fuses)
                        self.fused_runs += 1
                        return
                    # a recorded slot lost its metadata mid-stream: the
                    # staged registers would go stale — kill the program
                    # and fall through to replay
                    self._fuse_dead("a slot was pushed without fuse "
                                    "metadata", "run")
            self._submit_replay(slots)
            self.replays += 1
        elif st == "capture":
            cur, self._cur = self._cur, None
            if cur is not None:
                self._iters.append(cur)
                if len(self._iters) >= self._warmup:
                    self._compile()
        elif st == "flush":
            with self._lock:
                self._reset_locked()

    # -- pushes ---------------------------------------------------------

    def push(self, fn: Callable[[], None], const_vars: Sequence[int] = (),
             mutable_vars: Sequence[int] = (), priority: int = 0,
             name: str = "op", fuse: Optional[FuseOp] = None):
        """Sync push routed through the capture state machine. ``fuse``
        carries the op's traceable metadata (trace-and-fuse); ``None``
        marks the op non-traceable, keeping the sequence on replay."""
        self._push(False, fn, const_vars, mutable_vars, priority, name,
                   fuse)

    def push_async(self, fn: Callable[[Callable[[], None]], None],
                   const_vars: Sequence[int] = (),
                   mutable_vars: Sequence[int] = (), priority: int = 0,
                   name: str = "op", fuse: Optional[FuseOp] = None):
        """Async push routed through the capture state machine. ``fuse``
        as in :meth:`push` — a fused iteration publishes the op's effects
        through ``FuseOp.writeback`` instead of running ``fn``."""
        self._push(True, fn, const_vars, mutable_vars, priority, name,
                   fuse)

    def _push(self, is_async, fn, const_vars, mutable_vars, priority, name,
              fuse=None):
        sig = (is_async, name, int(priority),
               tuple(const_vars), tuple(mutable_vars))
        st = self._state
        if st == "ready":
            i = len(self._slots)
            if i < len(self._ops) and self._ops[i][0] == sig:
                self._slots.append(fn)
                self._fuse_slots.append(fuse)
                return
            with self._lock:
                slots, self._slots = self._slots, []
                self._fuse_slots = []
                self._state = "flush"
            self._flush_eager(
                slots, "op %d is %r, recorded %r" % (
                    i, name,
                    self._ops[i][0][1] if i < len(self._ops) else "<end>"))
        elif st == "capture":
            if self._cur is not None:
                self._cur.append(sig)
        # capture warmup, flush, and bailed-eager all forward eagerly
        if is_async:
            push_async(fn, const_vars, mutable_vars, priority, name)
        else:
            push(fn, const_vars, mutable_vars, priority, name)

    # -- internals ------------------------------------------------------

    def _reset_locked(self):
        self._state = "capture"
        self._iters = []
        self._cur = None
        self._ops = None
        self._slots = []
        self._fuse_slots = []
        self._fuse_state = None
        self._fused = None

    def _flush_eager(self, slots, why):
        """Replay deviated: run the already-matched prefix eagerly, in
        recorded order, so nothing is lost or reordered."""
        self.bails += 1
        _log.info("engine capture '%s': replay mismatch (%s); flushing %d "
                  "op(s) eagerly and recapturing", self._name, why,
                  len(slots))
        for j, fn in enumerate(slots):
            s_async, s_name, s_pri, s_const, s_mut = self._ops[j][0]
            if s_async:
                push_async(fn, s_const, s_mut, s_pri, s_name)
            else:
                push(fn, s_const, s_mut, s_pri, s_name)

    def _compile(self):
        """All warmup iterations observed: verify stability, resolve the
        dependency edges once, or bail to eager."""
        first = self._iters[0]
        if not first:
            self._iters = []  # empty steps: nothing to replay, keep looking
            return
        for k, it in enumerate(self._iters[1:], 1):
            if it != first:
                with self._lock:
                    self._state = "eager"
                    self._iters = []
                self.bails += 1
                _log.info(
                    "engine capture '%s': unstable across warmup (iteration "
                    "%d has %d op(s), first had %d; or var topology "
                    "changed) — staying eager until invalidated",
                    self._name, k, len(it), len(first))
                return
        ops = []
        last_writer: Dict[int, int] = {}
        readers_since: Dict[int, list] = {}
        union_mut: Dict[int, None] = {}
        union_const: Dict[int, None] = {}
        for i, sig in enumerate(first):
            const, mut = _dedup(sig[3], sig[4])  # per-op _dedup, done ONCE
            deps = set()
            for v in const:
                if v in last_writer:
                    deps.add(last_writer[v])            # RAW
            for v in mut:
                if v in last_writer:
                    deps.add(last_writer[v])            # WAW
                deps.update(readers_since.get(v, ()))   # WAR
            for v in const:
                readers_since.setdefault(v, []).append(i)
                union_const.setdefault(v)
            for v in mut:
                last_writer[v] = i
                readers_since[v] = []
                union_mut.setdefault(v)
            ops.append((sig, tuple(sorted(deps))))
        u_mut = tuple(union_mut)
        u_const = tuple(v for v in union_const if v not in union_mut)
        with self._lock:
            self._ops = ops
            self._union = (u_const, u_mut)
            self._iters = []
            self._state = "ready"
        _log.info("engine capture '%s': captured %d op(s) over %d vars, "
                  "replaying", self._name, len(ops),
                  len(u_const) + len(u_mut))

    def _submit_replay(self, slots):
        """Submit one iteration as a single module-level push_async. The
        union var set makes fence()/in-flight/file-var semantics hold for
        the whole sequence; inside, ops run in recorded order waiting
        only on precomputed edges to async predecessors."""
        ops = self._ops
        seq_name = self._name

        def replay(on_complete, _slots=slots, _ops=ops):
            tok = _telemetry.begin("engine.replay", domain="engine",
                                   ops=len(_ops), sequence=seq_name) \
                if _telemetry.enabled("engine") else None
            self._replay_children(_slots, _ops, seq_name)
            if tok is not None:
                _telemetry.end(tok)
            on_complete()

        push_async(replay, self._union[0], self._union[1],
                   name="replay:%s" % seq_name)

    @staticmethod
    def _replay_children(slots, ops, seq_name):
        """Run one iteration's recorded ops in order on the current engine
        worker, waiting only on the precomputed edges to async
        predecessors — the body of a replay submission, shared with the
        fused path's bail-to-replay fallback."""
        on_engine = _telemetry.enabled("engine")
        san = _san  # read once per replay: tests may toggle mid-run
        conf = san.replay_conflicts(ops) if san is not None else None
        events: List[Optional[threading.Event]] = [None] * len(ops)
        for i, (sig, deps) in enumerate(ops):
            is_async, opname = sig[0], sig[1]
            for d in deps:
                ev = events[d]
                if ev is not None:  # sync deps completed in program order
                    ev.wait()
            if conf is not None:
                # after the declared-edge waits, every conflicting
                # predecessor must already be done — or an edge is missing
                san.on_replay_child(seq_name, i, ops, conf, events)
            fn = slots[i]
            try:
                if is_async:
                    done_ev = threading.Event()
                    events[i] = done_ev
                    if on_engine:
                        optok = _telemetry.begin(opname, domain="engine",
                                                 replay=True)

                        def done(_ev=done_ev, _t=optok):
                            _telemetry.end(_t)
                            _ev.set()
                    else:
                        done = done_ev.set
                    fn(done)
                else:
                    if on_engine:
                        with _telemetry.span(opname, domain="engine",
                                             replay=True):
                            fn()
                    else:
                        fn()
            except Exception as e:  # mirror _dispatch: never escape the op
                traceback.print_exc()
                _notify_op_error(opname, e)
                if events[i] is not None:
                    events[i].set()
        # the submission completes only when every child has: that is
        # what keeps fence()/in-flight release correct under replay
        for ev in events:
            if ev is not None:
                ev.wait()

    # -- trace-and-fuse -------------------------------------------------

    def _fuse_wanted(self) -> bool:
        return self._fuse_opt if self._fuse_opt is not None \
            else fuse_enabled()

    def _fuse_dead(self, why: str, kind: str):
        with self._lock:
            self._fused = None
            self._fuse_state = "dead"
        self.fuse_bails += 1
        _count_fuse_bail(kind)
        _log.info("engine fuse '%s': %s; falling back to replay until the "
                  "sequence recaptures", self._name, why)

    def _stage_fuse(self, fuses):
        """First ready iteration with fusing requested: lower the recorded
        sequence into a FusedSequence, or mark why it cannot be."""
        try:
            missing = [i for i, f in enumerate(fuses) if f is None]
            if missing:
                raise _FuseIneligible(
                    "op(s) %s (%s) carry no traceable metadata"
                    % (missing,
                       ", ".join(self._ops[i][0][1] for i in missing)))
            prog = FusedSequence(self._name, self._ops, fuses, self._union)
        except _FuseIneligible as e:
            with self._lock:
                self._fuse_state = "ineligible"
            self.fuse_bails += 1
            _count_fuse_bail("ineligible")
            _log.info("engine fuse '%s': ineligible (%s); staying on "
                      "replay", self._name, e)
        except Exception:
            with self._lock:
                self._fuse_state = "dead"
            self.fuse_bails += 1
            _count_fuse_bail("stage")
            _log.warning("engine fuse '%s': staging failed; staying on "
                         "replay", self._name, exc_info=True)
        else:
            with self._lock:
                self._fused = prog
                self._fuse_state = "staged"

    def _submit_fused(self, slots, fuses):
        """Submit one iteration as a single module-level push_async running
        the staged program — same union var set as replay, so fences,
        in-flight accounting (one count) and async-completion semantics
        are unchanged. A pre-execution bail replays the iteration's
        recorded closures inline on the same worker."""
        prog = self._fused
        ops = self._ops
        seq_name = self._name
        prog.san_check(ops)

        def fused_run(on_complete, _slots=slots, _fuses=fuses, _prog=prog,
                      _ops=ops):
            tok = _telemetry.begin("engine.fused_run", domain="engine",
                                   ops=len(_ops), sequence=seq_name,
                                   signature=_prog.signature[:12]) \
                if _telemetry.enabled("engine") else None
            try:
                _prog.run_iteration(_fuses)
                _fuse_stats["runs"] += 1
                _fused_runs_counter.inc()
            except _FuseBail as e:
                # nothing was published yet: the iteration replays whole
                self._fuse_dead("bailed (%s)" % e, "run")
                self._replay_children(_slots, _ops, seq_name)
            except Exception as e:
                # a writeback failed mid-publish: replaying could double-
                # apply effects, so surface it like any failed engine op
                self._fuse_dead("writeback failed (%s)" % e, "error")
                traceback.print_exc()
                _notify_op_error("fused:%s" % seq_name, e)
            finally:
                if tok is not None:
                    _telemetry.end(tok)
                on_complete()

        push_async(fused_run, self._union[0], self._union[1],
                   name="fused:%s" % seq_name)


# --- happens-before sanitizer (MXNET_ENGINE_SANITIZER) -----------------------
# Dynamic half of mxnet_tpu.analysis.racecheck: with MXNET_ENGINE_SANITIZER=1
# (or sanitizer_enable()), every module-level push is checked against shadow
# epochs per engine var. Host state registered with guard_state(obj, var) is
# found by a bounded reachability scan over the pushed fn (closure cells,
# defaults, functools.partial, bound-method instances — one helper level
# deep); reaching it without declaring its var, while a prior access is not
# yet settled by a fence/wait on that var, is a race: the engine has no edge
# ordering the two ops. Checks run at push time only — op fns execute exactly
# as without the sanitizer (so MXNET_FAULT_PLAN composes untouched). Replays
# additionally validate that CapturedSequence's pre-resolved edge set
# dominates the conflict set: when a child starts, every conflicting async
# predecessor's done-event must already be set (declared edges + program
# order make that transitively true iff no edge is missing).
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

    # -- replay validation ---------------------------------------------------
    @staticmethod
    def replay_conflicts(ops):
        """Full conflict-predecessor map over a captured sequence: for each
        child, every earlier child sharing a var with at least one writer.
        The pre-resolved edge set must dominate this."""
        conf = []
        writers: Dict[int, List[int]] = {}
        readers: Dict[int, List[int]] = {}
        for i, (sig, _deps) in enumerate(ops):
            const, mutv = _dedup(sig[3], sig[4])
            c = set()
            for v in const:
                c.update(writers.get(v, ()))
            for v in mutv:
                c.update(writers.get(v, ()))
                c.update(readers.get(v, ()))
            conf.append(tuple(sorted(c)))
            for v in const:
                readers.setdefault(v, []).append(i)
            for v in mutv:
                writers.setdefault(v, []).append(i)
                readers[v] = []
        return conf

    def on_replay_child(self, seq, i, ops, conf, events):
        for j in conf[i]:
            ev = events[j]
            if ev is None or ev.is_set():
                continue  # sync child (done in program order) or completed
            sig_i, sig_j = ops[i][0], ops[j][0]
            shared = sorted(
                ({int(v) for v in sig_i[3]} | {int(v) for v in sig_i[4]})
                & ({int(v) for v in sig_j[3]} | {int(v) for v in sig_j[4]}))
            self._emit(self._mk(
                "replay-edge-violation", shared[0] if shared else -1,
                sig_i[1], "%s[%d]" % (seq, i), sig_j[1], "%s[%d]" % (seq, j),
                detail="replay child %d starts before conflicting async "
                       "child %d completed (shared vars %r): pre-resolved "
                       "edges do not dominate the access set" % (i, j,
                                                                 shared)))

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
