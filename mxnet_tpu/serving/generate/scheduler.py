"""Orca-style iteration-level scheduler for continuous-batching decode.

One scheduler thread drives every replica: each loop iteration it
(1) expires/admits waiting prefills into freed slots, (2) pushes ONE
fixed-shape decode step per occupied replica onto the engine
(``mutable_vars=[kv var]`` — the engine's dependency ordering serializes
step N+1 after step N and after any admits between them), (3) fences,
samples greedily on the host, streams tokens out, and retires finished
sequences — so the batch is re-formed **every step** as sequences finish
and new ones join mid-flight.

Compile discipline: all device work goes through the fixed
``DecodePrograms`` set (prefill ladder + one decode step + one admit per
replica), so steady state compiles nothing regardless of traffic shape.

Paged mode (``MXNET_DECODE_PAGED=1``, PR 13): the same loop drives
``PagedDecodePrograms`` + ``PagedKVCacheManager`` — admission goes
through ``try_admit`` (block reservation + prefix-hash lookup, returning
an ``AdmitPlan``), the prefill op becomes one fused paged-prefill
program (CoW fork + cached-prefix attention + suffix scatter), and the
decode step carries each row's block table as an extra fixed-shape arg.
The unpaged path is untouched and remains the bitwise-reference arm.

Lock discipline (declared in ``analysis/lockorder.py``):
``DecodeScheduler._cond`` has rank 50 — engine pushes and fences
(``engine._engine_lock``, rank 20) NEVER happen while it is held;
``TokenStream._cond``, ``KVCacheManager._lock`` and
``PagedKVCacheManager._lock`` are leaves (rank 100) and may be taken
under it.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ... import engine as _engine
from ... import telemetry as _telemetry
from ...telemetry import context as _trace_context
from ...telemetry import flight as _flight
from ..metrics import latency_histogram as _latency_histogram
from ...analysis import compile_witness as _witness
from ..batcher import ServingError
from .kv_cache import KVCacheManager
from .model import DecodeModel
from .paged import PagedKVCacheManager
from .programs import DecodePrograms, PagedDecodePrograms
from .spec import SpecDecoder, sample_token
from .stream import TokenStream


def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_flag(name, default):
    return os.environ.get(name, default).lower() \
        not in ("0", "", "false", "off")


def _env_buckets():
    raw = os.environ.get("MXNET_DECODE_PREFILL_BUCKETS", "8,16,32")
    try:
        return tuple(sorted({int(b) for b in raw.split(",") if b.strip()}))
    except ValueError:
        return (8, 16, 32)


def _env_eos():
    raw = os.environ.get("MXNET_DECODE_EOS", "")
    try:
        return int(raw) if raw.strip() else None
    except ValueError:
        return None


@dataclasses.dataclass
class GenerateConfig:
    """Decode-side knobs; every default reads its ``MXNET_DECODE_*`` env
    var at construction time (docs/env_var.md has the table). Head counts
    have no env default — they are architecture facts of the checkpoint."""
    num_heads: int
    num_kv_heads: int = 0
    slots: int = dataclasses.field(
        default_factory=lambda: _env_int("MXNET_DECODE_SLOTS", 4))
    max_context: int = dataclasses.field(
        default_factory=lambda: _env_int("MXNET_DECODE_MAX_CONTEXT", 64))
    prefill_buckets: Tuple[int, ...] = dataclasses.field(
        default_factory=_env_buckets)
    max_new_tokens: int = dataclasses.field(
        default_factory=lambda: _env_int("MXNET_DECODE_MAX_NEW_TOKENS", 32))
    queue_depth: int = dataclasses.field(
        default_factory=lambda: _env_int("MXNET_DECODE_QUEUE_DEPTH", 64))
    eos_id: Optional[int] = dataclasses.field(default_factory=_env_eos)
    rope_base: float = 10000.0
    # paged KV (PR 13): block pool + prefix reuse; 0 blocks = auto-size
    # to byte parity with the unpaged config (slots * ceil(capacity/T))
    paged: bool = dataclasses.field(
        default_factory=lambda: _env_flag("MXNET_DECODE_PAGED", "0"))
    block_tokens: int = dataclasses.field(
        default_factory=lambda: _env_int("MXNET_DECODE_BLOCK_TOKENS", 16))
    num_blocks: int = dataclasses.field(
        default_factory=lambda: _env_int("MXNET_DECODE_BLOCKS", 0))
    prefix_share: bool = dataclasses.field(
        default_factory=lambda: _env_flag("MXNET_DECODE_PREFIX_SHARE", "1"))
    # low-precision serving (PR 14): KV slab dtype (f32|bf16|int8 —
    # normalized by mxnet_tpu.quant at scheduler construction) and weight
    # PTQ opt-in ("" = off; "int8"/"fp8" quantizes the DecodeModel)
    kv_dtype: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "MXNET_DECODE_KV_DTYPE", "f32"))
    quant_weights: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "MXNET_QUANT_WEIGHT_DTYPE", ""))
    # speculative decoding (PR 16): draft-k-then-verify. spec_tokens = k
    # drafted per iteration; spec_draft picks the int8 self-draft
    # ("int8", quantize_decode_model) or the same-precision model
    # ("self" — the upper bound on acceptance, no quality gap)
    spec: bool = dataclasses.field(
        default_factory=lambda: _env_flag("MXNET_DECODE_SPEC", "0"))
    spec_tokens: int = dataclasses.field(
        default_factory=lambda: _env_int("MXNET_DECODE_SPEC_TOKENS", 4))
    spec_draft: str = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "MXNET_DECODE_SPEC_DRAFT", "int8"))


class _Active:
    """One sequence occupying a slot. ``temperature``/``rng`` carry the
    per-stream sampling context (temperature 0 = greedy, rng unused —
    a private RandomState per stream keeps draws deterministic per seed
    and independent of scheduling order across streams)."""
    __slots__ = ("stream", "replica", "slot", "last_token", "generated",
                 "temperature", "rng")

    def __init__(self, stream, replica, slot, last_token, generated,
                 temperature=0.0, rng=None):
        self.stream = stream
        self.replica = replica
        self.slot = slot
        self.last_token = last_token
        self.generated = generated
        self.temperature = temperature
        self.rng = rng


class DecodeScheduler:
    """Continuous-batching decode over one model across N replica slabs."""

    def __init__(self, model: DecodeModel, config: GenerateConfig,
                 replicas: int = 1):
        from ... import quant as _quant   # lazy — avoids an import cycle

        self.config = config
        kv_dtype = _quant.normalize_kv_dtype(config.kv_dtype)
        self.kv_dtype = kv_dtype
        if config.quant_weights and "wq_scale" not in model.params:
            model = _quant.quantize_decode_model(
                model, _quant.QuantConfig(
                    weight_dtype=config.quant_weights))
        self.model = model
        draft = None
        if config.spec:
            if config.spec_tokens < 1:
                raise ServingError("decode: spec_tokens must be >= 1")
            if config.spec_draft not in ("int8", "self"):
                raise ServingError(
                    "decode: unknown spec_draft %r (want int8|self)"
                    % config.spec_draft)
            if config.spec_draft == "int8" \
                    and "wq_scale" not in model.params:
                draft = _quant.quantize_decode_model(
                    model, _quant.QuantConfig(weight_dtype="int8"))
            else:
                # "self", or the target is already int8-quantized: the
                # draft IS the target — the step program is then byte-
                # identical to vanilla decode and shares its progcache
                # entry
                draft = model
        if config.paged:
            blocks = config.num_blocks or config.slots * (
                -(-config.max_context // config.block_tokens))
            self.programs: DecodePrograms = PagedDecodePrograms(
                model, config.slots, config.max_context,
                config.prefill_buckets, config.block_tokens, blocks,
                kv_dtype=kv_dtype, step_model=draft)
        else:
            self.programs = DecodePrograms(model, config.slots,
                                           config.max_context,
                                           config.prefill_buckets,
                                           kv_dtype=kv_dtype,
                                           step_model=draft)
        self._spec: Optional[SpecDecoder] = None
        if config.spec:
            self.programs.enable_verify(config.spec_tokens + 1)
            self._spec = SpecDecoder(self)
        self.replicas = int(replicas)
        self.caches: List[KVCacheManager] = []
        self._cond = threading.Condition()       # rank 50
        self._queue: deque = deque()             # (stream, prompt tokens)
        self._active: Dict[Tuple[int, int], _Active] = {}
        self._state = "stopped"                  # running|draining|stopped
        self._thread: Optional[threading.Thread] = None
        self.steps = 0
        # speculative-decode accounting (spec off: drafted stays 0 and
        # step_tokens == seq_steps, i.e. tokens/step is exactly 1.0)
        self.seq_steps = 0        # per-sequence step iterations
        self.step_tokens = 0      # tokens emitted by step iterations
        self.drafted_tokens = 0   # draft lanes eligible for acceptance
        self.accepted_tokens = 0  # draft lanes the target accepted
        reg = _telemetry.registry
        self._m_tokens = reg.counter(
            "decode_tokens_total", help="tokens emitted by decode streams")
        # explicit .set() (not fn=) — get_or_create would pin a stale
        # callback to a dead scheduler across server restarts
        self._m_occ = reg.gauge(
            "decode_batch_occupancy_pct",
            help="decode slots occupied, % (mean over replicas)")
        self._m_kv = reg.gauge(
            "kv_bytes", help="bytes held in decode KV slabs")
        # split-by-dtype twin of kv_bytes (the unlabeled gauge keeps its
        # historical meaning; capacity planning reads the labeled series)
        self._m_kv_dtype = reg.gauge(
            "kv_bytes", labels={"dtype": kv_dtype},
            help="bytes held in decode KV slabs")
        self._m_blocks_free = reg.gauge(
            "kv_blocks_free", labels={"decode_kv_dtype": kv_dtype},
            help="free KV blocks in the paged pool (sum over replicas)")
        self._m_blocks_total = reg.gauge(
            "kv_blocks_total", labels={"decode_kv_dtype": kv_dtype},
            help="usable KV blocks in the paged pool (sum over replicas)")
        self._m_prefix_hits = reg.counter(
            "decode_prefix_hits_total",
            help="admissions that reused a shared KV prefix")
        self._m_prefix_saved = reg.counter(
            "decode_prefix_tokens_saved_total",
            help="prompt tokens served from shared prefix blocks "
                 "instead of being re-prefilled")
        # explicit .set() from the scheduler loop, same staleness
        # rationale as decode_batch_occupancy_pct above
        self._m_accept_rate = reg.gauge(
            "decode_spec_accept_rate",
            help="speculative drafts accepted by the target model, "
                 "fraction of drafted tokens (0 when spec is off)")
        self._m_tokens_per_step = reg.gauge(
            "decode_tokens_per_step",
            help="tokens emitted per sequence per decode iteration "
                 "(vanilla decode: exactly 1.0)")

    # --- lifecycle --------------------------------------------------------
    def start(self):
        with self._cond:
            if self._state != "stopped":
                return
            self._state = "running"
        if self.config.paged:
            self.caches = [
                PagedKVCacheManager(self.programs, i,
                                    prefix_share=self.config.prefix_share)
                for i in range(self.replicas)]
            self._m_blocks_total.set(
                sum(c.blocks_total() for c in self.caches))
            self._m_blocks_free.set(
                sum(c.blocks_free() for c in self.caches))
        else:
            self.caches = [KVCacheManager(self.programs, i)
                           for i in range(self.replicas)]
        kv_total = sum(c.kv_bytes() for c in self.caches)
        self._m_kv.set(kv_total)
        self._m_kv_dtype.set(kv_total)
        self._thread = threading.Thread(target=self._loop,
                                        name="decode-scheduler", daemon=True)
        self._thread.start()

    def stop(self, drain: bool = False, deadline_ms: Optional[float] = None):
        """Stop the scheduler. ``drain=True`` finishes in-flight and queued
        streams first (refusing new submits, code ``shutting_down``);
        ``drain=False`` fails everything immediately (code ``shutdown``)."""
        with self._cond:
            if self._state == "stopped" and self._thread is None:
                return
            self._state = "draining" if drain else "stopped"
            self._cond.notify_all()
        t = self._thread
        if t is not None:
            timeout = None if deadline_ms is None else deadline_ms / 1000.0
            t.join(timeout)
            if t.is_alive():
                # drain deadline passed: force the loop out
                with self._cond:
                    self._state = "stopped"
                    self._cond.notify_all()
                t.join()
        self._thread = None
        code = "shutting_down" if drain else "shutdown"
        leftovers: List[TokenStream] = []
        with self._cond:
            self._state = "stopped"
            while self._queue:
                leftovers.append(self._queue.popleft()[0])
            actives, self._active = list(self._active.values()), {}
        for a in actives:
            self.caches[a.replica].free(a.slot)
            leftovers.append(a.stream)
        for s in leftovers:
            s._fail(ServingError("decode scheduler stopped", code=code))
        if self.caches:
            _engine.fence([c.var for c in self.caches]).wait()
            for c in self.caches:
                _engine.delete_variable(c.var)
        self.caches = []
        self._m_occ.set(0.0)

    # --- submission -------------------------------------------------------
    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None,
               timeout_ms: Optional[float] = None,
               temperature: float = 0.0,
               seed: Optional[int] = None,
               request_id: Optional[str] = None,
               trace=None) -> TokenStream:
        """Queue one prompt. ``temperature`` 0 (default) is greedy —
        bitwise the historical behavior; > 0 samples from the softmax
        with a per-stream RandomState seeded by ``seed`` (deterministic
        per seed, independent of co-resident streams). ``request_id``
        is carried on the TokenStream and annotated on decode spans so
        an HTTP SSE stream correlates with scheduler work."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ServingError("empty prompt", code="too_large")
        if self.programs.bucket_for(len(prompt)) is None:
            raise ServingError(
                "prompt length %d exceeds largest prefill bucket %d"
                % (len(prompt), self.programs.buckets[-1]), code="too_large")
        if len(prompt) >= self.programs.capacity:
            raise ServingError(
                "prompt length %d leaves no kv capacity (max_context %d)"
                % (len(prompt), self.programs.capacity), code="too_large")
        max_new = int(max_new_tokens or self.config.max_new_tokens)
        if max_new < 1:
            raise ServingError("max_new_tokens must be >= 1",
                               code="too_large")
        deadline = None if timeout_ms is None \
            else time.monotonic() + timeout_ms / 1000.0
        stream = TokenStream(len(prompt), max_new, deadline,
                             request_id=request_id,
                             trace=(trace if trace is not None else
                                    _trace_context.current_context()))
        temperature = float(temperature)
        rng = np.random.RandomState(seed) if temperature > 0.0 else None
        with self._cond:
            if self._state == "draining":
                raise ServingError("server is draining",
                                   code="shutting_down")
            if self._state != "running":
                raise ServingError("decode scheduler not running",
                                   code="shutdown")
            if len(self._queue) >= self.config.queue_depth:
                raise ServingError("decode queue full", code="queue_full")
            self._queue.append((stream, prompt, temperature, rng))
            self._cond.notify_all()
        return stream

    # --- scheduler loop ---------------------------------------------------
    def _loop(self):
        while True:
            with self._cond:
                while (self._state == "running" and not self._queue
                       and not self._active):
                    self._cond.wait(0.1)
                if self._state == "stopped":
                    return
                if (self._state == "draining" and not self._queue
                        and not self._active):
                    return
            self._expire_and_cancel()
            self._admit_waiting()
            self._step_all()
            occ = [c.occupancy_pct() for c in self.caches]
            self._m_occ.set(sum(occ) / max(1, len(occ)))
            if self.config.paged and self.caches:
                self._m_blocks_free.set(
                    sum(c.blocks_free() for c in self.caches))
            if self.seq_steps:
                self._m_tokens_per_step.set(
                    self.step_tokens / self.seq_steps)
            if self.drafted_tokens:
                self._m_accept_rate.set(
                    self.accepted_tokens / self.drafted_tokens)

    def _expire_and_cancel(self):
        now = time.monotonic()
        expired: List[TokenStream] = []
        cancelled: List[TokenStream] = []
        with self._cond:
            keep: deque = deque()
            for item in self._queue:
                s = item[0]
                if s.cancelled:
                    cancelled.append(s)
                elif s.deadline is not None and now > s.deadline:
                    expired.append(s)
                else:
                    keep.append(item)
            self._queue = keep
        for s in cancelled:
            s._finish("cancelled")
            self._stream_end(s, ok=True, code="cancelled")
        for s in expired:
            s._fail(ServingError("expired before a decode slot freed",
                                 code="deadline_exceeded"))
            self._stream_end(s, ok=False, code="deadline_exceeded",
                             queued=True)
        # active sequences: retire cancelled/expired before the next step
        for key, a in list(self._active.items()):
            if a.stream.cancelled:
                self._retire(a, reason="cancelled")
            elif a.stream.deadline is not None and now > a.stream.deadline:
                self._retire(a, error=ServingError(
                    "deadline exceeded mid-stream",
                    code="deadline_exceeded"))

    def _stream_end(self, stream: TokenStream, ok: bool,
                    code: Optional[str] = None, queued: bool = False):
        """Observability tail for one finished stream: the registry
        latency histogram (trace-id exemplar), the flight recorder's
        completed-request ring, and the deadline-miss bundle trigger.
        Called with no scheduler locks held."""
        lat_ms = (time.monotonic() - stream.submitted) * 1e3
        tr = stream.trace
        if (queued and tr is not None
                and _telemetry.enabled("serving")):
            # a stream that died waiting never got its queued span —
            # stamp one now so its flight timeline is complete
            _telemetry.complete("serving.queued", domain="serving",
                                start_ns=int(stream.submitted * 1e9),
                                tokens=stream.prompt_len, error=code,
                                **tr.child().stamps())
        if ok:
            _latency_histogram().observe(
                lat_ms, exemplar=tr.trace_id if tr is not None else None)
        _flight.request_end(tr, ok=ok, code=code, latency_ms=lat_ms,
                            kind="generate", request_id=stream.request_id)
        if code == "deadline_exceeded":
            _flight.on_anomaly("deadline_miss", tr,
                               request_id=stream.request_id,
                               latency_ms=lat_ms, kind="generate")

    def _retire(self, a: _Active, reason: Optional[str] = None,
                error: Optional[ServingError] = None):
        self.caches[a.replica].free(a.slot)
        with self._cond:
            self._active.pop((a.replica, a.slot), None)
        if error is not None:
            a.stream._fail(error)
            self._stream_end(a.stream, ok=False, code=error.code)
        else:
            a.stream._finish(reason or "eos")
            self._stream_end(a.stream, ok=True, code=reason or "eos")

    def _pick_replica(self) -> Optional[int]:
        best, best_free = None, 0
        for i, c in enumerate(self.caches):
            free = c.slots - len(c.active_slots())
            if free > best_free:
                best, best_free = i, free
        return best

    def _admit_waiting(self):
        """Prefill waiting prompts into free slots (unpaged) / free blocks
        (paged). Each admission is one engine op on the target replica's
        kv var (prefill → slot insert → first-token sample), fenced as a
        group so fresh sequences join the very next decode step. Paged
        plans may reuse a cached prefix: the op runs only the suffix, and
        a copy-on-write fork (fused into the same program) privatizes a
        partially-shared boundary block first."""
        admitted = []         # (active, holder)
        touched = []
        while True:
            rep = self._pick_replica()
            if rep is None:
                break
            with self._cond:
                if not self._queue:
                    break
                stream, prompt, temp, rng = self._queue.popleft()
            cache = self.caches[rep]
            plan = cache.try_admit(stream, prompt, stream.max_new_tokens)
            if plan is None:      # slots/blocks exhausted — wait for
                with self._cond:  # retirement, never evict mid-stream
                    self._queue.appendleft((stream, prompt, temp, rng))
                break
            # build the bucket's prefill program here (scheduler thread)
            # so the engine op never mutates the program dict — two
            # replicas' workers could otherwise race the lazy build
            self.programs.ensure_prefill(len(plan.suffix))
            if plan.ctx_len:
                self._m_prefix_hits.inc()
                self._m_prefix_saved.inc(plan.ctx_len)
            # trace plumbing: the queued span closes at admission; the
            # serving.dispatch span brackets push -> first token (stamped
            # post-fence); the prefill span nests under it via ts
            tr = stream.trace
            dctx, ts = None, None
            if tr is not None and _telemetry.enabled("serving"):
                _telemetry.complete("serving.queued", domain="serving",
                                    start_ns=int(stream.submitted * 1e9),
                                    tokens=len(prompt),
                                    **tr.child().stamps())
                dctx = tr.child()
                ts = dctx.child().stamps()
            holder: Dict[str, object] = {}
            admitted.append((_Active(stream, rep, plan.slot, 0, 0,
                                     temperature=temp, rng=rng), holder,
                             dctx, _telemetry.clock_ns()))
            touched.append(cache.var)

            if self.config.paged:
                def op(cache=cache, plan=plan, holder=holder,
                       rid=stream.request_id, ts=ts):
                    def run():
                        out = self.programs.paged_prefill(
                            cache.k_slab, cache.v_slab, plan.table,
                            plan.ctx_len, plan.suffix,
                            plan.fork_src, plan.fork_dst,
                            ks_slab=cache.k_scale, vs_slab=cache.v_scale)
                        cache.swap_slabs(*out[1:])
                        # sampled post-fence on the scheduler thread —
                        # the stream's rng is never touched off-thread
                        holder["logits"] = np.asarray(out[0])
                    try:
                        with _telemetry.span(
                                "decode.prefill", domain="serving",
                                tokens=len(plan.suffix),
                                reused=plan.ctx_len,
                                **(ts if ts is not None
                                   else {"request_id": rid})):
                            if plan.forked:
                                with _telemetry.span(
                                        "decode.cow_fork", domain="serving",
                                        src=plan.fork_src,
                                        dst=plan.fork_dst):
                                    run()
                            else:
                                run()
                    except Exception as e:      # noqa: BLE001
                        holder["error"] = e
            else:
                def op(cache=cache, plan=plan, holder=holder,
                       rid=stream.request_id, ts=ts):
                    try:
                        with _telemetry.span("decode.prefill",
                                             domain="serving",
                                             tokens=len(plan.suffix),
                                             **(ts if ts is not None
                                                else {"request_id": rid})):
                            pre = self.programs.prefill(plan.suffix)
                            if len(pre) == 5:   # int8 KV: + scale rows
                                last, k_new, v_new, ks_new, vs_new = pre
                                out = self.programs.admit(
                                    cache.k_slab, cache.v_slab, k_new,
                                    v_new, plan.slot,
                                    ks_slab=cache.k_scale,
                                    vs_slab=cache.v_scale,
                                    ks_new=ks_new, vs_new=vs_new)
                            else:
                                last, k_new, v_new = pre
                                out = self.programs.admit(
                                    cache.k_slab, cache.v_slab, k_new,
                                    v_new, plan.slot)
                            cache.swap_slabs(*out)
                            holder["logits"] = np.asarray(last)
                    except Exception as e:      # noqa: BLE001
                        holder["error"] = e

            _engine.push(op, mutable_vars=[cache.var], name="decode.prefill")
        if not admitted:
            return
        _engine.fence(touched).wait()
        for a, holder, dctx, t0 in admitted:
            err = holder.get("error")
            if err is not None:
                self.caches[a.replica].free(a.slot)
                a.stream._fail(ServingError(
                    "prefill failed: %s" % err, code="dispatch_error"))
                self._stream_end(a.stream, ok=False, code="dispatch_error")
                continue
            if dctx is not None:
                # the decode-path dispatch span: push -> first token,
                # parent of the prefill span recorded on the engine worker
                _telemetry.complete("serving.dispatch", domain="serving",
                                    start_ns=t0, kind="prefill",
                                    replica=a.replica, **dctx.stamps())
            with self._cond:
                self._active[(a.replica, a.slot)] = a
            self._emit(a, sample_token(holder["logits"], a.temperature,
                                       a.rng))

    def _emit(self, a: _Active, token: int, length: Optional[int] = None
              ) -> bool:
        """Deliver one sampled token; retire the sequence if done and
        return False once it has retired (the speculative path stops
        emitting a window's remaining tokens on eos). ``length`` is the
        committed kv length AFTER this token's predecessor landed —
        speculative emits pass it explicitly because the cache already
        holds the whole accepted run."""
        a.last_token = token
        a.generated += 1
        a.stream._emit(token)
        self._m_tokens.inc()
        eos = self.config.eos_id
        if eos is not None and token == eos:
            self._retire(a, reason="eos")
            return False
        if a.generated >= a.stream.max_new_tokens:
            self._retire(a, reason="max_tokens")
            return False
        if length is None:
            length = self.caches[a.replica].length(a.slot)
        if length >= self.programs.capacity:
            # the next step would write at kv position == capacity (the
            # write position IS the current length)
            self._retire(a, reason="capacity")
            return False
        return True

    def _step_all(self):
        """One decode step on every replica with occupied slots: push all
        step ops, fence once, then sample/stream on the host. With
        ``GenerateConfig.spec`` the iteration is the draft-k-then-verify
        loop in spec.py instead (same push/fence/emit skeleton, 1..k+1
        tokens per sequence)."""
        if self._spec is not None:
            self._spec.step_all()
            return
        stepped = []          # (replica, [active...], holder)
        touched = []
        with self._cond:
            by_rep: Dict[int, List[_Active]] = {}
            for (rep, _slot), a in self._active.items():
                by_rep.setdefault(rep, []).append(a)
        for rep, actives in sorted(by_rep.items()):
            cache = self.caches[rep]
            lengths = np.zeros(cache.slots, np.int32)
            tokens = np.zeros(cache.slots, np.int32)
            for a in actives:
                lengths[a.slot] = cache.length(a.slot)
                tokens[a.slot] = a.last_token
            # paged rows index kv through their block tables (freed rows
            # are all-trash: they write block 0 and read nothing unmasked)
            tables = cache.step_arrays()[1] if self.config.paged else None
            holder: Dict[str, object] = {}
            stepped.append((rep, actives, holder))
            touched.append(cache.var)
            # batch-level span: link every co-resident stream's trace so
            # each request's tree shows the decode steps it shared
            step_stamps = None
            if _telemetry.enabled("serving"):
                tids = [a.stream.trace.trace_id for a in actives
                        if a.stream.trace is not None]
                if tids:
                    step_stamps = {
                        "trace_ids": tids,
                        "span_id": _trace_context.mint_span_id()}

            def op(cache=cache, lengths=lengths, tokens=tokens,
                   tables=tables, holder=holder, ts=step_stamps):
                try:
                    with _telemetry.span("decode.step", domain="serving",
                                         rows=int((lengths > 0).sum()),
                                         **(ts or {})):
                        if tables is not None:
                            out = self.programs.decode(
                                cache.k_slab, cache.v_slab, tables,
                                lengths, tokens, ks_slab=cache.k_scale,
                                vs_slab=cache.v_scale)
                        else:
                            out = self.programs.decode(
                                cache.k_slab, cache.v_slab, lengths,
                                tokens, ks_slab=cache.k_scale,
                                vs_slab=cache.v_scale)
                        cache.swap_slabs(*out[1:])
                        holder["logits"] = np.asarray(out[0])
                except Exception as e:          # noqa: BLE001
                    holder["error"] = e

            _engine.push(op, mutable_vars=[cache.var], name="decode.step")
        if not stepped:
            return
        _engine.fence(touched).wait()
        self.steps += 1
        for rep, actives, holder in stepped:
            err = holder.get("error")
            if err is not None:
                # donation may have consumed the slabs — rebuild the
                # replica rather than risk stepping on poisoned state
                for a in actives:
                    self._retire(a, error=ServingError(
                        "decode step failed: %s" % err,
                        code="dispatch_error"))
                self.caches[rep].reset()
                continue
            logits = holder["logits"]
            for a in actives:
                self.caches[rep].advance(a.slot)
                self.seq_steps += 1
                self.step_tokens += 1
                self._emit(a, sample_token(logits[a.slot], a.temperature,
                                           a.rng))

    # --- introspection ----------------------------------------------------
    def stats(self) -> Dict[str, int]:
        with self._cond:
            queued = len(self._queue)
            active = len(self._active)
        # with the compile witness armed, the compile/disk split is read
        # back from the witness ledger (this program set's scope) so the
        # per-set stats and the process-wide counters share one source
        n_compiles, n_disk = self.programs.compiles, self.programs.disk_hits
        if _witness.enabled():
            sc = _witness.scope_counts(self.programs._witness_scope)
            n_compiles, n_disk = sc["compiles"], sc["disk_hits"]
        st = {"compiles": n_compiles,
              "disk_hits": n_disk,
              "steps": self.steps, "queued": queued, "active": active,
              "kv_dtype": self.kv_dtype,
              "quant_weights": self.config.quant_weights or "off",
              "seq_steps": self.seq_steps,
              "step_tokens": self.step_tokens,
              "drafted_tokens": self.drafted_tokens,
              "accepted_tokens": self.accepted_tokens,
              "spec": "%s k=%d" % (self.config.spec_draft,
                                   self.config.spec_tokens)
              if self.config.spec else "off"}
        if self.config.paged and self.caches:
            st["blocks_total"] = sum(c.blocks_total() for c in self.caches)
            st["blocks_free"] = sum(c.blocks_free() for c in self.caches)
            st["prefix_hits"] = sum(c.prefix_hits for c in self.caches)
            st["prefix_tokens_saved"] = sum(
                c.prefix_tokens_saved for c in self.caches)
            st["cow_forks"] = sum(c.cow_forks for c in self.caches)
        return st
