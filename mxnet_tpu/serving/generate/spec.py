"""Speculative decoding for the bounded-program decode engine.

Draft-k-then-verify (Leviathan et al., *Fast Inference from Transformers
via Speculative Decoding*): per scheduler iteration the DRAFT model —
``quantize_decode_model``'s int8 rewrite of the target by default — runs
``spec_tokens`` fixed-shape decode steps (the ordinary step program,
built from the draft's params via ``DecodePrograms(step_model=...)``),
then ONE fixed-shape verify program scores all k+1 window positions with
the TARGET model at once. Standard rejection sampling accepts 0..k draft
tokens plus a correction/bonus token, so the emitted stream follows the
target model's distribution EXACTLY regardless of draft quality (greedy
degenerates to longest-matching-prefix, which is what makes spec streams
token-identical to vanilla decode — the CI gate).

Program accounting: the draft step REPLACES the vanilla decode step (the
target never needs a 1-token program — the verify's accept-0 case IS a
vanilla step), so the paged program set stays at ladder + 2 and the
unpaged at ladder + 3 (its standalone admit rides along). Both are
progcache-keyed like everything else; a warm restart compiles nothing.

KV discipline: draft steps write draft-model K/V into the live slabs at
window positions (write position clamped to capacity − 1); the verify
attends under a strict per-row ``< length`` mask — the draft scratch is
invisible to it — and rewrites every window position with target-exact
K/V. After the verify the slabs hold target K/V through every committed
position, so rewind-on-reject is a pure host-side bookkeeping edit:
``truncate()`` on the cache manager (paged: a block-table/length edit;
unpaged: a length rollback), never a KV copy. ``keff`` additionally
clamps acceptance to the paged admission reservation, so a sequence
never allocates a block mid-stream — exactly the vanilla invariant.

Everything here runs inside the ONE engine op the scheduler pushes per
replica per iteration (``decode.draft``/``decode.verify`` spans nest
under ``decode.step``), so the sanitizer, fault plans and
``stop(drain=True)`` compose unchanged.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from ... import engine as _engine
from ... import telemetry as _telemetry
from ..batcher import ServingError


# --- sampling / acceptance math (host-side, f64) --------------------------
def _softmax64(logits, temperature: float) -> np.ndarray:
    """f64 softmax on the host — the one place sampling probabilities are
    computed, so vanilla and speculative paths share identical math."""
    z = np.asarray(logits, np.float64) / max(float(temperature), 1e-8)
    z = z - z.max()
    e = np.exp(z)
    return e / e.sum()


def _draw(probs: np.ndarray, rng) -> int:
    """One inverse-CDF draw (clamped against fp round-off in the cumsum
    tail)."""
    u = rng.random_sample()
    idx = int(np.searchsorted(np.cumsum(probs), u, side="right"))
    return min(idx, len(probs) - 1)


def sample_token(logits, temperature: float, rng) -> int:
    """Greedy argmax at temperature 0 (or without an rng), else one draw
    from the f64 softmax — shared by vanilla and speculative paths."""
    if temperature <= 0.0 or rng is None:
        return int(np.asarray(logits).argmax())
    return _draw(_softmax64(logits, temperature), rng)


def accept_greedy(draft: List[int], vlogits,
                  k_eff: int) -> Tuple[int, List[int]]:
    """Longest-matching-prefix acceptance: greedy rejection sampling
    degenerates to comparing each draft token with the target argmax.
    Returns ``(accepted, emitted)`` with ``len(emitted) == accepted + 1``
    — the final token is the target's correction (first mismatch) or
    bonus (whole window accepted), so every iteration advances ≥ 1
    token. ``k_eff == 0`` is exactly one vanilla greedy step."""
    emitted: List[int] = []
    for j in range(int(k_eff)):
        t = int(np.asarray(vlogits[j]).argmax())
        emitted.append(t)
        if t != int(draft[j]):
            return j, emitted
    emitted.append(int(np.asarray(vlogits[int(k_eff)]).argmax()))
    return int(k_eff), emitted


def accept_sampled(draft: List[int], draft_probs, vlogits, k_eff: int,
                   temperature: float, rng) -> Tuple[int, List[int]]:
    """Leviathan rejection sampling: accept draft ``d_j`` w.p.
    ``min(1, p[d]/q[d])``; the first rejection resamples from the
    residual ``max(p − q, 0)`` (falling back to ``p`` if the residual
    vanishes numerically); a fully-accepted window earns a bonus draw
    from the target's last position. The emitted marginals equal the
    target model's distribution exactly, regardless of draft quality."""
    emitted: List[int] = []
    for j in range(int(k_eff)):
        p = _softmax64(vlogits[j], temperature)
        q = np.asarray(draft_probs[j], np.float64)
        d = int(draft[j])
        if rng.random_sample() < min(1.0, p[d] / max(q[d], 1e-300)):
            emitted.append(d)
            continue
        resid = np.maximum(p - q, 0.0)
        s = resid.sum()
        emitted.append(_draw(resid / s if s > 0.0 else p, rng))
        return j, emitted
    emitted.append(_draw(_softmax64(vlogits[int(k_eff)], temperature), rng))
    return int(k_eff), emitted


# --- the scheduler's speculative step loop --------------------------------
class SpecDecoder:
    """One instance per ``DecodeScheduler`` when ``GenerateConfig.spec``
    is on. Owns no state beyond the back-reference — all bookkeeping
    stays on the scheduler and cache managers, so stats, drain and the
    poisoned-step recovery path are the vanilla code paths."""

    def __init__(self, sched):
        self.sched = sched
        self.k = int(sched.config.spec_tokens)

    def step_all(self):
        """One draft-k-then-verify iteration on every occupied replica:
        ONE engine op per replica (k draft dispatches + the verify +
        host acceptance, all inside), one fence, then commit — truncate
        the cache to the accepted length and emit 1..k+1 tokens."""
        sched = self.sched
        k = self.k
        cap = sched.programs.capacity
        stepped = []          # (replica, [active...], holder)
        touched = []
        with sched._cond:
            by_rep: Dict[int, list] = {}
            for (rep, _slot), a in sched._active.items():
                by_rep.setdefault(rep, []).append(a)
        for rep, actives in sorted(by_rep.items()):
            actives.sort(key=lambda a: a.slot)
            cache = sched.caches[rep]
            n0 = np.zeros(cache.slots, np.int32)
            t0 = np.zeros(cache.slots, np.int32)
            keff = np.zeros(cache.slots, np.int32)
            for a in actives:
                n0[a.slot] = cache.length(a.slot)
                t0[a.slot] = a.last_token
                # emit ≤ keff+1 tokens: stay within max_new_tokens AND
                # within capacity/admission reservation, so accepted
                # positions never need a block beyond what try_admit
                # reserved (rewind is then a pure length edit)
                remaining = a.stream.max_new_tokens - a.generated
                keff[a.slot] = max(0, min(k, remaining - 1,
                                          cap - 1 - int(n0[a.slot])))
            active = n0 > 0
            tables = cache.step_arrays()[1] if sched.config.paged else None
            # per-row sampling context, consumed inside the op — safe:
            # the scheduler fences before touching these streams again
            samplers = {a.slot: (a.temperature, a.rng) for a in actives
                        if a.temperature > 0.0 and a.rng is not None}
            holder: Dict[str, object] = {}
            stepped.append((rep, actives, holder))
            touched.append(cache.var)

            def op(cache=cache, n0=n0, t0=t0, keff=keff, tables=tables,
                   samplers=samplers, active=active, holder=holder):
                try:
                    with _telemetry.span("decode.step", domain="serving",
                                         rows=int(active.sum()),
                                         spec=k):
                        self._speculate(cache, n0, t0, keff, tables,
                                        samplers, active, holder)
                except Exception as e:          # noqa: BLE001
                    holder["error"] = e

            _engine.push(op, mutable_vars=[cache.var], name="decode.step")
        if not stepped:
            return
        _engine.fence(touched).wait()
        sched.steps += 1
        for rep, actives, holder in stepped:
            err = holder.get("error")
            if err is not None:
                # donation may have consumed the slabs mid-iteration —
                # rebuild the replica (the vanilla recovery path)
                for a in actives:
                    sched._retire(a, error=ServingError(
                        "decode step failed: %s" % err,
                        code="dispatch_error"))
                sched.caches[rep].reset()
                continue
            res = holder["res"]
            cache = sched.caches[rep]
            for a in actives:
                base, kk, acc, emitted = res[a.slot]
                # commit: KV through base+acc is target-exact (verify
                # rewrote the window); the reject rewind is this ONE
                # host edit — paged drops only entries past the
                # admission reservation (none in steady state)
                cache.truncate(a.slot, base + 1 + acc)
                sched.seq_steps += 1
                sched.step_tokens += len(emitted)
                sched.drafted_tokens += kk
                sched.accepted_tokens += acc
                for m, tok in enumerate(emitted):
                    if not sched._emit(a, tok, length=base + 1 + m):
                        break

    def _speculate(self, cache, n0, t0, keff, tables, samplers, active,
                   holder):
        """The device phase (engine worker thread): k draft steps, one
        verify, host acceptance. Every array is (slots,) or (slots, W)
        regardless of occupancy or accept counts — fixed shapes, so the
        program set never grows past draft step + verify."""
        sched = self.sched
        programs = sched.programs
        k = self.k
        cap = programs.capacity
        W = k + 1
        wtok = np.zeros((cache.slots, W), np.int32)
        wtok[:, 0] = t0
        qprobs: Dict[Tuple[int, int], np.ndarray] = {}
        cur = t0.copy()
        with _telemetry.span("decode.draft", domain="serving", k=k):
            for j in range(k):
                # clamp the write position to cap-1: a row nearing
                # capacity parks tail drafts on the last position (the
                # verify rewrites it target-exact; keff already keeps
                # anything ACCEPTED strictly below capacity)
                lens_j = np.where(active, np.minimum(n0 + j, cap - 1),
                                  0).astype(np.int32)
                if tables is not None:
                    out = programs.decode(
                        cache.k_slab, cache.v_slab, tables, lens_j, cur,
                        ks_slab=cache.k_scale, vs_slab=cache.v_scale)
                else:
                    out = programs.decode(
                        cache.k_slab, cache.v_slab, lens_j, cur,
                        ks_slab=cache.k_scale, vs_slab=cache.v_scale)
                cache.swap_slabs(*out[1:])
                logits = np.asarray(out[0])
                cur = logits.argmax(axis=-1).astype(np.int32)
                for slot, (temp, rng) in sorted(samplers.items()):
                    # rng draws only for lanes the row can accept —
                    # keff-excess lanes stay argmax (no stream drift)
                    if j < int(keff[slot]):
                        q = _softmax64(logits[slot], temp)
                        qprobs[(slot, j)] = q
                        cur[slot] = _draw(q, rng)
                wtok[:, j + 1] = cur
        vlens = np.where(active, n0, 0).astype(np.int32)
        with _telemetry.span("decode.verify", domain="serving", window=W):
            if tables is not None:
                out = programs.verify(
                    cache.k_slab, cache.v_slab, tables, vlens, wtok,
                    ks_slab=cache.k_scale, vs_slab=cache.v_scale)
            else:
                out = programs.verify(
                    cache.k_slab, cache.v_slab, vlens, wtok,
                    ks_slab=cache.k_scale, vs_slab=cache.v_scale)
            cache.swap_slabs(*out[1:])
            vlogits = np.asarray(out[0])           # (slots, W, V)
        res: Dict[int, Tuple[int, int, int, List[int]]] = {}
        for slot in np.nonzero(active)[0]:
            slot = int(slot)
            kk = int(keff[slot])
            draft = [int(wtok[slot, j + 1]) for j in range(kk)]
            ctx = samplers.get(slot)
            if ctx is None:
                acc, emitted = accept_greedy(draft, vlogits[slot], kk)
            else:
                temp, rng = ctx
                acc, emitted = accept_sampled(
                    draft, [qprobs[(slot, j)] for j in range(kk)],
                    vlogits[slot], kk, temp, rng)
            res[slot] = (int(n0[slot]), kk, acc, emitted)
        holder["res"] = res
