"""Runtime lock-order witness.

The static pass proves what *can* happen; the witness records what *does*.
Wrap real locks with :meth:`LockOrderWitness.wrap` and every acquisition
edge (lock B taken while this thread holds lock A) is counted and checked
against the same declared hierarchy the static analyzer uses
(:data:`.lockorder.LOCK_HIERARCHY`).

The surface is the metric.py / ServingMetrics idiom — ``get()`` returns
parallel name/value lists, ``get_name_value()`` zips them — so a serving
dashboard scrapes witness edges and per-bucket latency gauges through one
metrics path::

    witness = LockOrderWitness()
    lock = witness.wrap(threading.Lock(), "serving.metrics.ServingMetrics._lock")
    ...
    names, values = witness.get()        # edge counters + violation count
    assert not witness.violations()

Overhead is one thread-local list append per acquire; intended for tests
and canary deployments (opt-in through ``wrap()``), not the hot path.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

from .lockorder import LOCK_HIERARCHY


class _WitnessedLock:
    """Context-manager proxy recording acquisition order; delegates the
    full lock protocol (incl. Condition wait/notify) to the real lock."""

    def __init__(self, lock, name: str, witness: "LockOrderWitness"):
        self._lock = lock
        self._name = name
        self._witness = witness

    def acquire(self, *args, **kwargs):
        got = self._lock.acquire(*args, **kwargs)
        if got:
            self._witness._on_acquire(self._name)
        return got

    def release(self):
        self._witness._on_release(self._name)
        self._lock.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __getattr__(self, attr):  # wait/notify/locked/...
        return getattr(self._lock, attr)


class LockOrderWitness:
    """Records observed lock-acquisition edges across all threads."""

    def __init__(self, hierarchy: Optional[Dict[str, int]] = None):
        self._hierarchy = LOCK_HIERARCHY if hierarchy is None else hierarchy
        self._tls = threading.local()
        self._mu = threading.Lock()
        self._edges: Dict[Tuple[str, str], int] = {}

    def wrap(self, lock, name: str) -> _WitnessedLock:
        return _WitnessedLock(lock, name, self)

    def _held(self) -> List[str]:
        if not hasattr(self._tls, "held"):
            self._tls.held = []
        return self._tls.held

    def _on_acquire(self, name: str):
        held = self._held()
        if held:
            edge = (held[-1], name)
            with self._mu:
                self._edges[edge] = self._edges.get(edge, 0) + 1
        held.append(name)

    def _on_release(self, name: str):
        held = self._held()
        if name in held:
            held.reverse()
            held.remove(name)
            held.reverse()

    # --- metric.py-style surface -----------------------------------------
    def edges(self) -> Dict[Tuple[str, str], int]:
        with self._mu:
            return dict(self._edges)

    def violations(self) -> List[str]:
        """Observed edges that contradict the declared hierarchy."""
        out = []
        for (a, b), n in sorted(self.edges().items()):
            ra, rb = self._hierarchy.get(a), self._hierarchy.get(b)
            if ra is None or rb is None:
                continue
            if rb < ra:
                out.append("%s (rank %d) acquired under %s (rank %d), "
                           "%d time(s)" % (b, rb, a, ra, n))
            elif rb == ra and a != b:
                out.append("peer locks nested: %s under %s, %d time(s)"
                           % (b, a, n))
        return out

    def get(self):
        """(names, values) — EvalMetric.get() shape, like ServingMetrics."""
        names, values = [], []
        for (a, b), n in sorted(self.edges().items()):
            names.append("edge:%s->%s" % (a, b))
            values.append(n)
        names.append("violations")
        values.append(len(self.violations()))
        return names, values

    def get_name_value(self):
        names, values = self.get()
        return list(zip(names, values))

    def reset(self):
        with self._mu:
            self._edges.clear()
