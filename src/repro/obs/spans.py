"""Host-side phase spans on the profiler's clock (DESIGN.md §16).

The engines wrap their host-visible phases — CSR build, partition
planning, each super-step, the serial tail, delta mutation / compaction,
validation — in ``span("name")`` context managers, and ``repro.color``
opens one root span ``color`` around every request.  Spans are always
recorded:

* each span stamps its start and end with ``time.time_ns()`` (the realtime
  clock the JAX profiler's host events use) and opens a
  ``jax.profiler.TraceAnnotation("repro.<name>")``, so a profiler trace
  (xprof / Perfetto) shows the program's phases beside the device ops;
* each span carries its own id, its parent's id and its request's id (the
  id of its outermost open span), kept on a ``contextvars`` stack, so
  spans opened on another thread (the service's worker) nest on their own;
* every closed span goes into one process-wide ring of ``RING_CAP``
  events (drop-oldest), and to every active ``SpanRecorder``.

A span that waits for the device ends at a sync the engine already makes
(``int()`` of a count, ``block_until_ready`` before the readback), so its
duration holds the device time; a span around an asynchronous dispatch
holds only the enqueue.

Reading, for operators::

    from repro.obs import last_requests
    for root, spans in last_requests(3):           # the last 3 colorings
        print(root.duration, [(s.name, s.duration) for s in spans])

To collect the spans of one call, open a recorder around it::

    from repro.obs import recorder
    with recorder() as spans:
        result = color(g, algorithm="fused")
    # spans.events -> [SpanEvent(name="csr_build", ...), ...]

Engines that run with ``trace=True`` open their own recorder internally
and attach the captured events to ``ColoringResult.trace.spans``; an
outer recorder still sees every span (recorders nest — each event is
delivered to the whole active stack).

Compile attribution comes from JAX's own ``jax.monitoring`` events: each
backend compile and each persistent-cache load is counted on the
innermost open span of the thread that caused it, as
``meta["compiles"]`` and ``meta["cache_loads"]``.
"""
from __future__ import annotations

import collections
import contextvars
import dataclasses
import itertools
import threading
import time
from contextlib import contextmanager

import jax.monitoring
import jax.profiler

__all__ = [
    "RING_CAP",
    "SpanEvent",
    "SpanRecorder",
    "last_requests",
    "recorder",
    "span",
]

# closed spans the process keeps for ``last_requests`` (oldest dropped first)
RING_CAP = 4096

_RING: collections.deque = collections.deque(maxlen=RING_CAP)
# active recorders, process-wide: every closed span goes to each of them
_ACTIVE: list = []
# the open spans of the current thread / context, innermost last
_OPEN: contextvars.ContextVar = contextvars.ContextVar("repro_open_spans",
                                                       default=())
_IDS = itertools.count(1)

# JAX monitoring events: a backend compile (also fired around a persistent
# cache load, which first fires its own event) and a persistent-cache load
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_pending_loads = threading.local()


@dataclasses.dataclass
class SpanEvent:
    """One phase span (``time.time_ns()`` stamps; ``end_ns`` 0 while open).

    ``parent_id`` is 0 on a root span; ``request_id`` is the id of the
    span's root (its own id on a root).
    """

    name: str
    start_ns: int
    end_ns: int = 0
    span_id: int = 0
    parent_id: int = 0
    request_id: int = 0
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def duration(self) -> float:
        """Duration in seconds."""
        return (self.end_ns - self.start_ns) * 1e-9

    def to_dict(self) -> dict:
        return {"name": self.name, "start_ns": self.start_ns,
                "end_ns": self.end_ns, "span_id": self.span_id,
                "parent_id": self.parent_id, "request_id": self.request_id,
                "meta": dict(self.meta)}


class SpanRecorder:
    """Accumulates every ``SpanEvent`` closed while it is on the stack."""

    def __init__(self):
        self.events: list = []

    def __enter__(self):
        _ACTIVE.append(self)
        return self

    def __exit__(self, *exc):
        _ACTIVE.remove(self)
        return False

    def total(self, name: str | None = None) -> float:
        """Summed duration of matching spans (seconds)."""
        return sum(e.duration for e in self.events
                   if name is None or e.name == name)

    def by_name(self) -> dict:
        """Per span name: count, seconds, and the compiles and cache loads
        JAX reported while each was the innermost open span."""
        out: dict = {}
        for e in self.events:
            agg = out.setdefault(e.name, {"count": 0, "seconds": 0.0,
                                          "compiles": 0, "cache_loads": 0})
            agg["count"] += 1
            agg["seconds"] += e.duration
            agg["compiles"] += e.meta.get("compiles", 0)
            agg["cache_loads"] += e.meta.get("cache_loads", 0)
        return out


def recorder() -> SpanRecorder:
    """A fresh recorder; use as ``with recorder() as r: ...``."""
    return SpanRecorder()


@contextmanager
def span(name: str, **meta):
    """Time a phase; yields its ``SpanEvent`` so the phase can add meta."""
    stack = _OPEN.get()
    parent = stack[-1] if stack else None
    sid = next(_IDS)
    ev = SpanEvent(name, 0, 0, sid,
                   parent.span_id if parent else 0,
                   parent.request_id if parent else sid, meta)
    token = _OPEN.set(stack + (ev,))
    with jax.profiler.TraceAnnotation(f"repro.{name}"):
        ev.start_ns = time.time_ns()
        try:
            yield ev
        finally:
            ev.end_ns = time.time_ns()
            _OPEN.reset(token)
            _RING.append(ev)
            for rec in _ACTIVE:
                rec.events.append(ev)


def last_requests(k: int = 1, root: str = "color") -> list:
    """The last ``k`` closed spans named ``root``, oldest first, each as
    ``(root_event, descendants)`` with the descendants in closing order.

    ``repro.color`` opens a ``color`` span around every request, so
    ``last_requests(k)`` is the phase breakdown of the last ``k`` colorings
    (``superstep``, ``serial_tail``, ``csr_build``, ...).  Only what the
    ring still holds is returned: fewer than ``k`` when fewer ran, and a
    request whose spans outnumber ``RING_CAP`` loses its oldest ones.
    """
    events = list(_RING)
    roots = [e for e in events if e.name == root][-k:] if k > 0 else []
    children: dict = {}
    for e in events:
        children.setdefault(e.parent_id, []).append(e)
    out = []
    for r in roots:
        found, frontier = [], [r.span_id]
        while frontier:
            kids = [c for p in frontier for c in children.get(p, ())]
            found.extend(kids)
            frontier = [c.span_id for c in kids]
        found.sort(key=lambda e: e.end_ns)
        out.append((r, found))
    return out


def _count_on_open_span(key: str) -> None:
    stack = _OPEN.get()
    if stack:
        meta = stack[-1].meta
        meta[key] = meta.get(key, 0) + 1


def _on_jax_event(event: str, duration: float, **kw) -> None:
    if event == _CACHE_LOAD_EVENT:
        _count_on_open_span("cache_loads")
        _pending_loads.n = getattr(_pending_loads, "n", 0) + 1
    elif event == _COMPILE_EVENT:
        # a cache load ends inside the compile event that wraps it
        if getattr(_pending_loads, "n", 0):
            _pending_loads.n -= 1
        else:
            _count_on_open_span("compiles")


jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
