"""Named spans over the engine's hot path and the serving round.

Every span is a ``jax.profiler.TraceAnnotation`` (a TraceMe): a section
on a captured profile's host timeline, its labels attached as the
event's stats, and next to free when no profile is active.  The serving
round (``sched.step`` and its stages, ``engine.*``) and the flush
lifecycle (``flush -> drain``, ``ticket-wait``) are spanned in the
program, so a device trace can charge every idle gap on the chip to the
host stage that kept it waiting.

With recording on (:func:`set_tracing`; off by default) each span also
leaves a wall-clock :class:`Span` record in a bounded ring
(:func:`spans` reads, :func:`span_tree` nests, :func:`reset_spans`
clears).  Records carry absolute sequence numbers and link to their
parent by that number, so dropping the oldest record is O(1).
With recording off a span is the annotation alone: no record, no
allocation beyond the TraceMe.

:class:`FlushTiming` is the per-flush timing quad the engine stashes and
``FlushTicket.timing`` carries: queue residency (first enqueue -> flush
call), drain wall-clock, bucket-padded table length, and launches.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
from typing import Deque, Dict, List, Optional, Tuple

from repro.obs.metrics import now

try:
    from jax.profiler import TraceAnnotation as _Annotation
except Exception:       # profiler unavailable: records only
    def _Annotation(name, **labels):
        return contextlib.nullcontext()

#: bounded span-record ring size (oldest records drop past this)
MAX_SPANS = 4096

_RECORDS: Deque["Span"] = collections.deque(maxlen=MAX_SPANS)
_STACK: List["Span"] = []
_NEXT = 0               # sequence number of the next record
_RECORDING = False


@dataclasses.dataclass
class Span:
    """One recorded span: name, wall-clock bounds, nesting, labels."""

    name: str                      #: span name (e.g. "flush", "drain")
    start: float                   #: perf_counter seconds at entry
    end: float                     #: perf_counter seconds at exit
    depth: int                     #: nesting depth (0 = root)
    parent: int                    #: ``seq`` of the enclosing span, -1 = root
    labels: Tuple[Tuple[str, str], ...] = ()   #: sorted label pairs
    seq: int = -1                  #: absolute sequence number (0 = first)

    @property
    def us(self) -> float:
        """Span duration in microseconds."""
        return (self.end - self.start) * 1e6


@dataclasses.dataclass(frozen=True)
class FlushTiming:
    """Per-flush timing carried by ``FlushTicket.timing``: how long rows
    sat queued, how long the drain took, how big the padded table was,
    and how many launches it cost."""

    queue_residency_us: float      #: first enqueue -> flush call
    drain_us: float                #: _drain_rows wall-clock
    table_len: int                 #: bucket-padded rows dispatched (all chunks)
    launches: int                  #: device launches the flush issued


class _Recorded:
    """A span with recording on: the annotation plus a :class:`Span`."""

    __slots__ = ("ann", "rec")

    def __init__(self, ann, name: str, labels: Dict) -> None:
        self.ann = ann
        self.rec = Span(name=name, start=0.0, end=0.0, depth=0, parent=-1,
                        labels=tuple(sorted((k, str(v))
                                            for k, v in labels.items())))

    def __enter__(self) -> "_Recorded":
        global _NEXT
        rec = self.rec
        rec.depth = len(_STACK)
        rec.parent = _STACK[-1].seq if _STACK else -1
        rec.seq = _NEXT
        _NEXT += 1
        _RECORDS.append(rec)
        _STACK.append(rec)
        self.ann.__enter__()
        rec.start = now()
        return self

    def __exit__(self, *exc) -> None:
        self.rec.end = now()
        self.ann.__exit__(*exc)
        if _STACK and _STACK[-1] is self.rec:
            _STACK.pop()


def span(name: str, **labels):
    """A named span to use as ``with span("flush", stream=s): ...``: a
    ``jax.profiler.TraceAnnotation`` always (``labels`` become the trace
    event's stats), and with recording on (:func:`set_tracing`) a
    wall-clock :class:`Span` record too.  Recorded spans nest: each
    keeps its depth and its parent's sequence number."""
    ann = _Annotation(name, **labels)
    return _Recorded(ann, name, labels) if _RECORDING else ann


def spans(name: Optional[str] = None) -> List[Span]:
    """Recorded spans (optionally filtered by name), oldest first."""
    if name is None:
        return list(_RECORDS)
    return [r for r in _RECORDS if r.name == name]


def reset_spans() -> None:
    """Clear the span record ring and restart sequence numbers at 0."""
    global _NEXT
    _RECORDS.clear()
    _STACK.clear()
    _NEXT = 0


def tracing_enabled() -> bool:
    """Is span recording currently on?"""
    return _RECORDING


def set_tracing(flag: bool) -> bool:
    """Turn span recording on or off; returns the PREVIOUS state.  Off
    (the default) keeps only the profiler annotation; either way the
    engine's behavior is unchanged (host-side only)."""
    global _RECORDING
    prev = _RECORDING
    _RECORDING = bool(flag)
    return prev


def span_tree(records: Optional[List[Span]] = None) -> List[Dict]:
    """Render span records as a nested dict tree (children inline, a
    record whose parent dropped out of the ring becomes a root) — the
    debugging view of one round's span hierarchy."""
    records = list(_RECORDS) if records is None else records
    nodes = {r.seq: {"name": r.name, "us": r.us, "labels": dict(r.labels),
                     "children": []} for r in records}
    roots: List[Dict] = []
    for r in records:
        if r.parent in nodes:
            nodes[r.parent]["children"].append(nodes[r.seq])
        else:
            roots.append(nodes[r.seq])
    return roots


__all__ = [
    "Span",
    "FlushTiming",
    "span",
    "spans",
    "reset_spans",
    "tracing_enabled",
    "set_tracing",
    "span_tree",
    "MAX_SPANS",
]
