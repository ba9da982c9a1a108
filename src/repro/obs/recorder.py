"""The flight recorder: structured spans + typed events on a ring.

One process-global ``Recorder`` (installed with :func:`install` or the
:func:`recording` context manager) collects every layer's typed events —
drift alarms, plan commits, cache hits/misses, compiles, oracle
fallbacks, quarantines, actuator applies, SLO burns — on a single
monotonic timeline, bounded by a ring buffer, exportable as JSONL and
parseable back into the identical typed events (round-trip pinned by
``tests/test_obs.py``).

Clock discipline (DESIGN.md §12): timestamps come from a MONOTONIC
clock (``time.perf_counter``) rebased to the recorder's install epoch.
They are observability-only — controller *decisions* remain pure
functions of the sample stream (the wall-clock-free contract of
``control/controller.py``), which is why every decision-relevant event
also carries its logical index (the CU-sample counter ``at``) in its
fields: the decision log reconstructed from a trace is clock-free and
bit-for-bit comparable across machines.

Profiler sink: ``profile_spans(True)`` makes every ``span()`` also open
a ``jax.profiler.TraceAnnotation`` of the span's exact name for its
extent, so the spans land in a profiler trace on the same clock as the
device's operations.  The caller that takes the trace switches it on and
off; it is independent of the ring (``active()`` stays None while only
the sink is on).  Span names form the closed set ``SPAN_NAMES``.

Disabled-recorder cost: with no recorder installed and the sink off,
``active()`` returns None, ``span()`` hands back one shared no-op
singleton, and ``event()`` returns before touching anything —
instrumented hot paths guard with ``active()`` so the disabled path
allocates no per-event objects (gated to <2% of
``RedundancyController.observe`` wall time by ``tests/test_obs.py``).
"""
from __future__ import annotations

import dataclasses
import io
import json
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional, Tuple, Union

__all__ = ["EVENT_KINDS", "Event", "NULL_SPAN", "Recorder", "SPAN_NAMES",
           "active", "event", "install", "parse_jsonl", "profile_spans",
           "recording", "span", "uninstall"]

#: The event taxonomy (DESIGN.md §12).  Exporters and parsers reject
#: unknown kinds so a trace file is schema-checked on both ends.
EVENT_KINDS = frozenset({
    "drift_alarm",      # a detector channel crossed (service/load/failure/slo)
    "commit",           # the controller committed a (model, policy) decision
    "cache_hit",        # compiled-surface cache: warm executable reused
    "cache_miss",       # compiled-surface cache: new structural key
    "compile",          # an XLA trace was paid (fields carry the wall ms)
    "oracle_fallback",  # sweep backend failed; commit re-planned on the DES
    "quarantine",       # the controller's quarantine set changed
    "actuate",          # an actuator applied a committed (policy, model)
    "slo_alarm",        # the SLO monitor's multi-window burn crossed
    "infeasible",       # a commit aborted: no finite cell on the surface
    "sweep",            # one cluster-engine surface call (batched/fleet rep)
    "expert_load",      # a train step's rows per held expert, by layer
    "attn_tiles",       # a built train step's attention tiles visited/total
    "span",             # a closed span (name, start ts, duration)
    "mark",             # free-form annotation (regime boundaries, footers)
})

#: The span names (DESIGN.md §12): one per layer boundary of the
#: host-bound paths.  ``span()`` rejects any other name while the sink or
#: a recorder is on, and the parser rejects it in a trace file.
SPAN_NAMES = frozenset({
    "ctl.observe",        # RedundancyController.observe: one sample in
    "ctl.fit",            # _commit: fit, plan model, degradation
    "replan",             # _commit: the planner call (ControlEvent.replan_ms)
    "ctl.actuate",        # _commit: the actuators
    "surface.dispatch",   # enqueueing one lane program on the device
    "surface.fetch",      # first host read of its outputs (waits on it)
    "surface.summarize",  # host statistics of the lanes
    "train.batch",        # CodedTrainer.run_step: the coded batch
    "train.decode",       # straggler mask and decode coefficients
    "train.dispatch",     # host-to-device inputs and the step launch
})


@dataclasses.dataclass(frozen=True)
class Event:
    """One recorded event.  ``ts`` is seconds on the recorder's
    monotonic clock (epoch = recorder install); ``dur`` is a span's
    duration in seconds (None for instantaneous events); ``fields`` are
    the kind-specific payload (JSON-serializable scalars/lists only)."""

    ts: float
    kind: str
    name: str = ""
    dur: Optional[float] = None
    fields: Tuple[Tuple[str, Any], ...] = ()

    def field_dict(self) -> Dict[str, Any]:
        return dict(self.fields)

    def to_json(self) -> str:
        return json.dumps(
            {"ts": self.ts, "kind": self.kind, "name": self.name,
             "dur": self.dur, "fields": dict(self.fields)},
            separators=(",", ":"), sort_keys=True)

    @staticmethod
    def from_json(line: str) -> "Event":
        obj = json.loads(line)
        kind = obj["kind"]
        if kind not in EVENT_KINDS:
            raise ValueError(f"unknown event kind {kind!r} in trace line")
        if kind == "span":
            _check_span_name(obj.get("name", ""))
        fields = obj.get("fields", {})
        return Event(ts=float(obj["ts"]), kind=kind,
                     name=obj.get("name", ""),
                     dur=None if obj.get("dur") is None
                     else float(obj["dur"]),
                     fields=tuple(sorted(
                         (str(k), _canon(v)) for k, v in fields.items())))


def _canon(v):
    """Canonical hashable form of a JSON field value (lists -> tuples,
    recursively), so parsed events compare equal to emitted ones."""
    if isinstance(v, list):
        return tuple(_canon(x) for x in v)
    return v


class _NullSpan:
    """The shared disabled-path span: entering/exiting does nothing.
    A single module-level instance is reused for every disabled
    ``span()`` call — no per-event allocation on the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


def _check_span_name(name: str) -> None:
    if name not in SPAN_NAMES:
        raise ValueError(f"unknown span name {name!r}; known: "
                         f"{sorted(SPAN_NAMES)}")


class _Span:
    """Context manager recording one ``span`` event at exit, with the
    name of the span it opened inside (``parent``, None at the top), and
    the profiler annotation of the same name while the sink is on."""

    __slots__ = ("_rec", "_name", "_fields", "_t0", "_ann", "_stack")

    def __init__(self, rec: "Recorder", name: str, fields: dict):
        self._rec = rec
        self._name = name
        self._fields = fields
        self._t0 = 0.0
        self._ann = None
        self._stack = None

    def __enter__(self):
        if _ANNOTATION is not None:
            self._ann = _ANNOTATION(self._name)
            self._ann.__enter__()
        self._stack = stack = self._rec._open_spans()
        parent = stack[-1] if stack else None
        stack.append(self._name)
        # the event's canonical fields; most spans carry none of their
        # own, which skips the sort
        self._fields = (("parent", parent),) if not self._fields else \
            tuple(sorted((str(k), _canon_out(v)) for k, v in
                         dict(self._fields, parent=parent).items()))
        self._t0 = self._rec.now()
        return self

    def __exit__(self, *exc):
        t1 = self._rec.now()
        self._stack.pop()
        self._rec._append(Event(ts=self._t0, kind="span", name=self._name,
                                dur=t1 - self._t0, fields=self._fields))
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        return False


def _canon_out(v):
    """Canonicalize an outgoing field value so the in-memory event
    equals its JSONL round trip: tuples/lists -> tuples, numpy scalars
    -> python scalars (json would coerce them anyway)."""
    if isinstance(v, (list, tuple)):
        return tuple(_canon_out(x) for x in v)
    if hasattr(v, "item") and not isinstance(v, (str, bytes)):
        try:
            return v.item()
        except Exception:
            pass
    if isinstance(v, bool) or v is None or isinstance(v, (int, str)):
        return v
    if isinstance(v, float):
        return v
    return str(v)


class Recorder:
    """Bounded in-memory event ring with a span API and JSONL export.

    ``capacity`` bounds memory: the ring keeps the most recent events
    and counts evictions in ``dropped`` (a trace that wrapped says so
    instead of silently looking complete).  Appends are GIL-atomic
    deque operations — safe under free-threaded instrumentation without
    a lock on the hot path.
    """

    def __init__(self, capacity: int = 65536,
                 clock=time.perf_counter):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self._clock = clock
        self._epoch = clock()
        self._ring: deque = deque(maxlen=int(capacity))
        self._local = threading.local()      # each thread's open spans
        self.dropped = 0

    # -- clock --------------------------------------------------------------
    def now(self) -> float:
        """Seconds since this recorder was created (monotonic)."""
        return self._clock() - self._epoch

    # -- write side ---------------------------------------------------------
    def _append(self, ev: Event) -> None:
        ring = self._ring
        if len(ring) == ring.maxlen:
            self.dropped += 1
        ring.append(ev)

    def event(self, kind: str, name: str = "", dur: Optional[float] = None,
              **fields) -> None:
        """Record one typed event at the current clock reading."""
        if kind not in EVENT_KINDS:
            raise ValueError(
                f"unknown event kind {kind!r}; known: "
                f"{sorted(EVENT_KINDS)}")
        self._append(Event(
            ts=self.now(), kind=kind, name=name, dur=dur,
            fields=tuple(sorted(
                (str(k), _canon_out(v)) for k, v in fields.items()))))

    def span(self, name: str, **fields) -> _Span:
        """``with rec.span("replan", k=8): ...`` records one ``span``
        event at exit carrying the start timestamp, the duration and the
        enclosing span's name (field ``parent``).  ``name`` must be one
        of ``SPAN_NAMES``."""
        _check_span_name(name)
        return _Span(self, name, fields)

    def _open_spans(self) -> list:
        """The names of this thread's spans that are open, innermost
        last."""
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    # -- read side ----------------------------------------------------------
    def events(self, kind: Optional[str] = None) -> List[Event]:
        evs = list(self._ring)
        if kind is None:
            return evs
        return [e for e in evs if e.kind == kind]

    def __len__(self) -> int:
        return len(self._ring)

    # -- export -------------------------------------------------------------
    def export_jsonl(self, path_or_file: Union[str, io.IOBase]) -> int:
        """Write the ring as JSONL (one event per line, recording
        order).  Returns the number of events written."""
        evs = list(self._ring)
        if isinstance(path_or_file, str):
            with open(path_or_file, "w") as f:
                for e in evs:
                    f.write(e.to_json() + "\n")
        else:
            for e in evs:
                path_or_file.write(e.to_json() + "\n")
        return len(evs)


def parse_jsonl(path_or_file: Union[str, io.IOBase, Iterable[str]]
                ) -> List[Event]:
    """Parse a JSONL trace back into typed events (the exact inverse of
    ``Recorder.export_jsonl`` — round-trip equality is pinned)."""
    if isinstance(path_or_file, str):
        with open(path_or_file) as f:
            return [Event.from_json(ln) for ln in f if ln.strip()]
    return [Event.from_json(ln) for ln in path_or_file if ln.strip()]


# --------------------------------------------------------------------------
# The process-global recorder
# --------------------------------------------------------------------------

_ACTIVE: Optional[Recorder] = None

#: ``jax.profiler.TraceAnnotation`` while the profiler sink is on, else None
_ANNOTATION = None


def active() -> Optional[Recorder]:
    """The installed recorder, or None (tracing disabled).  THE hot-path
    guard: instrumented code calls this before building any event
    payload, so a disabled recorder costs one global read + one `is not
    None` per site."""
    return _ACTIVE


def install(recorder: Optional[Recorder] = None) -> Recorder:
    """Install (and return) the process-global recorder."""
    global _ACTIVE
    _ACTIVE = recorder if recorder is not None else Recorder()
    return _ACTIVE


def uninstall() -> Optional[Recorder]:
    """Disable tracing; returns the recorder that was installed."""
    global _ACTIVE
    rec, _ACTIVE = _ACTIVE, None
    return rec


class recording:
    """``with recording() as rec: ...`` — install a recorder for the
    block, restore the previous one after (re-entrant)."""

    def __init__(self, recorder: Optional[Recorder] = None,
                 capacity: int = 65536):
        self._rec = recorder if recorder is not None \
            else Recorder(capacity=capacity)
        self._prev: Optional[Recorder] = None

    def __enter__(self) -> Recorder:
        global _ACTIVE
        self._prev = _ACTIVE
        _ACTIVE = self._rec
        return self._rec

    def __exit__(self, *exc):
        global _ACTIVE
        _ACTIVE = self._prev
        return False


def profile_spans(on: bool) -> bool:
    """Switch the profiler sink on or off; returns whether it was on.

    While it is on, every ``span()`` also opens a
    ``jax.profiler.TraceAnnotation`` named exactly as the span (fields
    stay out of the name) for the span's extent, whether or not a
    recorder is installed.  Whoever takes the profiler trace switches
    it: the annotations cost a little even when no trace is taken."""
    global _ANNOTATION
    was = _ANNOTATION is not None
    if on:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    else:
        _ANNOTATION = None
    return was


def span(name: str, **fields):
    """Module-level span: through the global recorder when one is
    installed, a bare profiler annotation when only the sink is on, and
    the shared no-op singleton when neither is (zero allocation)."""
    rec = _ACTIVE
    if rec is not None:
        return rec.span(name, **fields)
    if _ANNOTATION is None:
        return NULL_SPAN
    _check_span_name(name)
    return _ANNOTATION(name)


def event(kind: str, name: str = "", dur: Optional[float] = None,
          **fields) -> None:
    """Module-level event through the global recorder; a no-op when
    disabled.  Hot paths should prefer guarding with ``active()`` so
    the kwargs dict is never even built."""
    rec = _ACTIVE
    if rec is not None:
        rec.event(kind, name=name, dur=dur, **fields)
