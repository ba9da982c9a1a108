"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

* busy intervals: the union of the ``XLA Ops`` events of each device
  plane (``/device:TPU:<i>``), clipped to the traced window; busy seconds
  are averaged over the devices;
* device time by program: ``XLA Modules`` events, by the jitted
  function's name (``jit_<name>``, the hash suffix dropped);
* device time by operation: ``XLA Ops`` events, named
  ``<program>:<op>`` after the module whose interval holds them;
* idle gaps: the stretches of the window in which no operation ran,
  each labelled by the benchmark's own host annotation
  (``jax.profiler.TraceAnnotation``) that overlaps it most, or
  ``host`` where none does.

Only the host annotations named in ``ANNOTATIONS`` label gaps.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

#: the benchmark's host spans around each call into a layer
ANNOTATIONS = ("observe", "surface", "summarize", "train_step", "data",
               "reference")

_HASH = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``start_trace`` directory (or
    the path itself when it names a file)."""
    if os.path.isfile(trace_dir):
        return trace_dir
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def program_name(module_event_name: str) -> str:
    """``jit__fleet_core(1234)`` -> ``jit__fleet_core``."""
    return _HASH.sub("", module_event_name)


def op_name(op_event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    head = op_event_name.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping [start, end) intervals (sorted output)."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Reduction:
    """What a trace says about the device, in seconds."""

    def __init__(self, busy: Dict[str, List[Tuple[float, float]]],
                 ops: List[Tuple[str, float, float]],
                 modules: List[Tuple[str, float, float]],
                 spans: List[Tuple[str, float, float]],
                 window: Tuple[float, float], window_s: float):
        self.busy = busy
        self.ops = ops
        self.modules = modules
        self.spans = spans
        self.window = window
        self.window_s = float(window_s)
        self.num_events = len(ops)

    @property
    def busy_s(self) -> float:
        if not self.busy:
            return 0.0
        tot = [sum(e - s for s, e in iv) for iv in self.busy.values()]
        return sum(tot) / len(tot) / 1e9

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or not self.busy:
            return None
        return max(0.0, 1.0 - self.busy_s / self.window_s)

    def program_seconds(self, names) -> float:
        """Device seconds of the programs whose name contains any of
        ``names`` (all devices)."""
        return sum(e - s for n, s, e in self.modules
                   if any(x in n for x in names)) / 1e9

    def program_count(self, names) -> int:
        return sum(1 for n, _, _ in self.modules
                   if any(x in n for x in names))

    def device_ops(self, top: int = 10) -> List[List]:
        acc: Dict[str, float] = {}
        for name, s, e in self.ops:
            acc[name] = acc.get(name, 0.0) + (e - s) / 1e9
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> List[List]:
        """Idle seconds of the window by the host span they fall in."""
        if not self.busy:
            return []
        iv = next(iter(self.busy.values()))      # the first device
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in iv:
            if s > t:
                gaps.append((t, min(s, hi)))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        spans = sorted(self.spans, key=lambda x: x[1])
        acc: Dict[str, float] = {}
        j = 0
        for gs, ge in gaps:
            if ge <= gs:
                continue
            while j < len(spans) and spans[j][2] <= gs:
                j += 1
            best, label = 0.0, "host"
            for name, ss, se in spans[j:]:
                if ss >= ge:
                    break
                ov = min(se, ge) - max(ss, gs)
                if ov > best:
                    best, label = ov, name
            acc[label] = acc.get(label, 0.0) + (ge - gs) / 1e9
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:top]]

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(), "idle_gaps": self.idle_gaps()}


def reduce_trace(trace_dir: str, window_s: Optional[float] = None
                 ) -> Reduction:
    """Read the trace a ``start_trace``/``stop_trace`` pair wrote."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    busy: Dict[str, List[Tuple[float, float]]] = {}
    ops, modules, spans = [], [], []
    host_lo, host_hi = None, None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            raw, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        raw.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        mods.append((program_name(ev.name), ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
            mods.sort(key=lambda m: m[1])
            raw.sort(key=lambda o: o[1])
            j = 0
            for name, s, e in raw:
                while j + 1 < len(mods) and mods[j + 1][1] <= s:
                    j += 1
                prog = mods[j][0] if mods and mods[j][1] <= s else "?"
                ops.append((f"{prog}:{op_name(name)}", s, e))
            modules.extend(mods)
            busy[plane.name] = union([(s, e) for _, s, e in raw])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ANNOTATIONS:
                        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                        spans.append((ev.name, s, e))
                        host_lo = s if host_lo is None else min(host_lo, s)
                        host_hi = e if host_hi is None else max(host_hi, e)
    starts = [iv[0][0] for iv in busy.values() if iv]
    ends = [iv[-1][1] for iv in busy.values() if iv]
    lo = min([x for x in (host_lo, min(starts, default=None))
              if x is not None], default=0.0)
    hi = max([x for x in (host_hi, max(ends, default=None))
              if x is not None], default=0.0)
    if window_s is None:
        window_s = (hi - lo) / 1e9
    else:
        hi = max(hi, lo + window_s * 1e9)
    clipped = {k: [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]
               for k, iv in busy.items()}
    return Reduction(clipped, ops, modules, spans, (lo, hi), window_s)
