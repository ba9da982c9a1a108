"""Helpers the per-layer metric readers share."""
from __future__ import annotations

import json
from pathlib import Path

#: the jitted programs that run the lane recurrence (monolithic, chunked)
LANE_PROGRAMS = ("jit__sweep_core", "jit__fleet_core", "jit__cached_kernel")


def idle_share_percent(run, out):
    """100 x (1 - device busy / traced window), or None without a trace."""
    if run.reduced is None:
        return None
    share = run.reduced.idle_share()
    return None if share is None else 100.0 * share


def lane_ns_per_elem(run, out):
    """Device nanoseconds of the sweep program per lane-job per worker:
    the lane programs' device time over the whole sweeps traced, divided
    by their lane-jobs times the fleet width n; None without a trace."""
    red = run.reduced
    sweeps = out.get("traced_sweeps", 0)
    if red is None or not sweeps or not red.program_count(LANE_PROGRAMS):
        return None
    elems = sweeps * out["lane_jobs_per_sweep"] * run.config["n"]
    return 1e9 * red.program_seconds(LANE_PROGRAMS) / elems


def peak(device_kind: str, key: str) -> float:
    """A published peak of ``device_kind``; a device not in the table is
    an error, not a default."""
    table = json.loads((Path(__file__).resolve().parent.parent
                        / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return float(table[device_kind][key])
