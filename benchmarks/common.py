"""Shared benchmark plumbing: timing, memory, CSV/markdown emit, checks."""
from __future__ import annotations

import datetime
import json
import os
import platform
import resource
import subprocess
import sys
import time
from typing import Callable, Dict, List

OUT_DIR = os.environ.get("REPRO_BENCH_OUT", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "bench_results"))


def ensure_out() -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    return OUT_DIR


def peak_rss_mb() -> float:
    """Lifetime peak resident set of THIS process, in MiB.

    ``ru_maxrss`` is a high-water mark, not a gauge: it only ever grows,
    so a memory gate must bracket the measured section — record it
    before, run the workload, and attribute the DELTA plus the baseline.
    Linux reports KiB; macOS reports bytes."""
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return rss / 1024.0 if sys.platform != "darwin" else rss / (1024.0 ** 2)


def time_call(fn: Callable, *args, repeat: int = 3, **kw) -> float:
    """Median wall-time (us) of fn(*args), after one warmup."""
    fn(*args, **kw)
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn(*args, **kw)
        ts.append((time.perf_counter() - t0) * 1e6)
    ts.sort()
    return ts[len(ts) // 2]


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def run_header() -> Dict:
    """Uniform provenance header stamped into every BENCH_*.json
    (``emit_json`` adds it as the ``"run"`` key): git sha, UTC
    timestamp, interpreter/jax versions, backend devices, platform, and
    the peak-RSS bracket START (``peak_rss_mb`` is a high-water mark —
    artifacts record the header value so a reader can attribute the
    final peak to the measured section, not interpreter boot)."""
    hdr = {
        "git_sha": _git_sha(),
        "timestamp_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "python": platform.python_version(),
        "platform": platform.platform(),
        "peak_rss_mb_at_header": round(peak_rss_mb(), 1),
        "argv": list(sys.argv),
    }
    try:
        import jax
        hdr["jax"] = jax.__version__
        hdr["devices"] = [str(d) for d in jax.devices()]
    except Exception as exc:                      # jax absent or broken
        hdr["jax"] = f"unavailable ({type(exc).__name__})"
    return hdr


def emit_rows(name: str, rows: List[Dict], keys: List[str]) -> str:
    """Write CSV + echo; returns path."""
    out = ensure_out()
    path = os.path.join(out, f"{name}.csv")
    with open(path, "w") as f:
        f.write(",".join(keys) + "\n")
        for r in rows:
            f.write(",".join(str(r.get(k, "")) for k in keys) + "\n")
    print(f"[{name}] {len(rows)} rows -> {path}")
    return path


def emit_json(name: str, obj) -> str:
    out = ensure_out()
    path = os.path.join(out, f"{name}.json")
    if isinstance(obj, dict) and "run" not in obj:
        obj = {"run": run_header(), **obj}
    with open(path, "w") as f:
        json.dump(obj, f, indent=1, default=str)
    print(f"[{name}] -> {path}")
    return path


class Check:
    """Collects pass/fail assertions against the paper's stated results."""

    def __init__(self, name: str):
        self.name = name
        self.results = []

    def expect(self, desc: str, ok: bool, detail: str = ""):
        self.results.append((desc, bool(ok), detail))
        tag = "PASS" if ok else "FAIL"
        print(f"  [{tag}] {desc}" + (f"  ({detail})" if detail else ""))

    def summary(self) -> bool:
        ok = all(r[1] for r in self.results)
        n = sum(1 for r in self.results if r[1])
        print(f"[{self.name}] {n}/{len(self.results)} checks pass")
        return ok
