"""Run one benchmark cell on the accelerator and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json`` at the
root of the checkout: its configuration file (``bench/configs/``), its
traffic file (``bench/traffic/<traffic>.json``, which names a driver in
``bench/drivers/``), and one reader per per-layer metric
(``bench/metrics/<metric>.py``).  A new cell or metric is new files and
new entries, never an edit here.

A run: checks that JAX sees a TPU and as many chips as the cell asks for
(otherwise it exits 2 and prints no result); sets the driver up (weights
and inputs from ``--seed``, every shape the window uses compiled and
warmed: ``setup_s``); measures for ``--seconds``; counts the compiles
inside the window; reads the device's peak memory; then compares what
the window produced with the plain reference.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the window.  The numbers compared for ``correct`` are
printed beside their limits as the last lines on standard error, and
under ``checks``, the last key of the result line.
"""
from __future__ import annotations

import argparse
import hashlib
import importlib.util
import json
import math
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def sub_seed(seed: int, *tags) -> int:
    """A 31-bit seed for one use of the run's seed (any whole number)."""
    h = hashlib.sha256(repr((int(seed),) + tags).encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF


def load_module(path: Path):
    """Import a Python file by path (metric files carry dots in names)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic and metrics."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    bench_dir = root / spec["paths"][0]
    traffic = json.loads(
        (bench_dir / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    per_layer = [m for m in spec["per_layer"] if mine(m)]
    return dict(name=name, cell=cell, config=config, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def driver_for(cell: dict):
    return load_module(cell["bench_dir"] / "drivers"
                       / f"{cell['traffic']['driver']}.py")


def metric_reader(cell: dict, name: str):
    return load_module(cell["bench_dir"] / "metrics" / f"{name}.py")


def find_chips(chips: int):
    """The devices to run on, or a message saying why there are none."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return None, f"JAX found no accelerator ({e})"
    if devices[0].platform != "tpu":
        return None, f"JAX found no TPU (platform {devices[0].platform})"
    if len(devices) < chips:
        return None, (f"the cell needs {chips} TPUs, JAX found "
                      f"{len(devices)}")
    return devices[:chips], None


class CompileCounter:
    """Counts XLA backend compiles while ``active``."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0

        def listener(event, duration_secs, **kw):
            if self.active and event.endswith("backend_compile_duration"):
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


class Run:
    """What one run carries between the harness, its driver and the
    metric readers."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 devices):
        self.cell = cell
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.trace_dir = None
        self.tracing = False
        self.trace_window_s = None
        self.reduced = None          # bench.trace.Reduction once read
        self._trace_t0 = None

    def sub_seed(self, *tags) -> int:
        return sub_seed(self.seed, *tags)

    def annotate(self, name: str):
        """A host span in the profiler's trace, named for the layer the
        call goes into; free when no trace is taken."""
        import contextlib
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- the traced part of the window ----------------------------------
    def start_trace(self) -> None:
        if not self.trace:
            return
        import jax
        self.trace_dir = str(ROOT / ".bench_trace" / self.cell["name"])
        import shutil
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans: ours only
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.tracing = True
        self._trace_t0 = time.perf_counter()

    def trace_due(self, limit_s: float) -> bool:
        """True once the trace has run ``limit_s`` seconds."""
        return self.tracing and time.perf_counter() - self._trace_t0 >= limit_s

    def stop_trace(self) -> None:
        if not self.tracing:
            return
        import jax
        self.trace_window_s = time.perf_counter() - self._trace_t0
        self.tracing = False
        jax.profiler.stop_trace()


def _peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def _fmt(v) -> str:
    return repr(float(v)) if isinstance(v, (int, float)) else str(v)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    cell = load_cell(args.workload)
    chips = int(cell["cell"]["chips"])
    devices, why = find_chips(chips)
    if devices is None:
        print(f"bench: {why}; no result", file=sys.stderr)
        return 2
    dev = devices[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}", flush=True)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    compiles = CompileCounter()

    run = Run(cell, args.seed, args.seconds, bool(args.trace), devices)
    driver = driver_for(cell)
    state = driver.setup(run)
    setup_s = time.perf_counter() - T_START
    print(f"setup: {setup_s!r} s", flush=True)

    counts0 = driver.counters(state)
    compiles.active = True
    run.start_trace()
    out = driver.window(run, state)
    run.stop_trace()
    compiles.active = False
    counts1 = driver.counters(state)
    in_window = {k: counts1[k] - counts0[k] for k in counts0}
    in_window["xla_backend_compiles"] = compiles.count
    print(f"compiles inside the window: {in_window}", flush=True)
    for line in out.get("lines", []):
        print(line, flush=True)
    peak = _peak_bytes(devices)

    try:
        checks = driver.check(run, state, out)
        error = None
    except Exception as e:                 # a crash reads as not correct
        import traceback
        traceback.print_exc()
        checks, error = {}, f"{type(e).__name__}: {e}"
    correct = error is None and bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if args.trace:
        t_read = time.perf_counter()
        red = load_module(BENCH / "trace.py").reduce_trace(
            run.trace_dir, run.trace_window_s)
        import shutil
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        run.reduced = red
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        metrics = {}
        for m in cell["per_layer"]:
            value = metric_reader(cell, m["name"]).read(run, out)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result["breakdown"] = red.breakdown()
        print(f"trace: {red.num_events} device events, read in "
              f"{time.perf_counter() - t_read!r} s", flush=True)
    else:
        metrics = {}
        for m in cell["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" \
                else out["end_to_end"].get(m["name"])
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if error is not None:
        print(f"check raised: {error}", file=sys.stderr)
    # a number that is not finite (a crashed or diverged reading) goes
    # out as null; its line on standard error says what it was
    result["checks"] = {k: {"value": c["value"] if math.isfinite(c["value"])
                            else None, "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k}: {_fmt(c['value'])} <= limit {_fmt(c['limit'])}"
              f" ({'ok' if c['value'] <= c['limit'] else 'FAIL'})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
