"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload noc6x6.fig9 --seed 7 --seconds 10 --trace 0

Reads `BENCHMARK.json` at the root of the checkout, finds the cell, and
loads what belongs to it by name: the configuration file the cell's config
names, `bench/traffic/<traffic>.json`, the entry module that traffic names
(`bench/entries/<entry>.py`) and one reader per metric
(`bench/metrics/<metric>.py`).  A new cell, traffic mix or metric is a new
file plus entries in `BENCHMARK.json`; nothing here changes.

A run: set-up (imports, the cell's arguments, one warm-up step that traces
and compiles or loads every program the window runs), then a window of
`--seconds`, then the check of the window's answers against the plain
reference.  `--trace 0` reports the cell's end-to-end metrics; `--trace 1`
profiles a short steady window of its own and reports the per-layer
metrics, `busy_s`/`window_s` and a `breakdown`.  The last line of stdout is
one JSON object; the compared numbers with their limits are the last lines
of stderr.  Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# Fixed, inside the checkout: the path is part of the persistent cache's key.
CACHE_DIR = ROOT / ".jax_cache"
# Steps in the traced window of a `--trace 1` run.  One sweep of the paper
# grid is ~4.5 million device operations on a v5e (one kernel launch and its
# scan ops per simulated cycle): a 230 MB trace that takes the profiler about
# two minutes to collect, so a second step would not fit a run's time.
TRACE_STEPS = 1

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    """Import one benchmark file by path (entries and metric readers)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no benchmark module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """`BENCHMARK.json` and the files it names, under one root."""

    def __init__(self, root: Path):
        self.root = Path(root)
        with open(self.root / "BENCHMARK.json") as f:
            self.spec = json.load(f)

    def cell(self, workload: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == workload:
                return w
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                with open(self.root / c["file"]) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        with open(self.root / "bench" / "traffic" / f"{name}.json") as f:
            return json.load(f)

    def entry(self, name: str):
        return load_module(self.root / "bench" / "entries" / f"{name}.py",
                           f"bench_entry_{name}")

    def metrics(self, workload: str, trace: bool) -> list[dict]:
        """The metrics a run of this cell reports: end-to-end without the
        trace, per-layer with it; a metric with `workloads` only there."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str):
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py",
                           f"bench_metric_{metric.replace('.', '_')}")


class Monitor:
    """JAX's own compile-time spans and events (`jax.monitoring`), on the
    host clock."""

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []
        self.events: list[tuple[str, float]] = []

    def on_span(self, event, start, end, **_):
        self.spans.append((event, start, end))

    def on_event(self, event, **_):
        self.events.append((event, time.time()))

    def __enter__(self):
        import jax

        jax.monitoring.register_event_time_span_listener(self.on_span)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_time_span_listener(self.on_span)
        jax.monitoring.unregister_event_listener(self.on_event)

    def count(self, event: str) -> int:
        return sum(e == event for e, _ in self.events)

    def between(self, lo: float, hi: float, event: str | None = None):
        """Spans that began in [lo, hi) (wall-clock seconds)."""
        return [(e, s, t) for e, s, t in self.spans
                if lo <= s < hi and (event is None or e == event)]


def check_devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class Context:
    """What the metric readers read: host-clock times, JAX's compile spans,
    and (with `--trace 1`) the reduced device trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True,
             t_start: float = T_START) -> dict:
    """One run of one cell; returns the result line as a dict."""
    bench = Bench(root)
    cell = bench.cell(workload)
    with Monitor() as monitor:
        # before the program is imported, so that a machine without the
        # chips compiles and caches nothing
        devices = check_devices(cell["chips"], require_tpu)
        return _run(bench, workload, cell, devices, monitor, seed, seconds,
                    trace, t_start)


def _run(bench, workload, cell, devices, monitor, seed, seconds, trace,
         t_start) -> dict:
    import jax

    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    metrics = bench.metrics(workload, trace)
    readers = {m["name"]: bench.reader(m["name"]) for m in metrics}
    entry = bench.entry(traffic["entry"])
    used = devices[:cell["chips"]]

    # ---- set-up: the cell's arguments and one warm-up step
    wall0 = time.time() - (time.perf_counter() - t_start)
    runner = entry.Runner(config, traffic, chips=cell["chips"], seed=seed)
    runner.step(0)
    setup_end = time.perf_counter()
    setup_end_wall = time.time()
    setup_s = setup_end - t_start
    log(f"[setup] {workload}: {setup_s:.3f} s ({runner.describe()}); "
        f"persistent cache hits "
        f"{monitor.count('/jax/compilation_cache/cache_hits')}, misses "
        f"{monitor.count('/jax/compilation_cache/cache_misses')}")

    # ---- the measured window (or the traced one)
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        # host spans: the benchmark's TraceAnnotations and JAX's dispatch
        # (level 1); no Python call tracing, no HLO protos
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    steps = []
    win_wall0 = time.time()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            k = len(steps) + 1
            steps.append(runner.step(k))
            t1 = time.perf_counter()
            if (len(steps) >= TRACE_STEPS) if trace else (t1 - t0 >= seconds):
                break
    win_wall1 = time.time()
    reduced = None
    if trace:
        from bench import trace_reduce

        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        t_read = time.perf_counter()
        reduced = trace_reduce.reduce_dir(
            trace_dir, window="bench.window", n_devices=len(used))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"[trace] collected in {t_read - t_stop:.3f} s, reduced in "
            f"{time.perf_counter() - t_read:.3f} s; "
            f"{sum(map(len, reduced.ops.values()))} device ops")
    window_compiles = len(monitor.between(win_wall0, win_wall1,
                                          BACKEND_COMPILE))
    log(f"[window] {len(steps)} steps in {t1 - t0:.3f} s; backend compiles "
        f"in the window: {window_compiles}")
    peak = memory_peak(used)

    ctx = Context(setup_s=setup_s, monitor=monitor,
                  setup_span=(wall0, setup_end_wall), window_s=t1 - t0,
                  steps=steps, trace=reduced)
    values = {}
    for m in metrics:
        v = readers[m["name"]].read(ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    # ---- the check against the plain reference, after the window
    t_check = time.perf_counter()
    check = runner.check(steps)
    log(f"[check] {check['points']} points against the reference in "
        f"{time.perf_counter() - t_check:.3f} s")
    for name, (value, limit) in check["compared"].items():
        log(f"[check] {name}={value} limit={limit}")

    dev = used[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {
        "correct": check["correct"],
        "attempted": sum(s.points for s in steps),
        "failed": check["failed"],
        "metrics": values,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown
    result["compared"] = {
        name: {"value": value, "limit": limit}
        for name, (value, limit) in check["compared"].items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # libtpu logs to /tmp/tpu_logs unless told otherwise; keep it in TMPDIR
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        tempfile.gettempdir(), "tpu_logs"))
    # the program's entry points take the cache directory from here too
    # (`repro.launch.compile_cache`); JAX does not create it
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    # every program of the cell, however quick to compile, comes from the
    # cache after a checkout's first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: with a size limit set in the environment JAX reads every
    # entry's access-time file on each write, and one entry without it
    # fails every write after it
    jax.config.update("jax_compilation_cache_max_size", -1)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        log(f"[device] {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
