"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it keeps JAX's compile cache in ``$JAX_COMPILATION_CACHE_DIR``
or the checkout's ``.jax_cache``, fails unless JAX's backend is a TPU with
as many chips as the cell asks for, builds the cell from the seed and warms
its shapes (``setup_s``, counted from the start of the process), measures
for ``--seconds``, checks what the window produced against the plain
reference, and prints one JSON line last on standard output.

With ``--trace 0`` the line's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and they are the
cell's per-layer metrics, each read by ``bench/metrics/<name>.py``, with
the device's busy seconds and a ``breakdown`` of the trace. Every number
compared with the reference is printed beside its limit, as the last lines
of standard error and under ``checks``, the line's last key.

Exit codes: 0 a result was printed (``correct`` may be false); 2 the
benchmark's files or the program cannot be loaded; 3 no TPU, or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402
from bench import trace as tracing  # noqa: E402
from bench.spans import Spans  # noqa: E402

TRACE_DIR = ROOT / ".bench_out" / "trace"
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def chips(n: int):
    """The first ``n`` TPU devices; :class:`NoChip` without them."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"the benchmark runs on a TPU only; JAX's backend is "
                     f"{backend!r}")
    devices = jax.devices()
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips; JAX sees "
                     f"{len(devices)}")
    return devices[:n]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), caching every program."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


class CompileCount:
    """Traces and compiles JAX reports; read around the window."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.n += 1


class GcCount:
    """Python's garbage collections while it is registered: the count per
    generation, their total milliseconds and the longest. A log line, not
    a metric."""

    def __init__(self):
        self.counts = [0, 0, 0]
        self.total_ms = self.max_ms = 0.0
        self._t0 = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            ms = 1e3 * (time.perf_counter() - self._t0)
            self.counts[info["generation"]] += 1
            self.total_ms += ms
            self.max_ms = max(self.max_ms, ms)
            self._t0 = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def summary(self) -> dict:
        return {"gen0": self.counts[0], "gen1": self.counts[1],
                "gen2": self.counts[2], "total_ms": self.total_ms,
                "max_ms": self.max_ms}


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # Python calls would swamp the host
    opts.host_tracer_level = 2
    return opts


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            t_start: float, compiles: CompileCount = None):
    """Set up, measure and check one run. Returns (result, log lines)."""
    import jax
    log = []
    driver = cell.driver().Driver(cell, seed)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    spans = Spans(annotate=trace)
    c0 = compiles.n if compiles else 0
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR),
                                 profiler_options=_profile_options())
    try:
        with GcCount() as collections, spans.span(tracing.WINDOW):
            facts = driver.window(seconds, spans)
    finally:
        if trace:
            jax.profiler.stop_trace()
    if compiles:
        log.append(f"compiles_in_window={compiles.n - c0}")
    log.append("gc_in_window=" + json.dumps(collections.summary()))
    summary = None
    if trace:
        summary = tracing.summarize(tracing.extract(
            tracing.load(str(TRACE_DIR)), tracing.plane_names(devices)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "device_kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": int(peak)}
    metrics = {}
    if trace:
        peaks = cells.peaks(dev.device_kind)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(facts, summary, peaks)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        log.append("device_ops_seen=" + json.dumps(summary["op_names"][:64]))
    else:
        e2e = driver.end_to_end(facts)
        for m in cell.end_to_end:
            v = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log.append("facts=" + json.dumps(facts))
    log.append("op_counts=" + json.dumps(driver.op_counts(facts)))
    log.append("setup_parts=" + json.dumps(driver.setup_parts))
    t_check = time.perf_counter()
    driver.release()
    numbers = driver.check()
    limits = driver.ref.LIMITS
    log.append("info=" + json.dumps(driver.info()))
    log.append(f"check_s={time.perf_counter() - t_check}")
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    result = {"correct": all(v <= limits[k] for k, v in numbers.items()),
              "attempted": int(facts["units"]), "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    log += [f"check {k}={v['value']} limit={v['limit']}"
            for k, v in checks.items()]
    return result, log


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cells.resolve(args.workload)
        sys.path.insert(0, str(ROOT / "src"))
        import repro  # noqa: F401
    except (FileNotFoundError, KeyError, ImportError) as e:
        print(f"bench: cannot load the cell or the program: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 2
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    import jax
    print(f"bench: compile cache {enable_compile_cache()}", file=sys.stderr)
    compiles = CompileCount()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    result, log = measure(cell, args.seed, args.seconds, bool(args.trace),
                          devices, T_START, compiles)
    for line in log:
        print(f"bench: {line}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
