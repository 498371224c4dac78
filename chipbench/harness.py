"""One run of one cell: set-up, timed window, optional trace, check.

Everything the run needs is found by name (``chipbench/plugins.py``):
``BENCHMARK.json`` names the cell, the cell its configuration
(``chipbench/configs/<config>.json``) and its traffic mix
(``chipbench/traffic/<traffic>.json``); the traffic's ``kind`` names its
load (``chipbench/loads/<kind>.py``), and each metric its reader
(``chipbench/end_to_end/<name>.py``, ``chipbench/per_layer/<name>.py``).
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

from . import gen, plugins, reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SPAN = "chipbench.call"
#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = ".chipbench_jax_cache"


class Refused(Exception):
    """The run cannot be made here (no chip, too few chips, no program);
    it prints no result."""


def process_age() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic) for ``workload``."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json; "
                      f"known: {sorted(cells)}")
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = load_json(root, files[cell["config"]])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return bench, cell, config, traffic


def cell_metrics(bench: dict, workload: str, section: str) -> list[dict]:
    """The metrics of ``section`` that this cell reports."""
    return [m for m in bench[section]
            if workload in m.get("workloads", [workload])]


def metric_reader(section: str, name: str):
    """The ``read`` function of a metric of ``section`` (``end_to_end`` or
    ``per_layer``), found by name."""
    return plugins.find(section, name, "read")


def make_load(config: dict, traffic: dict, seed: int,
              opts: dict | None = None):
    """The load of ``traffic``'s kind, found by name."""
    cls = plugins.find("loads", traffic["kind"], "Load")
    return cls(config, traffic, seed, opts)


# ---------------------------------------------------------------------------
# the run


class _CompileCounter:
    """Counts jaxpr traces and XLA compilations (``jax.monitoring``)."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax.monitoring
        self.counts = {"traces": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)


def _device_info(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_window(load, seconds: float, traced: bool):
    """Calls back to back while fewer than ``seconds`` have passed since
    the first call started. Returns (per-call host times, error)."""
    import jax
    times = []
    t0 = time.perf_counter()
    error = None
    while not times or time.perf_counter() - t0 < seconds:
        i = len(times)
        span = (jax.profiler.TraceAnnotation(SPAN, index=i) if traced
                else contextlib.nullcontext())
        s = time.perf_counter()
        try:
            with span:
                units = load.call(i)
        except Exception as e:                    # noqa: BLE001
            traceback.print_exc()
            error = f"call {i} raised {type(e).__name__}: {e}"
            break
        times.append((s, time.perf_counter(), units))
    return times, error


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, require_tpu: bool = True,
        config_override: dict | None = None, compile_cache: bool = True,
        opts: dict | None = None, out=None, err=None) -> int:
    """Run ``workload`` once and print its result line. Returns the exit
    code. ``require_tpu``, ``config_override`` and ``compile_cache``
    exist for the CPU tests; ``opts`` are front-door options on top of
    the configuration's (``assign_precision="bf16"`` is the control of
    ``correct``)."""
    out = out or sys.stdout
    err = err or sys.stderr
    bench, cell, config, traffic = load_cell(root, workload)
    config = {**config, **(config_override or {})}
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"no partitioner under {src}: run from a checkout "
                      "of the repository")
    if src not in sys.path:
        sys.path.insert(0, src)
    if compile_cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                               CACHE_DIR)
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devices[0].platform!r} "
                      "devices; the benchmark only measures on the chip")
    if len(devices) < cell["chips"]:
        raise Refused(f"{workload} needs {cell['chips']} chips, JAX found "
                      f"{len(devices)}")
    used = devices[:cell["chips"]]
    counter = _CompileCounter()
    load = make_load(config, traffic, seed, opts)
    load.setup()
    setup_s = process_age()
    before = counter.snapshot()
    tdir = tempfile.mkdtemp(prefix="chipbench-") if trace else None
    if trace:
        popts = jax.profiler.ProfileOptions()
        popts.python_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=popts)
        trace_zero = time.perf_counter()
    times, error = run_window(load, seconds, trace)
    if trace:
        jax.profiler.stop_trace()
    in_window = {k: v - before[k] for k, v in counter.snapshot().items()}
    counter.close()
    peak = _peak_bytes(used)
    print(f"chipbench: compilations in the window: {in_window}; "
          f"peak_bytes_in_use: {peak}", file=err, flush=True)

    result: dict = {"correct": False, "attempted": len(times) + bool(error),
                    "failed": int(bool(error))}
    metrics = {}
    window_s = times[-1][1] - times[0][0] if times else 0.0
    dev_extra = {}
    if trace and times:
        from . import tracefile
        host_spans = [((s - trace_zero) * 1e9, (e - trace_zero) * 1e9)
                      for s, e, _ in times]
        red = tracefile.reduce_dir(tdir, SPAN, host_spans)
        print(f"chipbench: call spans from the {red.spans_from}: "
              f"{red.spans}", file=err, flush=True)
        traced = TracedRun(load, red, used, config)
        for m in cell_metrics(bench, workload, "per_layer"):
            v = metric_reader("per_layer", m["name"])(traced)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = red.breakdown()
        dev_extra = {"busy_s": red.busy_seconds(), "window_s": red.window_s}
    elif times:
        window = Window(times, setup_s)
        for m in cell_metrics(bench, workload, "end_to_end"):
            v = metric_reader("end_to_end", m["name"])(window)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if tdir:
        shutil.rmtree(tdir, ignore_errors=True)
    result["metrics"] = metrics
    result["device"] = {**_device_info(used), "memory_peak_bytes": peak,
                        **dev_extra}

    answers = [load.answer(i) for i in range(len(times))]
    sweeps = [load.sweeps(i) for i in range(len(times))]
    load.results.clear()
    load.inputs.clear()
    gc.collect()
    limits = {"out_of_range": 0, "imbalance": config["epsilon"],
              **config["limits"]}
    t_ref = time.perf_counter()
    try:
        readings = reference.check_calls(
            answers, config["k"], gen.derive_seed(seed, 3), limits)
    except Exception as e:                        # noqa: BLE001
        traceback.print_exc()
        readings = {"failed_calls": len(answers), "error": repr(e)}
    ref_s = time.perf_counter() - t_ref
    result["failed"] += readings.get("failed_calls", 0)
    checks = {}
    for name, limit in limits.items():
        value = readings.get(name)
        if value is not None and not math.isfinite(value):
            value = None                          # JSON has no infinity
        checks[name] = {"value": value, "limit": limit}
    ok = (error is None and "error" not in readings
          and all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values()))
    result["correct"] = bool(ok and result["failed"] == 0)
    print(f"chipbench: {workload} seed {seed}: {len(times)} calls in "
          f"{window_s} s, sweeps per call {sweeps}, reference took "
          f"{ref_s} s, checked in full "
          f"{readings.get('calls_checked_in_full')}", file=err, flush=True)
    if error:
        print(f"chipbench: {error}", file=err, flush=True)
    if "error" in readings:
        print(f"chipbench: reference failed: {readings['error']}", file=err,
              flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=err,
              flush=True)
    result["checks"] = checks
    print(json.dumps(result), file=out, flush=True)
    return 0


class Window:
    """What an end-to-end metric reader sees: the window's calls, each
    ``(start, end, points)`` on the host clock in seconds, and the
    set-up time."""

    def __init__(self, calls, setup_s: float):
        self.calls = calls
        self.setup_s = setup_s


class TracedRun:
    """What a per-layer metric reader sees: the calls of the traced
    window (their spans on the trace's clock and the sweeps each ran),
    the trace reduction, the chips used and the configuration."""

    def __init__(self, load, reduction, devices, config):
        self.trace = reduction
        self.config = config
        self.device_kind = devices[0].device_kind
        self.chips = len(devices)
        spans = reduction.spans
        if len(spans) < len(load.results):
            raise RuntimeError(f"trace holds {len(spans)} call spans, the "
                               f"window made {len(load.results)} calls")
        self.calls = [{"start_ns": s, "end_ns": e, "sweeps": load.sweeps(i),
                       "n": config["n"], "k": config["k"], "d": config["d"]}
                      for i, (s, e) in enumerate(spans[:len(load.results)])]
