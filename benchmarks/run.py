#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process, one cell, one JSON object on the last line of stdout.
Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by name (``harness/manifest.py``);
this file holds what every cell shares: the look for the chip, set-up
and its clock, the closed loop that is timed, the trace, the call of
the reference once the window has closed, and the result line.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()   # the process's start, as near as Python sees it

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import manifest as mf  # noqa: E402
from harness import readers  # noqa: E402
from harness import trace as tr  # noqa: E402

TRACE_SECONDS = 6.0     # a traced run measures this much of the window
QUEUE_DEPTH = 8         # calls kept queued on the device: 1.7 to 3.5 s of work
RC_NO_CHIP = 2


class Context:
    """What a family's adapter and a metric's reader are handed."""

    def __init__(self, cell: mf.Cell, seed: int, out_dir: str):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.limits = cell.limits
        self.seed = int(seed)
        self.out_dir = out_dir
        self.devices: list = []
        self.counters: dict[str, float] = {}
        self.spans: list[tuple[str, float, float]] = []
        self.compared: list[dict] = []
        self.controls: list[tuple[str, float]] = []
        self.readings_s: list[float] = []
        self.reduced: dict | None = None
        self.peaks: dict = {}
        self.shapes: dict = {}    # the family fills it in set-up
        self.memory_peak_bytes = 0

    def say(self, msg: str) -> None:
        print(msg, flush=True)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))

    def span_seconds(self, name: str) -> float | None:
        got = [t1 - t0 for n, t0, t1 in self.spans if n == name]
        return sum(got) if got else None

    def count(self, name: str, n: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def compare(self, name: str, value: float, limit: float) -> None:
        """One number of ``correct`` beside its limit, printed in every
        run. A value that is not a number has failed."""
        value = float(value)
        ok = value == value and value <= float(limit)
        self.compared.append(
            {"name": name, "value": value, "limit": float(limit), "ok": ok})
        self.say(f"[check] {name} = {value:.6g}  limit {limit:.6g}  "
                 f"{'ok' if ok else 'FAILED'}")

    def control(self, name: str, value: float) -> None:
        """A reading of the control (limit-setting runs only)."""
        self.controls.append((name, float(value)))
        self.say(f"[control] {name} = {float(value):.6g}")


class Monitor:
    """Compile seconds and persistent-cache traffic, as JAX reports
    them through ``jax.monitoring`` (what ``chip_smoke.py`` reads)."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.each: list[float] = []    # seconds of every compile, in order
        self.hits = 0
        self.misses = 0

    def install(self):
        import jax

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs
                self.compiles += 1
                self.each.append(round(secs, 2))

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self


def _annotate(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def _start_trace(trace_dir: str) -> None:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # annotations only; keep the host quiet
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def timed_window(ctx: Context, state, seconds: float, trace_dir: str | None):
    """The closed loop of one client that keeps ``QUEUE_DEPTH`` calls
    queued: a call takes the one before's output as a device value, as
    a training loop under JAX's asynchronous dispatch does, and the
    host waits for the oldest. So the device goes from one call to the
    next without the host in between, as it does inside the one long
    program a user's run is, and a host stall shorter than the queue
    costs nothing (this machine's host stalls a waiting thread for 0.1
    to 3 s about once a minute: PERF.md). A reading is the time from
    one call's end to the next one's. Dispatch stops when the queued
    calls will fill the window. Returns (calls, elapsed seconds): every
    call dispatched is waited for and counted, with its time."""
    on = trace_dir is not None
    if on:
        _start_trace(trace_dir)
    readings = ctx.readings_s
    queue: collections.deque = collections.deque()
    gc.collect()
    gc.disable()                   # no collector pause inside a reading
    t_start = t_last = time.perf_counter()
    deadline = t_start + seconds

    def top_up():
        a_call = statistics.median(readings) if readings else 0.0
        while len(queue) < QUEUE_DEPTH and \
                time.perf_counter() + len(queue) * a_call < deadline:
            with _annotate(on, "bench:dispatch"):
                queue.append(state.dispatch())

    with _annotate(on, tr.WINDOW_SPAN):
        top_up()
        while queue:
            with _annotate(on, "bench:sync"):
                state.sync(queue.popleft())
            now = time.perf_counter()
            readings.append(now - t_last)
            t_last = now
            top_up()
    gc.enable()
    if on:
        import jax

        jax.profiler.stop_trace()
    return len(readings), t_last - t_start


def read_memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             manifest_path: str | None = None, bench_dir: str = BENCH_DIR,
             require_tpu: bool = True, out_dir: str | None = None,
             root: str | None = None, control: bool = False
             ) -> tuple[int, dict | None]:
    """Run one cell; returns (exit code, the result object or None).

    ``require_tpu=False`` is for the tests beside the benchmark (a
    rehearsal on the CPU), ``control=True`` for limit-setting runs
    (the check also reads the control); the command line offers
    neither."""
    manifest_path = manifest_path or os.path.join(ROOT, "BENCHMARK.json")
    out_dir = out_dir or os.path.join(ROOT, ".bench_out")
    cell = mf.Cell(manifest_path, workload, bench_dir, root)
    ctx = Context(cell, seed, out_dir)
    if control:
        ctx.limits = dict(cell.limits, _control=True)

    import jax

    devs = jax.devices()
    # set-up is counted from here: the interpreter's, JAX's and the TPU
    # runtime's own start is the platform's, 10 to 12 s that swing by
    # more than a second from run to run and that no PR can move work
    # into; it is printed, and kept out of setup_s so that a second of
    # real set-up work shows
    t_setup = time.perf_counter()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        print(f"benchmark: cell {workload!r} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform!r} device(s) "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). "
              f"Nothing was run.", file=sys.stderr)
        return RC_NO_CHIP, None
    if len(devs) < cell.chips:
        raise RuntimeError(f"{workload}: {cell.chips} devices asked for, "
                           f"{len(devs)} present")
    ctx.devices = list(devs[:cell.chips])
    if devs[0].platform == "tpu":
        ctx.peaks = mf.peaks(devs[0].device_kind, bench_dir)

    from tpu_distalg.utils import compile_cache

    cache_dir = compile_cache.configure()
    # jax persists only compiles over a second by default; a warm run
    # has to find every program, the small ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    monitor = Monitor().install()
    ctx.say(f"[bench] cell {workload} seed {seed} seconds {seconds} "
            f"trace {int(trace)} | {devs[0].platform} "
            f"{devs[0].device_kind!r} x{len(ctx.devices)} | "
            f"compile cache {cache_dir}")

    family = cell.family()
    state = family.setup(ctx)
    setup_s = time.perf_counter() - t_setup
    c0 = monitor.compiles

    trace_dir = os.path.join(out_dir, "trace", workload) if trace else None
    calls, elapsed = timed_window(
        ctx, state, min(seconds, TRACE_SECONDS) if trace else seconds,
        trace_dir)
    ctx.memory_peak_bytes = read_memory_peak(ctx.devices)
    work = calls * state.work_per_call
    rate = work / elapsed
    ms = [r * 1e3 for r in ctx.readings_s]
    ctx.counters.update(
        compile_s=monitor.compile_s, cache_misses=monitor.misses,
        cache_hits=monitor.hits, window_calls=calls, window_s=elapsed,
        window_compiles=monitor.compiles - c0,
        steps_per_call=state.steps_per_call,
        work_per_call=state.work_per_call)
    ctx.say(f"[window] {calls} readings in {elapsed:.4f} s; call ms "
            f"median {statistics.median(ms):.4f} p95 "
            f"{readers.percentile(ms, 0.95):.4f} max {max(ms):.4f} (reading "
            f"{ms.index(max(ms)) + 1}); "
            f"{state.work_per_call} {state.work_unit} a call; "
            f"{cell.config['rate_metric']} {rate:.6g}; set-up "
            f"{setup_s:.3f} s after {t_setup - _T0:.3f} s of platform "
            f"start (compile {monitor.compile_s:.2f} s, cache "
            f"{monitor.hits} hit / {monitor.misses} miss)")
    ctx.say("[setup] spans s: " + ", ".join(
        f"{n} {t1 - t0:.2f}" for n, t0, t1 in ctx.spans)
        + f"; compiles over 0.5 s: {[x for x in monitor.each if x > 0.5]}")

    # the program's state goes before the reference comes, so that the
    # peak above stays the program's and both fit
    outputs = state.finish()
    t_ref = time.perf_counter()
    ctx.compare("window_compiles", monitor.compiles - c0, 0)
    family.check(ctx, outputs)
    ctx.say(f"[check] reference and comparison took "
            f"{time.perf_counter() - t_ref:.2f} s (not in setup_s)")
    correct = all(c["ok"] for c in ctx.compared)

    d0 = ctx.devices[0]
    result = {"correct": correct, "attempted": calls, "failed": 0,
              "metrics": {},
              "device": {"platform": d0.platform, "kind": d0.device_kind,
                         "count": len(ctx.devices),
                         "memory_peak_bytes": int(ctx.memory_peak_bytes)}}
    if not trace:
        values = {"setup_s": setup_s, cell.config["rate_metric"]: rate}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise KeyError(f"end-to-end metric {m['name']!r} is listed "
                               f"for {workload} and nothing measures it")
            result["metrics"][m["name"]] = {
                "value": values[m["name"]], "unit": m["unit"]}
    else:
        raw = tr.load_xplane(tr.find_xplane(trace_dir))
        ctx.reduced = tr.reduce(raw)
        result["device"]["busy_s"] = ctx.reduced["busy_s"]
        result["device"]["window_s"] = ctx.reduced["window_s"]
        result["breakdown"] = {
            "device_ops": ctx.reduced["device_ops"],
            "idle_gaps": ctx.reduced["idle_gaps"]}
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {
                    "value": float(value), "unit": m["unit"]}
        if ctx.reduced["busy_s"] <= 0:
            raise RuntimeError("traced run: no operation ran on the device")
    return 0, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        rc, result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    except Exception:
        traceback.print_exc()
        print("benchmark: the run failed; no result line", file=sys.stderr)
        return 1
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
