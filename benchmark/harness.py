"""One run of one cell: set up, warm, measure a window of back-to-back
jobs, read the device, compare what the last job left with the plain
reference, and reduce it all to the result line.

Everything that belongs to one configuration, one traffic mix, one app
or one per-layer metric sits in a file of its own that is found by its
name (``configs/``, ``traffic/``, ``apps/``, ``metrics/``); nothing here
knows a cell.  From the program the harness takes the public path a user
takes (``Context``, a taskpool builder, ``params``) and its counters.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, ".bench_out")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> tuple:
    """(spec, cell, config, traffic) of the cell ``name`` of
    ``BENCHMARK.json``: the configuration from its ``file``, the traffic
    mix from ``traffic/<traffic>.json`` beside this file."""
    spec = _read_json(root, "BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                         f"(has: {', '.join(sorted(cells))})")
    cell = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = _read_json(root, cfg["file"])
    traffic = _read_json(root, spec["paths"][0], "traffic",
                         cell["traffic"] + ".json")
    return spec, cell, config, traffic


def device_info() -> dict:
    """What JAX reports: carried by every line that carries a number."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> dict:
    """A cell measures the chip: no TPU, or fewer chips than it asks
    for, ends the run with a message and no result."""
    dev = device_info()
    if dev["platform"] != "tpu" or dev["count"] < chips:
        raise SystemExit(
            f"benchmark: this cell needs {chips} TPU chip(s); JAX reports "
            f"platform={dev['platform']!r} device_kind={dev['kind']!r} "
            f"count={dev['count']}.  Nothing was measured.")
    return dev


def place_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the key), taken by the program too:
    ``init_devices`` sets none where the environment names one.  No size
    cap: a cell whose programs were evicted would start cold every run."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    # keep the small programs too: a warm set-up then compiles nothing
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    return CACHE_DIR


class Spans:
    """The benchmark's own host spans: kept in memory on the host clock,
    and written into the profiler's trace while one is being taken."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.closed = []                 # (name, start_s, end_s)

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation("bench:" + name)
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.closed.append((name, t0, time.perf_counter()))

    def total(self, name: str, lo: float, hi: float) -> float:
        return sum(min(e, hi) - max(s, lo) for n, s, e in self.closed
                   if n == name and min(e, hi) > max(s, lo))


def _one_job(ctx, app, span) -> tuple:
    """Stage, insert, wait, fence: what a caller of the solver does, and
    what the comparison is made on.  Returns (insert time, fence end)."""
    from benchmark import tiles
    with span("stage"):
        app.stage()
    t_ins = time.perf_counter()
    with span("insert"):
        ctx.add_taskpool(app.pool())
    with span("wait"):
        ctx.wait()
    with span("fence"):
        tiles.fence(*app.outputs)
    return t_ins, time.perf_counter()


def _start_trace(trace_dir: str) -> None:
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # the device and our spans only
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _devices_report(ctx) -> list:
    out = []
    for d in ctx.device_registry.accelerators:
        mem = d.jdev.memory_stats() or {}
        out.append({"name": d.name, "stats": d.stats.as_dict(),
                    "fuse_failures": {f"{k}x{w}": v for (k, w), v
                                      in d.fuse_failures.items()},
                    "peak_bytes_in_use": mem.get("peak_bytes_in_use")})
    return out


def run_cell(spec: dict, cell: dict, config: dict, traffic: dict,
             seed: int, seconds: float, trace: bool, t_start: float,
             app_factory=None) -> dict:
    """Run ``cell`` once and return the result line as a dict.  The
    caller has made sure of the devices; ``app_factory`` lets a test put
    a broken app in the place of ``apps/<config.app>.py``."""
    import jax
    from parsec_tpu.core.context import Context
    from parsec_tpu.devices.xla import wait_fuse_warm
    from parsec_tpu.utils.mca import params
    from benchmark import trace as trace_mod

    dev = device_info()
    chips = int(cell["chips"])
    if app_factory is None:
        app_factory = importlib.import_module(
            f"benchmark.apps.{config['app']}").Job
    compiles = []                        # (host time, seconds) of each
    listener = lambda event, secs, **kw: (
        compiles.append((time.perf_counter(), secs))
        if event == COMPILE_EVENT else None)
    jax.monitoring.register_event_duration_secs_listener(listener)
    mca = {**config.get("mca", {}), "device_max": chips}
    for k, v in mca.items():
        params.set(k, v)
    span = Spans(trace)
    jobs, failed, error = [], 0, None
    trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
    try:
        with Context(nb_cores=4) as ctx:
            t_ctx = time.perf_counter()
            app = app_factory(config, traffic, ctx, seed)
            app.setup()
            warm = Spans(False)
            warm_s = []
            for _ in range(int(config.get("warm_jobs", 2))):
                t_w = time.perf_counter()
                _one_job(ctx, app, warm)
                wait_fuse_warm()
                warm_s.append(round(time.perf_counter() - t_w, 3))
            gc.collect()
            log(f"benchmark: set-up: {t_ctx - t_start:.3f}s to the Context, "
                f"warm jobs (fused-width compiles waited out) {warm_s}s "
                f"{dev}")
            ici0 = ctx.ici.stats.as_dict() if ctx.ici is not None else {}
            if trace:
                _start_trace(trace_dir)
            t_open = time.perf_counter()
            setup_s = t_open - t_start
            with span("window"):
                while True:
                    try:
                        jobs.append(_one_job(ctx, app, span))
                    except Exception as exc:      # the run goes on to report
                        failed, error = failed + 1, repr(exc)
                        break
                    if time.perf_counter() - t_open >= seconds:
                        break
            t_close = time.perf_counter()
            if trace:
                jax.profiler.stop_trace()
            devices = _devices_report(ctx)
            ici = {k: v - ici0[k] for k, v in
                   ctx.ici.stats.as_dict().items()} if ici0 else {}
            peak_bytes = max((d["peak_bytes_in_use"] or 0) for d in devices)
            # the comparison: after the window, after the peak is read
            t_chk = time.perf_counter()
            checked = app.check() if not failed else {"numbers": {},
                                                      "notes": {}}
            check_s = time.perf_counter() - t_chk
            limits = dict(app.limits)
            app.drop()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
        for k in mca:
            params.unset(k)

    numbers = dict(checked["numbers"])
    numbers["device_faults"] = float(sum(d["stats"]["faults"]
                                         for d in devices))
    limits["device_faults"] = 0.0
    compared = {k: {"value": v, "limit": limits[k]}
                for k, v in numbers.items()}
    correct = (not failed and bool(checked["numbers"])
               and all(c["value"] <= c["limit"] for c in compared.values()))

    window_s = t_close - t_open
    run = {"config": config, "traffic": traffic, "chips": chips,
           "device": dev, "jobs": jobs, "t_open": t_open, "t_close": t_close,
           "window_s": window_s, "setup_s": setup_s, "spans": span,
           "flop_per_job": app.flop, "tasks_per_job": app.tasks,
           "compiles_in_window": sum(1 for t, _ in compiles
                                     if t_open <= t <= t_close),
           "devices": devices, "ici": ici, "trace": None}
    device = {**dev, "memory_peak_bytes": peak_bytes}
    breakdown = None
    if trace:
        t_red = time.perf_counter()
        tr = run["trace"] = trace_mod.load(trace_dir)
        lo, hi = trace_mod.window(tr)
        busy = trace_mod.busy(tr)
        used = sorted(busy.values(), reverse=True)[:chips]
        # mean over the chips used; the metrics read the same two numbers
        device["busy_s"] = sum(used) / max(len(used), 1)
        device["window_s"] = (hi - lo) / 1e9
        if used:                  # no device plane, nothing to read
            run["traced"] = {k: device[k] for k in ("busy_s", "window_s")}
        breakdown = {"device_ops": trace_mod.device_ops(tr),
                     "idle_gaps": trace_mod.idle_gaps(tr)}
        log(f"benchmark: programs by device time [name, s, executions] "
            f"{trace_mod.programs(tr)} {dev}")
        log(f"benchmark: trace reduced in "
            f"{time.perf_counter() - t_red:.1f}s; busy seconds by chip "
            f"{ {p: round(b, 4) for p, b in busy.items()} } {dev}")

    wanted = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        # ``tasks_per_s.host_paced`` is ``metrics/tasks_per_s.py`` again,
        # under the end-to-end metric its cells report
        reader = importlib.import_module(
            f"benchmark.metrics.{m['name'].split('.', 1)[0]}")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    job_s = [round(e - s, 4) for s, e in jobs]
    log(f"benchmark: {cell['name']} seed={seed} jobs={len(jobs)} "
        f"job_s={job_s} window_s={window_s:.3f} setup_s={setup_s:.3f} "
        f"check_s={check_s:.3f} compiles_in_window="
        f"{run['compiles_in_window']} ici={ici} error={error} {dev}")
    for d in devices:
        log(f"benchmark: {d} {dev}")
    log(f"benchmark: notes {checked['notes']} {dev}")
    for k, c in compared.items():
        log(f"benchmark: compared {k} = {c['value']!r} "
            f"(limit {c['limit']!r}) "
            f"{'ok' if c['value'] <= c['limit'] else 'OVER'} {dev}")
    result = {"correct": correct, "attempted": len(jobs) + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result
