"""What the host was doing in every idle gap of the device: the
runtime's own thread-state spans (``parsec:*``, written into the
profiler's trace by parsec_tpu/prof/pins.py TraceMePins) joined to the
device's program executions on the profiler's one clock.

Reduced in two steps, like ``trace.py``.  ``load`` turns the run's
``.xplane.pb`` into plain data::

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...]},
     "threads": [[[name, start_ns, dur_ns, args], ...], ...],
     "done": [start_ns, ...]}

``devices`` holds the ``XLA Modules`` events of each chip, ``threads``
one list a host thread line that carries ``parsec:`` or ``bench:``
spans, with each span's arguments, ``done`` the host times at which
PJRT noticed an execution's end.  ``reduce`` works on that alone
(tests/ holds a hand-made trace and one recorded on the chip).

What the v5e's trace looks like (looked at by hand, PR 25): every host
thread line is called ``python``, so a thread is known by the spans on
it — ``parsec:mgr.*`` a manager of the device its ``mgr.launch`` spans
name, ``parsec:fin.*`` that device's completer, ``parsec:worker.idle``
a worker; TraceMe arguments come back as the event's ``stats``, typed.
A span that is open when the session starts or stops is not in the
trace, so a thread's first and last episode are missing.  The device
plane has a clock of its own: in the first trace looked at its events
lay 1.09-1.27 ms BEFORE the host events that caused them (PR 24's
fixture: a third of a millisecond after), so every run measures the
offset itself (``clock_offset_ns``) and device times are corrected by it
before anything is joined.  The host plane also carries PJRT's own
events; ``tpu::System::Execute=>Done`` comes once an execution, in
order, when the host notices its end.

**The join.**  A device's queue is in order: the k-th execution of
program P in the trace is the k-th ``mgr.dispatch`` span that names P.
**The attribution** of an idle gap ``[g0, g1)`` that ends where
execution X starts, D being X's dispatch span and L the ``mgr.launch``
around D:

- ``device_queue``  ``[max(g0, D.end), g1)``: the jitted call had
  returned and the chip had not started (PJRT, transfers);
- ``launch``  ``[max(g0, L.begin), min(g1, D.end))``: the runtime was
  popping, staging and calling this very launch;
- before ``L.begin``, by what the device's manager threads were doing:
  ``backpressure`` while one sat in ``mgr.inflight_wait``, else
  ``launch`` while one was inside another ``mgr.launch``, else
  ``starved`` while the benchmark's ``insert`` or ``wait`` span was open
  (no ready task had reached a manager), else ``outside`` (the benchmark
  was staging or fencing: not the runtime's time).

A gap that ends at the window's end, or before a program with no
dispatch span (the benchmark's own), is split by the last rule alone.
The five buckets partition the gaps; ``reduce`` refuses (None) a run in
which they do not add up to the harness's own idle time within a point,
or in which the join does not hold.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

from benchmark import harness, trace

PREFIX = "parsec:"
#: the runtime's dispatched programs (devices/xla.py names them so); its
#: stage-in's own small programs (zeros for a NEW flow) are not dispatched
RUNTIME_PROGRAM = "jit_parsec_"
#: PJRT's host event for "the execution has ended", one an execution
DONE_EVENT = "tpu::System::Execute=>Done"
BUCKETS = ("starved", "launch", "backpressure", "device_queue", "outside")
#: dispatch spans and executions of one program may differ by this many
#: (a span cut by the session's start or stop at either edge)
JOIN_SLACK = 2

_loaded = {}
_reduced = {}


def newest_trace(root: str = None) -> str:
    """The newest ``.xplane.pb`` under the harness's trace directory: a
    process runs one cell, and the harness empties the cell's directory
    before it traces."""
    root = root or os.path.join(harness.OUT_DIR, "trace")
    paths = glob.glob(os.path.join(root, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return max(paths, key=os.path.getmtime)


def load(path: str = None) -> dict:
    """The trace at ``path`` (default: the run's own) as plain data,
    read once a process."""
    path = path or newest_trace()
    if path in _loaded:
        return _loaded[path]
    from jax.profiler import ProfileData
    out = {"devices": {}, "threads": [], "done": []}
    for plane in ProfileData.from_file(path).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            out["devices"][plane.name] = [
                [e.name, int(e.start_ns), int(e.duration_ns)]
                for ln in plane.lines if ln.name == trace.MODULE_LINE
                for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = [[e.name, int(e.start_ns), int(e.duration_ns),
                        dict(e.stats)] for e in ln.events
                       if e.name.startswith((PREFIX, trace.SPAN_PREFIX))]
                if evs:
                    out["threads"].append(evs)
                out["done"] += [int(e.start_ns) for e in ln.events
                                if e.name == DONE_EVENT]
    out["done"].sort()
    _loaded[path] = out
    return out


# -- interval arithmetic on sorted lists of disjoint (start, end) ----------

def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def subtract(a: list, b: list) -> list:
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if e > s:
            out.append((s, e))
    return out


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _within(ivs: list, starts: list, a: int, b: int) -> int:
    """Length of ``[a, b)`` covered by the sorted disjoint ``ivs``."""
    if b <= a:
        return 0
    n = 0
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(ivs) and ivs[i][0] < b:
        n += max(0, min(ivs[i][1], b) - max(ivs[i][0], a))
        i += 1
    return n


# -- the spans, by thread --------------------------------------------------

def program_of(module_event: str) -> str:
    """``jit_parsec_GEMM_x8(1234)`` -> ``jit_parsec_GEMM_x8``."""
    return re.sub(r"\(\d+\)$", "", module_event)


def _spans(data: dict, name: str):
    """(start, end, args, thread index) of every span called ``name``."""
    for t, evs in enumerate(data["threads"]):
        for n, s, d, a in evs:
            if n == name:
                yield s, s + d, a, t


def window(data: dict) -> tuple:
    for s, e, _a, _t in _spans(data, trace.SPAN_PREFIX + "window"):
        return s, e
    raise ValueError("the trace holds no bench:window span")


def launches(data: dict) -> list:
    """Every ``mgr.launch`` with what its thread did inside it:
    ``{"s", "e", "args", "dev", "dispatch": [(s, e, args)],
    "inflight_wait": [(s, e)]}``, sorted by start.  Dispatch spans that
    lie in no launch (a chain forced from ``sync()``) come as launches of
    their own, ``dev`` None."""
    out = []
    for evs in data["threads"]:
        ls = sorted((s, s + d, a) for n, s, d, a in evs
                    if n == PREFIX + "mgr.launch")
        mine = [{"s": s, "e": e, "args": a, "dev": a.get("dev"),
                 "dispatch": [], "inflight_wait": []} for s, e, a in ls]
        starts = [m["s"] for m in mine]
        for n, s, d, a in evs:
            kind = n[len(PREFIX):] if n.startswith(PREFIX) else ""
            if kind not in ("mgr.dispatch", "mgr.inflight_wait"):
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s + d <= mine[i]["e"]:
                mine[i][kind[4:]].append((s, s + d, a))
            elif kind == "mgr.dispatch":
                out.append({"s": s, "e": s + d, "args": {}, "dev": None,
                            "dispatch": [(s, s + d, a)],
                            "inflight_wait": []})
        out += mine
    return sorted(out, key=lambda m: m["s"])


def _plane_of(dev, planes: list):
    """``tpu:0`` -> ``/device:TPU:0``; a span with no device goes to the
    one chip where there is one."""
    if dev is None:
        return planes[0] if len(planes) == 1 else None
    for p in planes:
        if p.rsplit(":", 1)[-1] == str(dev).rsplit(":", 1)[-1]:
            return p
    return None


def join(data: dict) -> dict:
    """Per device plane ``{"pairs": [(exec start, exec end, dispatch
    (s, e, args), launch)], "counts": {program: [spans, executions]},
    "ok": bool}``: the k-th execution of a program with its k-th
    dispatch span.  ``ok`` is False where the two counts of any program
    differ by more than ``JOIN_SLACK``."""
    planes = sorted(data["devices"])
    by_plane = {p: {} for p in planes}
    for la in launches(data):
        p = _plane_of(la["dev"], planes)
        if p is None:
            continue
        for d in la["dispatch"]:
            by_plane[p].setdefault(d[2].get("program"), []).append((d, la))
    out = {}
    for p in planes:
        execs = {}
        for name, s, d in sorted(data["devices"][p], key=lambda e: e[1]):
            if not trace.is_own(name):
                execs.setdefault(program_of(name), []).append((s, s + d))
        pairs, counts, ok = [], {}, True
        for prog in sorted(set(execs) | set(by_plane[p])):
            ds = sorted(by_plane[p].get(prog, []), key=lambda x: x[0][0])
            xs = execs.get(prog, [])
            counts[prog] = [len(ds), len(xs)]
            if prog.startswith(RUNTIME_PROGRAM):
                ok = ok and abs(len(ds) - len(xs)) <= JOIN_SLACK
            pairs += [(x[0], x[1], d, la) for x, (d, la) in zip(xs, ds)]
        out[p] = {"pairs": sorted(pairs, key=lambda x: x[0]),
                  "counts": counts, "ok": ok}
    return out


def offset_samples(data: dict) -> tuple:
    """What bounds ``device clock - host clock``, as two lists of (host
    time, ns): from above, ``execution start - dispatch begin`` of every
    joined pair (no execution starts before its dispatch call began);
    from below, ``execution end - its Done event`` (the host notices an
    end after it happened).  The second needs the Done events to number
    the one chip's executions: it is empty where they do not, and with
    several chips."""
    above = [(d[0], xs - d[0]) for j in join(data).values()
             for xs, _xe, d, _la in j["pairs"]]
    below = []
    planes = [evs for evs in data["devices"].values() if evs]
    done = data.get("done") or []
    if len(planes) == 1 and len(done) == len(planes[0]):
        ends = sorted(s + d for _n, s, d in planes[0])
        below = [(t, e - t) for e, t in zip(ends, done)]
    return above, below


def clock_offset_ns(data: dict, t0: int = None, t1: int = None,
                    samples: tuple = None):
    """``(low, high)`` in ns: the interval in which ``device clock -
    host clock`` lies (``low`` None without the lower samples), or None
    with nothing joined; from the host times ``[t0, t1)`` alone where
    they are given (the two clocks may drift).  ``samples`` saves
    computing ``offset_samples`` again."""
    def inside(t):
        return (t0 is None or t0 <= t) and (t1 is None or t < t1)

    above, below = samples or offset_samples(data)
    high = [v for t, v in above if inside(t)]
    low = [v for t, v in below if inside(t)]
    if not high:
        return None
    return (max(low) if low else None), min(high)


def reduce(data: dict, idle_s: float = None, shift_ns: int = 0) -> dict:
    """The idle buckets, the join and the thread times of one traced
    window.  ``shift_ns`` is taken off every device time first (the
    clock correction).  ``idle_s`` is the harness's own idle time
    (window - busy, mean over chips) to close on; ``closed`` says
    whether the buckets do, within a point of the window."""
    lo, hi = window(data)
    if shift_ns:
        data = {"threads": data["threads"],
                "devices": {p: [[n, s - shift_ns, d] for n, s, d in evs]
                            for p, evs in data["devices"].items()}}
    joined = join(data)
    all_launches = launches(data)
    bench = union((s, e) for name in ("insert", "wait") for s, e, _a, _t
                  in _spans(data, trace.SPAN_PREFIX + name))
    buckets = dict.fromkeys(BUCKETS, 0)
    names = sorted(data["devices"])
    planes = [p for p in names if data["devices"][p]]
    for p in planes:
        mine = [la for la in all_launches
                if _plane_of(la["dev"], names) == p]
        bp = union(w[:2] for la in mine for w in la["inflight_wait"])
        other = subtract(union((la["s"], la["e"]) for la in mine), bp)
        starved = subtract(subtract(bench, bp), other)
        layers = [("backpressure", bp), ("launch", other),
                  ("starved", starved)]
        layers = [(k, ivs, [s for s, _e in ivs]) for k, ivs in layers]

        def by_state(a, b, acc):
            left = max(b - a, 0)
            for key, ivs, starts in layers:
                n = _within(ivs, starts, a, b)
                acc[key] += n
                left -= n
            acc["outside"] += left

        busy = union((max(s, lo), min(s + d, hi))
                     for _n, s, d in data["devices"][p])
        gaps = subtract([(lo, hi)], busy)
        starts_at = {xs: (d, la) for xs, _xe, d, la in joined[p]["pairs"]}
        acc = dict.fromkeys(BUCKETS, 0)
        for g0, g1 in gaps:
            hit = starts_at.get(g1)
            if hit is None:
                by_state(g0, g1, acc)
                continue
            d, la = hit
            q0 = min(max(g0, d[1]), g1)
            l0 = min(max(g0, la["s"]), q0)
            acc["device_queue"] += g1 - q0
            acc["launch"] += q0 - l0
            by_state(g0, l0, acc)
        for k in BUCKETS:
            buckets[k] += acc[k]
    ndev = max(len(planes), 1)
    win_s = (hi - lo) / 1e9
    out = {"window_s": win_s,
           "buckets_s": {k: v / 1e9 / ndev for k, v in buckets.items()},
           "join": {p: j["counts"] for p, j in joined.items()},
           "join_ok": bool(planes) and all(joined[p]["ok"] for p in planes)}
    out["idle_s"] = sum(out["buckets_s"].values())
    out["closed"] = idle_s is None or \
        abs(out["idle_s"] - idle_s) <= 0.01 * win_s

    inside = [la for la in all_launches
              if la["dev"] is not None and lo <= la["s"] and la["e"] <= hi]
    out["launches"] = len(inside)
    out["launch_host_ms"] = None if not inside else sum(
        la["e"] - la["s"] - sum(w[1] - w[0] for w in la["inflight_wait"])
        for la in inside) / len(inside) / 1e6
    rel = [(s, e) for s, e, _a, _t in _spans(data, PREFIX + "fin.release")
           if lo <= s and e <= hi]
    out["released"] = len(rel)
    out["release_us_per_task"] = None if not rel else \
        total(rel) / len(rel) / 1e3
    # a worker is certainly busy between two of its recorded idle
    # episodes; before its first and after its last it is not known
    busy_ns, workers = 0, 0
    for evs in data["threads"]:
        idle = sorted((s, s + d) for n, s, d, _a in evs
                      if n == PREFIX + "worker.idle")
        if not idle:
            continue
        workers += 1
        busy_ns += sum(max(0, min(b[0], hi) - max(a[1], lo))
                       for a, b in zip(idle, idle[1:]))
    out["workers"] = workers
    out["worker_busy_s"] = busy_ns / 1e9 if workers else None
    # every span by name: [how many, seconds] inside the window, all
    # threads together (for PERF.md's breakdown; no metric reads it)
    totals = {}
    for evs in data["threads"]:
        for n, s, d, _a in evs:
            a, b = max(s, lo), min(s + d, hi)
            if b > a and n.startswith(PREFIX):
                c = totals.setdefault(n[len(PREFIX):], [0, 0.0])
                c[0] += 1
                c[1] += (b - a) / 1e9
    out["span_totals"] = dict(sorted(totals.items()))
    return out


def of_run(run: dict):
    """The reduction of the run the harness has just traced, or None
    where there is nothing to read (no trace; a program without the
    spans, as the parent of PR 25 is; a join or a sum that does not
    hold).  Device times are corrected by the middle of the measured
    clock-offset interval.  Says what it found on standard error, once."""
    traced = run.get("traced")
    if run.get("trace") is None or not traced:
        return None
    key = id(run["trace"])
    if key in _reduced:
        return _reduced[key]
    dev = run.get("device")
    idle_s = traced["window_s"] - traced["busy_s"]
    red = None
    try:
        data = load()
        samples = offset_samples(data)
        off = clock_offset_ns(data, samples=samples)
        if off is not None:
            low, high = off
            # (the two ends can cross by some tens of us where the
            # clocks drift over the window: PERF.md section 3)
            ends = (high, high) if low is None else (low, high)
            red = reduce(data, idle_s, sum(ends) // 2)
            red["clock_offset_ns"] = off
            lo, hi = window(data)
            red["clock_offset_quarters_ns"] = [
                clock_offset_ns(data, lo + i * (hi - lo) // 4,
                                lo + (i + 1) * (hi - lo) // 4, samples)
                for i in range(4)]
            # the same at both ends of the offset's interval: how far
            # each bucket can move with what is not known of the clock
            edge = [reduce(data, idle_s, e)["buckets_s"] for e in ends]
            red["buckets_range_s"] = {
                k: sorted(round(b[k], 4) for b in edge) for k in BUCKETS}
            harness.log(
                "benchmark: runtime spans: idle buckets s "
                f"{ {k: round(v, 4) for k, v in red['buckets_s'].items()} } "
                f"sum {red['idle_s']:.4f}, the harness's idle "
                f"{idle_s:.4f} (closed={red['closed']}); device clock - "
                f"host clock in (low, high) ns {off}, by quarter of the "
                f"window {red['clock_offset_quarters_ns']}, corrected by "
                "the middle; buckets at the two ends "
                f"{red['buckets_range_s']} {dev}")
            harness.log(
                f"benchmark: runtime spans: launches {red['launches']} "
                f"launch_host_ms {red['launch_host_ms']} released "
                f"{red['released']} release_us_per_task "
                f"{red['release_us_per_task']} workers {red['workers']} "
                f"worker_busy_s {red['worker_busy_s']}; spans in the "
                "window [count, s] "
                f"{ {k: [c, round(t, 4)] for k, (c, t) in red['span_totals'].items()} } "
                f"{dev}")
            harness.log("benchmark: runtime spans: join [dispatch spans, "
                        f"executions] by program {red['join']} "
                        f"ok={red['join_ok']} {dev}")
            if not (red["closed"] and red["join_ok"]):
                red = None
    except (OSError, ValueError) as exc:
        harness.log(f"benchmark: runtime spans: nothing read: {exc!r} {dev}")
        red = None
    _reduced[key] = red
    return red


def idle_pct(run: dict, bucket: str):
    """One idle bucket as a share of the traced window, in %."""
    red = of_run(run)
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * red["buckets_s"][bucket] / red["window_s"]
