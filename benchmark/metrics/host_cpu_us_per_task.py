"""Layer: PTG, dep engine, scheduler.  What a task costs the host,
whoever pays: per thread line that carries ``parsec:`` spans the thread's
CPU clock at its last reading inside the window less that at its first,
summed over ALL such lines (managers, completers, workers, warmers, the
client), over the window's tasks (the DAG's formula x jobs), in
microseconds.  If the interpreter lock is saturated the cell's pace is
1e6 / this.

This file also holds the reduction that ``completer_cpu_us_per_task``
and ``gc_pause_pct`` share (``of_run``); it uses ``runtime_spans.load`` /
``window`` and the spans' own arguments only, never the device plane, so
every reader works on four chips (the host spans are whole there).

**What the numbers are.**  Since PR 35 the program's sink
(parsec_tpu/prof/pins.py ``TraceMePins``) puts two integers on a
``parsec:`` span, read off the emitting thread's CPU clock
(``time.thread_time_ns``): ``cpu_ns``, the time the thread was ON a core
between the span's begin and its end, and ``cpu_end_ns``, the clock's
absolute reading at the end — so ``cpu_end_ns - cpu_ns`` is its reading
at the begin, and the CPU a thread burned BETWEEN two readings (a
worker's task bodies, the benchmark's staging on the client's thread) is
known whatever spans lay between.  The sink rations the clock by time:
one span a process every 10 ms carries the integers, the threads taking
turns, so a line has its clock several times a second, and a span's own
``cpu_ns`` is a sample that no reader uses.  The clock counts Python
executed under the interpreter lock plus C that runs with the lock
released (the tail of a jitted call inside PJRT).  A line's wall time
less its declared waits less its CPU is everything else: waits for the
interpreter lock, for a Python lock or condition, blocking inside PJRT,
preemption (small while the host has more cores than runnable threads:
``nproc`` is in the log line).  So a CPU figure is exact for "what would
a faster implementation have to execute less of"; the wait figure is an
UPPER bound of the interpreter lock's share.

The same sum over the WINDOW (how many cores' worth the runtime's
threads ran: this metric x tasks a second / 1e6) is in the log line and
is no metric of its own.

None where the run was not traced or no span carries ``cpu_ns`` (a
program older than PR 35: the metric is left out of its line)."""

from __future__ import annotations

import os

from benchmark import harness, runtime_spans

PREFIX = runtime_spans.PREFIX
#: spans in which a thread declares that it waits (for work, for room,
#: for the chip, for the insert window, for its pools): their wall time
#: is asked of no lock
WAITS = ("mgr.starved", "mgr.inflight_wait", "mgr.warm_wait", "fin.idle",
         "worker.idle", "dtd.window_wait", "ctx.wait")
#: a thread line is known by the spans on it: the first kind that is
#: there names its role (a client that forces a chain carries a
#: ``mgr.dispatch`` too; ``gc.collect`` and ``ici.*`` land on any line)
ROLES = (("ctx", "client"), ("dtd", "client"), ("fin", "completer"),
         ("mgr", "manager"), ("worker", "worker"), ("warm", "warmer"))

#: what the log prints of a thread line, in this order
COLUMNS = ("wall_ns", "wait_ns", "cpu_ns")

_reduced = {}


def boundaries(evs: list) -> list:
    """(host time, thread CPU clock) at both ends of the line's
    ``parsec:`` spans that carry the two integers, sorted by time."""
    out = []
    for n, s, d, a in evs:
        if n.startswith(PREFIX) and "cpu_end_ns" in a and "cpu_ns" in a:
            end = int(a["cpu_end_ns"])
            out.append((s, end - int(a["cpu_ns"])))
            out.append((s + d, end))
    return sorted(out)


def role_of(evs: list) -> str:
    kinds = {n[len(PREFIX):].split(".", 1)[0] for n, _s, _d, _a in evs
             if n.startswith(PREFIX)}
    return next((role for kind, role in ROLES if kind in kinds), "other")


def reduce(data: dict) -> dict:
    """The CPU side of one traced window, from ``runtime_spans.load``'s
    plain data; None where no span carries ``cpu_ns``.

    ``lines``: per thread line with ``parsec:`` spans ``{"role",
    "wall_ns", "cpu_ns", "wait_ns", "back"}`` — between its first and its
    last clock reading inside the window the wall time covered, the CPU
    clock's advance, the wall time inside its declared waits (``WAITS``,
    a blocking ``fin.drain``), and how often the clock read LOWER than at
    the reading before (0 on a sound trace: one thread a line).
    ``spans`` / ``clocked``: the ``parsec:`` spans begun in the window,
    and how many of them carry the thread clock.  ``gc_ns``: wall time of
    the ``gc.collect`` spans, clipped to the window; ``gc_gens``: per
    generation ``[count, wall ns, the longest's ns]`` of those that touch
    it.  ``released``: the ``fin.release`` spans begun in it."""
    lo, hi = runtime_spans.window(data)
    lines, gc_ns, gc_gens = [], 0, {}
    spans = clocked = released = 0
    for evs in data["threads"]:
        mine = [(n[len(PREFIX):], s, s + d, a) for n, s, d, a in evs
                if n.startswith(PREFIX)]
        for kind, s, e, a in mine:
            if kind == "gc.collect" and min(e, hi) > max(s, lo):
                gc_ns += min(e, hi) - max(s, lo)
                g = gc_gens.setdefault(int(a.get("gen", -1)), [0, 0, 0])
                g[0] += 1
                g[1] += e - s
                g[2] = max(g[2], e - s)
            if lo <= s < hi:
                spans += 1
                clocked += "cpu_ns" in a
                released += kind == "fin.release"
        marks = [(t, c) for t, c in boundaries(evs) if lo <= t <= hi]
        if len(marks) < 2:
            continue
        (t0, c0), (t1, c1) = marks[0], marks[-1]
        waits = runtime_spans.union(
            (max(s, t0), min(e, t1)) for kind, s, e, a in mine
            if kind in WAITS
            or (kind == "fin.drain" and int(a.get("block", 0)) == 1))
        lines.append({
            "role": role_of(evs), "wall_ns": t1 - t0, "cpu_ns": c1 - c0,
            "wait_ns": runtime_spans.total(waits),
            "back": sum(1 for (_ta, ca), (_tb, cb)
                        in zip(marks, marks[1:]) if cb < ca)})
    if not clocked:
        return None
    return {"window": (lo, hi), "lines": lines, "released": released,
            "spans": spans, "clocked": clocked, "gc_ns": gc_ns,
            "gc_gens": dict(sorted(gc_gens.items())),
            "cpu_ns": sum(ln["cpu_ns"] for ln in lines)}


def of_run(run: dict):
    """``reduce`` of the run the harness has just traced, once a process;
    None without a trace, without the window's span or without ``cpu_ns``
    on the spans.  Says what it found on standard error, once: how many
    cores' worth the runtime's threads ran, and every thread line with
    its role, the wall time it covers, its declared waits and its CPU
    time (PERF.md section 5's running / waiting lines are written from
    it; ``runtime_spans.of_run`` has the spans by name)."""
    if run.get("trace") is None:
        return None
    key = id(run["trace"])
    if key in _reduced:
        return _reduced[key]
    dev = run.get("device")
    try:
        red = reduce(runtime_spans.load())
    except (OSError, ValueError) as exc:
        harness.log(f"benchmark: host cpu: nothing read: {exc!r} {dev}")
        red = None
    if red is not None:
        win_ns = red["window"][1] - red["window"][0]
        by_role = {}
        for ln in red["lines"]:
            r = by_role.setdefault(ln["role"], [0, 0, 0, 0])
            r[0] += 1
            for i, k in enumerate(COLUMNS):
                r[i + 1] += ln[k]

        def sec(ns):
            return round(ns / 1e9, 4)

        harness.log(
            f"benchmark: host cpu: thread CPU clock over {sec(win_ns)}s of "
            f"window, nproc {len(os.sched_getaffinity(0))}, read on "
            f"{red['clocked']} of the {red['spans']} spans begun in it: all "
            f"lines {sec(red['cpu_ns'])}s = {red['cpu_ns'] / win_ns:.4f} "
            f"threads; fin.release begun in the window {red['released']}, "
            "the window's tasks by the DAG's formula "
            f"{run['tasks_per_job'] * len(run['jobs'])}; gc.collect "
            f"{sec(red['gc_ns'])}s, by generation [count, s, the longest s] "
            f"{ {g: [c, sec(w), sec(m)] for g, (c, w, m) in red['gc_gens'].items()} }; "
            "by role [lines, wall s covered, declared waits s, cpu s] "
            f"{ {k: [v[0]] + [sec(x) for x in v[1:]] for k, v in sorted(by_role.items())} }; "
            "by line [role, wall s, waits s, cpu s, clock went back] "
            f"{[[ln['role']] + [sec(ln[k]) for k in COLUMNS] + [ln['back']] for ln in red['lines']]} "
            f"{dev}")
    _reduced[key] = red
    return red


def read(run):
    red = of_run(run)
    if red is None or not red["lines"] or not run["jobs"]:
        return None
    return red["cpu_ns"] / 1e3 / (run["tasks_per_job"] * len(run["jobs"]))
