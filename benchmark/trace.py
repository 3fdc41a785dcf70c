"""From the profiler's trace to numbers: device busy time, program
executions, time by name, and idle gaps joined to the benchmark's own
host spans.  Part of the yardstick: every PR reduces a trace this way.

A trace is reduced in two steps.  ``load`` turns the profiler's
``.xplane.pb`` into plain data, ``{"devices": {plane: {"modules": [...],
"ops": [...]}}, "host": [...]}`` with every event a ``[name, start_ns,
duration_ns]`` triple on the profiler's one clock; the functions below
work on that alone (tests/ holds such a trace recorded on the chip).

What the v5e's trace looks like (looked at by hand, PR 24): one plane a
chip, ``/device:TPU:<i>``; its line ``XLA Modules`` has one event for
every execution of a compiled program, named ``jit_<function>(<id>)``;
its line ``XLA Ops`` has the operations inside them.  The benchmark's
``TraceAnnotation`` spans are events named ``bench:<span>`` on the
host's thread lines.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
SPAN_PREFIX = "bench:"
#: programs of the benchmark itself (staging, the comparison): in the
#: trace, and not the runtime's
OWN_PROGRAM = re.compile(r"^jit_bench_")


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain data."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            out["devices"][plane.name] = {
                key: [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in lines[name].events] if name in lines else []
                for key, name in (("modules", MODULE_LINE),
                                  ("ops", OP_LINE))}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                out["host"] += [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in ln.events
                                if e.name.startswith(SPAN_PREFIX)]
    return out


def is_own(event_name: str) -> bool:
    return bool(OWN_PROGRAM.match(event_name))


def union_ns(intervals) -> int:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def window(trace: dict) -> tuple:
    """(start_ns, end_ns) of the traced window: the ``bench:window`` span."""
    for name, s, d in trace["host"]:
        if name == SPAN_PREFIX + "window":
            return s, s + d
    raise ValueError("the trace holds no bench:window span")


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def busy(trace: dict, runtime_only: bool = False) -> dict:
    """Per device plane, the seconds inside the window in which a program
    ran (union of the ``XLA Modules`` intervals).  ``runtime_only`` leaves
    the benchmark's own programs out."""
    lo, hi = window(trace)
    out = {}
    for plane, dev in trace["devices"].items():
        out[plane] = union_ns(
            (a, b) for name, a, b in _clip(dev["modules"], lo, hi)
            if not (runtime_only and is_own(name))) / 1e9
    return out


def launches(trace: dict) -> int:
    """Executions of the runtime's programs inside the window, all
    devices together; the benchmark's own programs are not counted."""
    lo, hi = window(trace)
    return sum(1 for dev in trace["devices"].values()
               for name, _a, _b in _clip(dev["modules"], lo, hi)
               if not is_own(name))


def programs(trace: dict, top: int = 10) -> list:
    """[[program, seconds, executions], ...] inside the window, summed
    over devices, largest first; a program is named as the trace names
    it, ``jit_<function>(<id>)``."""
    lo, hi = window(trace)
    acc = {}
    for dev in trace["devices"].values():
        for name, a, b in _clip(dev["modules"], lo, hi):
            t, n = acc.get(name, (0, 0))
            acc[name] = (t + b - a, n + 1)
    return [[k, t / 1e9, n] for k, (t, n) in
            sorted(acc.items(), key=lambda kv: -kv[1][0])[:top]]


def op_label(program: str, op: str) -> str:
    """``jit_fn(12)`` and ``%fusion.2 = bf16[...] fusion(...)`` ->
    ``jit_fn(12)/%fusion.2``: an operation under the program it ran in
    (the trace prints the whole HLO line; its head is the name)."""
    return f"{program}/{op.split(' = ', 1)[0]}"[:120]


def device_ops(trace: dict, top: int = 10) -> list:
    """[[program/op, seconds], ...]: the operations that took most device
    time inside the window, each under the program whose execution
    contains it, summed over executions and devices."""
    lo, hi = window(trace)
    acc = {}
    for dev in trace["devices"].values():
        mods = sorted((s, s + d, name) for name, s, d in dev["modules"])
        i = 0
        for name, s, d in sorted(dev["ops"], key=lambda e: e[1]):
            while i < len(mods) and mods[i][1] <= s:
                i += 1
            inside = i < len(mods) and mods[i][0] <= s
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                key = op_label(mods[i][2] if inside else "?", name)
                acc[key] = acc.get(key, 0) + (b - a)
    return [[k, v / 1e9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def idle_gaps(trace: dict, top: int = 10) -> list:
    """[[span, seconds], ...]: the time inside the window in which no
    program ran on the busiest-gapped device, by the benchmark's host
    span that was open when the gap began (``none`` between spans),
    largest first.  Gaps of every device are summed per span and divided
    by the number of devices."""
    lo, hi = window(trace)
    spans = sorted((s, s + d, name[len(SPAN_PREFIX):])
                   for name, s, d in trace["host"]
                   if name != SPAN_PREFIX + "window")

    def span_at(t):
        # innermost = the latest-starting span that covers t
        best = "none"
        for s, e, name in spans:
            if s > t:
                break
            if t < e:
                best = name
        return best

    acc = {}
    ndev = max(len(trace["devices"]), 1)
    for dev in trace["devices"].values():
        cur = lo
        merged = sorted((a, b) for _n, a, b in _clip(dev["modules"], lo, hi))
        for a, b in merged + [(hi, hi)]:
            if a > cur:
                key = span_at(cur)
                acc[key] = acc.get(key, 0) + (a - cur)
            cur = max(cur, b)
    return [[k, v / 1e9 / ndev] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]
