"""Layer: device module.  What a task costs the thread that takes it
back from the managers (``XlaDevice._completer_loop``): per completer
line (the thread that carries ``parsec:fin.*``), the traced window less
its ``fin.idle`` time (nothing was in flight) less its ``fin.drain``
spans with ``block`` = 1 (there it waits for the chip: the valve, not
its cost), summed over the completers, over the ``fin.release`` spans
begun in the window, in microseconds.  ``fin.release``
(``release_us_per_task``) is inside it; the rest is the hand-over: the
device's lock, the readiness probes, the finalization, and the waits for
the interpreter lock that any host span includes.

A span that is open when the profiler's session starts is not in the
trace, and the completer's idle episode before the first job is such a
one: a line's window begins with its first span, and ends with its last,
where those lie inside the benchmark's.  None where the run was not
traced or the program emits no ``fin.release``."""

from benchmark import runtime_spans

FIN = runtime_spans.PREFIX + "fin."


def read(run):
    if run.get("trace") is None:
        return None
    try:
        data = runtime_spans.load()
        lo, hi = runtime_spans.window(data)
    except (OSError, ValueError):
        return None
    busy_ns, released = 0, 0
    for evs in data["threads"]:
        fin = [(n[len(FIN):], s, s + d, a) for n, s, d, a in evs
               if n.startswith(FIN)]
        if not fin:
            continue
        a0 = max(lo, min(s for _k, s, _e, _a in fin))
        a1 = min(hi, max(e for _k, _s, e, _a in fin))
        waits = runtime_spans.union(
            (max(s, a0), min(e, a1)) for kind, s, e, a in fin
            if kind == "idle"
            or (kind == "drain" and int(a.get("block", 0)) == 1))
        busy_ns += max(a1 - a0, 0) - runtime_spans.total(waits)
        released += sum(1 for kind, s, _e, _a in fin
                        if kind == "release" and lo <= s < hi)
    return busy_ns / released / 1e3 if released else None
