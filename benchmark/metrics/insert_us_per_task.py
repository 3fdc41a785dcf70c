"""Layer: DTD front end.  What an insert costs the inserting thread
(``DTDTaskpool.insert_task``: the task object, the dep tracking of each
tile under the pool's lock, the scheduling of what became ready): the
``dtd.insert`` spans (one a run of a pool's inserter, ``n`` = tasks it
inserted) less the ``dtd.window_wait`` spans inside them (there the
thread is blocked on the window, not working), inside the benchmark's
window, over the tasks those inserters inserted, in microseconds.  An
upper bound: a span's wall time includes the thread's waits for the
interpreter lock.

None where the run was not traced or the program emits no ``dtd.insert``
(a program without the span, a cell whose app inserts nothing)."""

from benchmark import runtime_spans

DTD = runtime_spans.PREFIX + "dtd."


def spans(run):
    """(window, [(kind, start, end, args)]) of the run's ``dtd.*``
    spans that lie inside the benchmark's window; None without a trace."""
    if run.get("trace") is None:
        return None
    try:
        data = runtime_spans.load()
        lo, hi = runtime_spans.window(data)
    except (OSError, ValueError):
        return None
    return (lo, hi), [(n[len(DTD):], s, s + d, a)
                      for evs in data["threads"] for n, s, d, a in evs
                      if n.startswith(DTD) and lo <= s and s + d <= hi]


def read(run):
    got = spans(run)
    if got is None:
        return None
    _win, evs = got
    inserted = sum(int(a.get("n", 0)) for k, _s, _e, a in evs
                   if k == "insert")
    if not inserted:
        return None
    busy = sum((e - s) * (1 if k == "insert" else -1) for k, s, e, _a in evs
               if k in ("insert", "window_wait"))
    return busy / inserted / 1e3
