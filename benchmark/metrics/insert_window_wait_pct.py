"""Layer: DTD front end.  Share of the benchmark's window the inserting
thread spent blocked on the insert window (``dtd.window_wait``: the
window was full, ``dtd_window_size`` tasks in flight, and the thread
waited until fewer than ``dtd_threshold_size`` were), in percent.  It
says who paces whom: near 0 the inserter is the slower side and the
runtime waits for tasks; high, the inserter runs ahead and the window
holds it back.  No better side is claimed (``BENCHMARK.json`` has to
name one: lower, the window costing the client's thread nothing).

None where the run was not traced or the program emits no ``dtd.insert``
span; 0.0 where it inserted and never waited."""

from benchmark.metrics import insert_us_per_task


def read(run):
    got = insert_us_per_task.spans(run)
    if got is None:
        return None
    (lo, hi), evs = got
    if not any(k == "insert" for k, _s, _e, _a in evs) or hi <= lo:
        return None
    waited = sum(e - s for k, s, e, _a in evs if k == "window_wait")
    return 100.0 * waited / (hi - lo)
