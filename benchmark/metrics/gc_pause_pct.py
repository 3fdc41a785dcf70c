"""Layer: PTG, dep engine, scheduler.  Share of the traced window the
interpreter's collector held every Python thread still: the WALL time of
the ``gc.collect`` spans (one a collection, on whichever thread tripped
it; ``gen`` says which generation) inside the window over the window, in
percent.  Its wall time is the number, not its CPU time: while it runs,
every other thread waits for the interpreter lock.

None where the run was not traced or the spans carry no ``cpu_ns`` (a
program older than PR 35 emits no ``gc.collect`` either); 0.0 where they
do and no collection fell in the window."""

from benchmark.metrics import host_cpu_us_per_task


def read(run):
    red = host_cpu_us_per_task.of_run(run)
    if red is None:
        return None
    lo, hi = red["window"]
    return 100.0 * red["gc_ns"] / (hi - lo) if hi > lo else None
