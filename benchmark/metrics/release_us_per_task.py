"""Layer: PTG, dep engine, scheduler.  The single completer's cost a
task: total ``fin.release`` time (``complete_execution``: dep release
and the scheduling of successors) inside the window over the tasks
released in it."""

from benchmark import runtime_spans


def read(run):
    red = runtime_spans.of_run(run)
    return None if red is None else red["release_us_per_task"]
