"""Layer: device module.  ``completer_us_per_task`` on the thread's CPU
clock: per completer line (the thread that carries ``parsec:fin.*``) the
thread clock from its first to its last span boundary in the window,
summed over the completers, over the ``fin.release`` spans begun in the
window, in microseconds.  Its declared waits (``fin.idle``, a blocking
``fin.drain``) burn no CPU, so nothing is subtracted
(``host_cpu_us_per_task`` says what the two integers are).

None where the run was not traced, the spans carry no ``cpu_ns`` or
nothing was released in the window."""

from benchmark.metrics import host_cpu_us_per_task


def read(run):
    red = host_cpu_us_per_task.of_run(run)
    if red is None or not red["released"]:
        return None
    return sum(ln["cpu_ns"] for ln in red["lines"]
               if ln["role"] == "completer") / red["released"] / 1e3
