"""Layer: PTG, dep engine, scheduler.  Share of the traced window in
which the chip was idle because no ready task had reached a manager:
every manager thread of the device outside ``mgr.launch`` (in
``mgr.starved``) while the benchmark sat in ``insert`` or ``wait``.
The ``starved`` bucket of ``benchmark/runtime_spans.py``."""

from benchmark import runtime_spans


def read(run):
    return runtime_spans.idle_pct(run, "starved")
