"""Layer: device module.  Share of the traced window in which the chip
was idle while a manager thread was inside ``mgr.launch`` (popping the
wave, staging, the jitted call): the launch that ended the gap, from
its begin to the return of its ``mgr.dispatch``, or another launch
before it.  The ``launch`` bucket of ``benchmark/runtime_spans.py``."""

from benchmark import runtime_spans


def read(run):
    return runtime_spans.idle_pct(run, "launch")
