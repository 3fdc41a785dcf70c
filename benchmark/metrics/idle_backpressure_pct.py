"""Layer: device module.  Share of the traced window in which the chip
was idle while a manager thread sat in ``mgr.inflight_wait`` (no room
under ``device_inflight_depth``: the completer had not taken the
earlier launches' tasks).  The ``backpressure`` bucket of
``benchmark/runtime_spans.py``."""

from benchmark import runtime_spans


def read(run):
    return runtime_spans.idle_pct(run, "backpressure")
