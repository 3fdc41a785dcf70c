"""Layer: device.  Share of the traced window in which the chip was
idle after the jitted call of the next program had returned
(``mgr.dispatch`` closed) and before the program started: PJRT's queue
and transfers.  The ``device_queue`` bucket of
``benchmark/runtime_spans.py``; it carries the uncertainty of the
device-to-host clock offset (PERF.md section 3)."""

from benchmark import runtime_spans


def read(run):
    return runtime_spans.idle_pct(run, "device_queue")
