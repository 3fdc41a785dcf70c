"""Layer: device module.  Manager time a launch: the mean, over the
``mgr.launch`` spans inside the window, of the span less the
``mgr.inflight_wait`` inside it (held chain heads count: they are
staged too)."""

from benchmark import runtime_spans


def read(run):
    red = runtime_spans.of_run(run)
    return None if red is None else red["launch_host_ms"]
