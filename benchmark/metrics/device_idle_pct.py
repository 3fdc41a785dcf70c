"""Layer: device.  From the profiler's trace of the window: 1 - (union
of the intervals in which a program ran on a chip) / traced span, the
mean over the cell's chips (every chip's busy seconds are printed on an
earlier line)."""


def read(run):
    traced = run.get("traced")
    if not traced or traced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - traced["busy_s"] / traced["window_s"])
