"""End to end, host clock: useful flop of ALL jobs completed in the
window (the benchmark's own count, work.py) over ALL the wall time of
the window, over the cell's chips.  Re-staging between jobs is inside
the window; the job in flight at the deadline runs to its end and counts
with its time.  No median of jobs, no subtraction."""


def read(run):
    if not run["jobs"]:
        return None
    return (run["flop_per_job"] * len(run["jobs"])
            / run["window_s"] / run["chips"] / 1e12)
