"""Layer: entry point.  (slowest - fastest job) / mean job, jobs timed
insert-to-fence on the host clock.  A steadier companion to the rate; a
tail needs a mix with hundreds of jobs a window."""


def read(run):
    t = [e - s for s, e in run["jobs"]]
    if len(t) < 2:
        return None
    return 100.0 * (max(t) - min(t)) / (sum(t) / len(t))
