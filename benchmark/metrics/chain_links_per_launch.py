"""Layer: device module.  Chain heads held (``DeviceStats.held_tasks``:
tasks that never had a dispatch of their own) over the launches that
carried one into its successor (``chained_launches``), summed over the
chips: 1.0 where every held head went out with its successor, as the
Cholesky cells' POTRF does and as the QR column does two links at a
time; over 1 where heads were forced alone.  The counters are the
process's (every job of a run is the same DAG).  None where no chained
launch was made, or the program has no such counters."""


def read(run):
    held = sum(d["stats"].get("held_tasks", 0) for d in run["devices"])
    launches = sum(d["stats"].get("chained_launches", 0)
                   for d in run["devices"])
    return held / launches if launches else None
