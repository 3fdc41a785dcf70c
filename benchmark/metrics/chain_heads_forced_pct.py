"""Layer: device module.  Held chain heads that went out ALONE
(``DeviceStats.heads_forced``: no launch of their declared successor
carried them) over the heads held (``held_tasks``), summed over the
chips, in percent: 0 where every held GETRF went out inside its row
panel's wave.  The counters are the process's (every job of a
run is the same DAG).  None where no head was held, or on a program
without the counter."""


def read(run):
    stats = [d["stats"] for d in run["devices"]]
    if not stats or any("heads_forced" not in s for s in stats):
        return None
    held = sum(s.get("held_tasks", 0) for s in stats)
    return 100.0 * sum(s["heads_forced"] for s in stats) / held \
        if held else None
