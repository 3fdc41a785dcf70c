"""Layer: compile cache.  Chain programs the run built
(``DeviceStats.chain_programs``, summed over the chips): every one holds
a panel kernel, compiled once more, so the count is what a cold set-up
pays for chain fusion.  Bounded by the taskpool's classes and the fused
widths (PR 31): two for the QR cell whatever its nt.  None where the
program does not count them (the parent of PR 31)."""


def read(run):
    counts = [d["stats"].get("chain_programs") for d in run["devices"]]
    counts = [c for c in counts if c is not None]
    return sum(counts) if counts else None
