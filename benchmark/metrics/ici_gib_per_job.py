"""Layer: ICI transport.  What crossed between the chips a job: the
window's ``IciStats`` bytes (put + bcast + permute; a broadcast counts
one payload a destination) / 2^30 / jobs completed.  None where the
program drives one chip (no ICI engine, no counters) or no job ended."""

BYTES = ("put_bytes", "bcast_bytes", "permute_bytes")


def read(run):
    ici = run.get("ici") or {}
    if not run["jobs"] or not any(k in ici for k in BYTES):
        return None
    return sum(ici.get(k, 0) for k in BYTES) / 2 ** 30 / len(run["jobs"])
