"""Layer: kernels.  Useful flop of the traced jobs over (bf16 peak of the
``device_kind`` x the seconds in which the runtime's programs ran, summed
over the chips).

Why the compute roof is the roofline here: a tile kernel does 2 mb^3
flop on 3 mb^2 tiles of 2 bytes, mb/3 flop a byte — 683 at mb=2048 and
2048 at mb=6144, against a ridge of 197e12 / 819e9 = 240 flop a byte, so
every tile kernel of these cells is compute-bound.  The numerator is the
LAPACK count whatever implements a kernel (TRSM by inverse and a full
SYRK do more than the useful flop), so this cannot pass 100 unless the
count or the time is wrong.  Returns nothing where no program ran."""

from benchmark import trace, work


def read(run):
    if run["trace"] is None or not run["jobs"]:
        return None
    busy_s = sum(trace.busy(run["trace"], runtime_only=True).values())
    if busy_s <= 0:
        return None
    useful = run["flop_per_job"] * len(run["jobs"])
    peak = work.peak(run["device"]["kind"])["bf16_flop_per_s"]
    return 100.0 * useful / (peak * busy_s)
