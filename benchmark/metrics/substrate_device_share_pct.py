"""Layer: data substrate.  What the runtime's data handling costs the
chip beside the sweeps: the device time, inside the window, of the
runtime's programs that are NOT of class ``S`` (snapshot copies, reshape
slices, zero fills, the INIT wave that cuts the first halos) over the
device time of all the runtime's programs; the benchmark's own
``jit_bench_*`` programs are left out of both.  Returns nothing without
a trace, or where no ``S`` program ran (a run of another app)."""

from benchmark.metrics.sweep_hbm_roofline_pct import sweep_seconds


def read(run):
    if run["trace"] is None:
        return None
    sweep_s, all_s = sweep_seconds(run["trace"])
    if sweep_s <= 0:
        return None
    return 100.0 * (all_s - sweep_s) / all_s
