"""Layer: kernels.  The LU panel kernels' share of their roof, which is
the chip's bf16 peak: the useful flop of the window's panel tasks (per
job nt GETRF at 2 mb^3 / 3 and nt(nt-1)/2 TRSM of each family at mb^3,
``reference/getrf.py panel_flops``) over (the ``device_kind``'s bf16
peak x the device seconds, inside the window, of the programs whose
name carries GETRF, TRSMR or TRSMC).  The count is LAPACK's, whatever
the kernels execute (GETRF's inverses and the TRSMs' blocks above the
triangle are not useful flop), so it cannot pass 100 unless the count
or the time is wrong.  Returns nothing without a trace, for a run of
another app, or where no panel program ran."""

from benchmark import work
from benchmark.metrics.lu_panel_device_share_pct import panel_seconds
from benchmark.reference import getrf as reference


def read(run):
    t = run["traffic"]
    if run["trace"] is None or not run["jobs"] \
            or run["config"].get("app") != "getrf":
        return None
    panel_s, _all = panel_seconds(run["trace"])
    if panel_s <= 0:
        return None
    useful = reference.panel_flops(int(t["n"]), int(t["mb"])) \
        * len(run["jobs"])
    peak = work.peak(run["device"]["kind"])["bf16_flop_per_s"]
    return 100.0 * useful / (peak * panel_s)
