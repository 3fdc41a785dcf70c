"""Layer: kernels.  What the panel costs the chip: the device time of the
runtime's programs whose name carries GEQRT or TSQRT (a chain program
counts whole: ``jit_parsec_chain_GEQRT__TSQRT_x1`` is two panel kernels)
over the device time of all the runtime's programs inside the window.
The panel is built in float32 at HIGHEST precision (six bf16 passes a
product) on the critical path of every column; the rest of the job is
matmul-class updates.  Returns nothing without a trace, or where no
program of the runtime ran."""

from benchmark import trace

PANEL = ("GEQRT", "TSQRT")


def read(run):
    if run["trace"] is None:
        return None
    ours = [(name, s) for name, s, _n in
            trace.programs(run["trace"], top=10 ** 9)
            if not trace.is_own(name)]
    total = sum(s for _name, s in ours)
    if total <= 0:
        return None
    return 100.0 * sum(s for name, s in ours
                       if any(p in name for p in PANEL)) / total
