"""Layer: kernels.  What the LU panel costs the chip: the device time of
the runtime's programs whose name carries GETRF, TRSMR or TRSMC (a chain
program counts whole: ``jit_parsec_chain_GETRF__TRSMR_x8`` is the
diagonal tile's factor and eight row-panel solves; GETRFL is the last
diagonal tile) over the device time of all the runtime's programs
inside the window.  The rest of the job is the trailing GEMM waves.
Returns nothing without a trace, or where no program of the runtime
ran."""

from benchmark import trace

PANEL = ("GETRF", "TRSMR", "TRSMC")


def panel_seconds(tr) -> tuple:
    """(device seconds of the panel programs, of all the runtime's
    programs) inside the window."""
    ours = [(name, s) for name, s, _n in trace.programs(tr, top=10 ** 9)
            if not trace.is_own(name)]
    return (sum(s for name, s in ours if any(p in name for p in PANEL)),
            sum(s for _name, s in ours))


def read(run):
    if run["trace"] is None:
        return None
    panel, total = panel_seconds(run["trace"])
    return 100.0 * panel / total if total > 0 else None
