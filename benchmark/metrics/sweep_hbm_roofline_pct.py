"""Layer: kernels.  The sweep kernel's share of its roofline, which is
HBM's: the bytes the traced jobs' sweeps HAVE to move (every point read
once and written once a sweep: ``reference/stencil.py bytes_moved``,
the work and not what an implementation happens to move) over (the
published ``hbm_bytes_per_s`` of the ``device_kind`` x the device
seconds, inside the window, of the runtime's programs of class ``S``:
``jit_parsec_S``, ``jit_parsec_S_x<w>``).

Why HBM is the roof here: a point update is 3 flop on 8 bytes, 0.375
flop a byte against a ridge of 197e12 / 819e9 = 240.  Every sweep's grid
is materialised in HBM (the configuration's guarantee), so no kernel can
move less and the share cannot pass 100 unless the count or the time is
wrong.  Returns nothing without a trace, for a run of another app, or
where no ``S`` program ran."""

import re

from benchmark import trace, work
from benchmark.reference import stencil as reference

S_PROGRAM = re.compile(r"^jit_parsec_S(_x\d+)?\(")


def sweep_seconds(tr) -> tuple:
    """(device seconds of the ``S`` programs, of all the runtime's
    programs) inside the window."""
    ours = [(name, s) for name, s, _n in trace.programs(tr, top=10 ** 9)
            if not trace.is_own(name)]
    return (sum(s for name, s in ours if S_PROGRAM.match(name)),
            sum(s for _name, s in ours))


def read(run):
    t = run["traffic"]
    if run["trace"] is None or not run["jobs"] \
            or not all(k in t for k in ("n", "nb", "steps")):
        return None
    sweep_s, _all = sweep_seconds(run["trace"])
    if sweep_s <= 0:
        return None
    moved = reference.bytes_moved(int(t["n"]), int(t["nb"]),
                                  int(t["steps"])) * len(run["jobs"])
    peak = work.peak(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * moved / (peak * sweep_s)
