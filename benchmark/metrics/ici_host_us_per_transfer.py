"""Layer: ICI transport.  What one transfer costs the thread that makes
it (mostly a completer, inside ``fin.release``): the total of the
``parsec:ici.put`` / ``ici.bcast`` / ``ici.permute`` spans that began in
the traced window over their count, in microseconds.  None where the
run was not traced or the program emits no such span."""

from benchmark import runtime_spans

SPANS = tuple(runtime_spans.PREFIX + "ici." + k
              for k in ("put", "bcast", "permute"))


def read(run):
    if run.get("trace") is None:
        return None
    try:
        data = runtime_spans.load()
        lo, hi = runtime_spans.window(data)
    except (OSError, ValueError):
        return None
    durs = [d for evs in data["threads"] for n, s, d, _a in evs
            if n in SPANS and lo <= s < hi]
    return sum(durs) / len(durs) / 1e3 if durs else None
