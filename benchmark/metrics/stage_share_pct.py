"""Layer: entry point.  Host-clock spans the benchmark puts round its own
re-staging, as a share of the window: how much of the window is not the
runtime's.  Staging is asynchronous, so this is the host's part; the
device's part shows as ``jit_bench_stage_tile`` in the breakdown."""


def read(run):
    if not run["jobs"]:
        return None
    staged = run["spans"].total("stage", run["t_open"], run["t_close"])
    return 100.0 * staged / run["window_s"]
