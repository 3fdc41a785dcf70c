"""End to end, host clock: process start to the window's opening —
imports, native-extension load, Context, tile generation, the warm jobs,
the background fused-width compiles, cache reads or compiles."""


def read(run):
    return run["setup_s"]
