"""Layer: compile cache.  Backend compiles (cache reads included) that
JAX's monitoring reported between the window's opening and its close.
Should read 0: whatever compiles there was not reached by the warm jobs.
"""


def read(run):
    return float(run["compiles_in_window"])
