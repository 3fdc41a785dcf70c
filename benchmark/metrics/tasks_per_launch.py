"""Layer: device module.  Tasks of the traced jobs (the DAG's formula)
over the executions of the runtime's programs in the trace (events of
the ``XLA Modules`` lines; the benchmark's own ``jit_bench_*`` programs
are not counted).  Read from the trace because ``DeviceStats`` leaves
held chain heads out of ``executed_tasks``."""

from benchmark import trace


def read(run):
    if run["trace"] is None or not run["jobs"]:
        return None
    n = trace.launches(run["trace"])
    if n == 0:
        return None
    return run["tasks_per_job"] * len(run["jobs"]) / n
