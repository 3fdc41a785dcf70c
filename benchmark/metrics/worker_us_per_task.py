"""Layer: PTG, dep engine, scheduler.  Worker-thread time a task: the
time the workers spent outside ``worker.idle`` inside the window (a
worker counts as busy between two of its recorded idle episodes), summed
over the workers, over the window's tasks (the DAG's formula x jobs)."""

from benchmark import runtime_spans


def read(run):
    red = runtime_spans.of_run(run)
    if red is None or red["worker_busy_s"] is None or not run["jobs"]:
        return None
    return 1e6 * red["worker_busy_s"] / (run["tasks_per_job"]
                                         * len(run["jobs"]))
