"""Layer: PTG / dep engine / scheduler.  Tasks per job by the DAG's own
formula (work.py) x jobs completed / window seconds."""


def read(run):
    if not run["jobs"]:
        return None
    return run["tasks_per_job"] * len(run["jobs"]) / run["window_s"]
