"""tasks_per_s: tasks of the dispatches that completed inside the
window, over the time from the window's start to the last of those
completions (host clock).  All the work and all the time: no
per-dispatch medians."""


def compute(records, trace):
    done = [d for d in records["dispatches"] if d["end"] <= records["close"]]
    if not done:
        return None
    return (len(done) * records["batch"]
            / (max(d["end"] for d in done) - records["t0"]))
