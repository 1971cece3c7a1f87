"""Open-loop schedule: mean over the window's ticks of how late the push
started after its due time (ms).  Grows without bound above the knee."""


def read(run):
    if not run.pushes:
        return None
    return sum(p.start - p.due for p in run.pushes) / len(run.pushes) * 1e3
