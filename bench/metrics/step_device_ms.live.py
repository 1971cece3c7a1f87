"""Fleet step: device time of the step's program executions in the traced
window, summed over tiles and devices, per push (ms)."""

from bench import trace


def read(run):
    ns = trace.step_ns(run)
    if not ns or not run.pushes:
        return None
    return ns / len(run.pushes) / 1e6
