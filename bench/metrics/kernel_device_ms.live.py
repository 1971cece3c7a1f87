"""Fleet step: device time of the fleet kernel's operations
(``hdc_fleet_counts``) in the traced window, summed over tiles and devices,
per push (ms)."""

from bench import fleet_spans


def read(run):
    ns = fleet_spans.kernel_ns(run)
    if not ns or not run.pushes:
        return None
    return ns / len(run.pushes) / 1e6
