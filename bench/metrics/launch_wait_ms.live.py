"""Device: mean per push of the time from each ``fleet.dispatch`` span's end
to the first operation of the step execution it issued, the device idle
with work queued, summed over the push's tiles and rounds (ms)."""

from bench import fleet_spans


def read(run):
    return fleet_spans.mean_ms(fleet_spans.launch_wait_ns(run))
