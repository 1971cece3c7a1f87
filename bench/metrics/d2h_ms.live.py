"""Decision collection: mean per push of the program's ``fleet.d2h`` spans
(device-to-host copies of frame HVs and scores), summed over tiles (ms)."""

from bench import fleet_spans


def read(run):
    return fleet_spans.mean_ms(
        fleet_spans.per_push(run, "fleet.d2h", "bench.collect"))
