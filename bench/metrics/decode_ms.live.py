"""Decision collection: mean per push of the program's ``fleet.decode``
spans (argmax and the FrameDecision objects), summed over tiles (ms)."""

from bench import fleet_spans


def read(run):
    return fleet_spans.mean_ms(
        fleet_spans.per_push(run, "fleet.decode", "bench.collect"))
