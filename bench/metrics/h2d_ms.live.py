"""Host ingest and dispatch: mean per push of the program's ``fleet.h2d``
spans (the device puts of codes and lengths), summed over the push's tiles
and rounds (ms)."""

from bench import fleet_spans


def read(run):
    return fleet_spans.mean_ms(
        fleet_spans.per_push(run, "fleet.h2d", "bench.ingest"))
