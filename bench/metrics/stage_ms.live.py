"""Host ingest and dispatch: mean per push of the program's ``fleet.stage``
spans (staging-ring slice writes, and waits for a slot's previous step),
summed over the push's tiles and rounds (ms)."""

from bench import fleet_spans


def read(run):
    return fleet_spans.mean_ms(
        fleet_spans.per_push(run, "fleet.stage", "bench.ingest"))
