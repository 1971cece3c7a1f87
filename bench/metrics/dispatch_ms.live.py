"""Host ingest and dispatch: mean per push of the program's
``fleet.dispatch`` spans (the call of the step's executable), summed over
the push's tiles and rounds (ms)."""

from bench import fleet_spans


def read(run):
    return fleet_spans.mean_ms(
        fleet_spans.per_push(run, "fleet.dispatch", "bench.ingest"))
