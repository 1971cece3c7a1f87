"""Host ingest and dispatch: mean seconds per push inside
``StreamingFleet.push_codes_raw`` (staging rings, device puts, step
dispatch), from the harness's span around the call (ms)."""


def read(run):
    if not run.pushes:
        return None
    return sum(p.pushed - p.start for p in run.pushes) / len(run.pushes) * 1e3
