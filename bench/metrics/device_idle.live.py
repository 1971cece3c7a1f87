"""Device idle share while decisions are owed: 1 - busy / the union of the
pushes' critical intervals (due time to decisions on the host), mean over
the devices used (%).  The wait for the next tick does not count."""

from bench import trace


def read(run):
    if run.trace is None or not any(
            run.trace.devices[d].ops for d in run.devices) or not run.pushes:
        return None
    owed = trace.merge((run.to_ns(p.due), run.to_ns(p.collected))
                       for p in run.pushes)
    total = sum(e - s for s, e in owed)
    shares = []
    for d in run.devices:
        merged = trace.busy(run.trace.devices[d])
        shares.append(sum(trace.covered(merged, s, e) for s, e in owed) / total)
    return (1 - sum(shares) / len(shares)) * 100
