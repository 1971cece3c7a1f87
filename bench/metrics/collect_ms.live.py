"""Decision collection: mean per push of ``collect_decisions`` after the
rounds were waited for with ``block_until_ready`` (traced run only), so the
span holds the device-to-host copies and the FrameDecision objects (ms)."""


def read(run):
    done = [p.collected - p.ready for p in run.pushes if p.ready is not None]
    if not done:
        return None
    return sum(done) / len(done) * 1e3
