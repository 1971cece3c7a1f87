"""Fleet step: the least bytes the window's pushes must move
(``workbytes.step_min_bytes``) at peak HBM bandwidth, over the step's
device time summed over devices (%)."""

from bench import trace, workbytes


def read(run):
    ns = trace.step_ns(run)
    if not ns:
        return None
    need = sum(workbytes.step_min_bytes(
        run.config, sessions=run.sessions, cycles=p.cycles,
        frames_out=p.decisions, patients=run.config["patients"],
        devices=len(run.devices)) for p in run.pushes)
    return need / run.peaks["hbm_bytes_per_s"] / (ns / 1e9) * 100
