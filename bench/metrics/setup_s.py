"""Set-up seconds: from process start to the first timed push (imports,
bank, traffic, fleet, warm-up compiles or cache loads, warm-up pushes)."""


def read(run):
    return run.setup_s
