"""Reader of what the run itself counted or clocked: the entry of the
runner's ``observed`` table that the metric's own file names under
``observed``. ``None`` where the run has no such number, so the harness
leaves the metric out."""


def read(run, spec):
    return run["observed"].get(spec["observed"])
