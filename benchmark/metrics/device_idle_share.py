"""1 - (union of device-operation intervals) / (traced window), from the
profiler trace of the traced round."""


def read(run):
    t = run["trace"]
    return None if t is None else t["idle_share"]
