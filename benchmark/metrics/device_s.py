"""Sum of device-operation durations in the profiler trace of the traced
round, per pass of that round."""


def read(run):
    t = run["trace"]
    return None if t is None else t["device_s"]
