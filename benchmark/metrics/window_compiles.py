"""engineBuilds + compileMisses + speculativeCompiles counted across the
window. Set-up warms every program the window uses and waits out the
background compiles its pass arms, so this should read 0."""

from readers import delta


def read(run):
    return delta(run, "engineBuilds") + delta(run, "compileMisses") + delta(run, "speculativeCompiles")
