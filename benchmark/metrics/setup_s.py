"""Process start to window open: JAX start-up, cluster generation, the
scheduler profile, import, one warm-up cycle of the mix's smallest count
and the background compiles it arms, compilation or cache loads
included."""


def read(run):
    return run["setup_s"]
