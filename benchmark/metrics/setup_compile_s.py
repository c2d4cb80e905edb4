"""phases.compileSeconds of GET /api/v1/metrics when the window opens:
the compiles set-up paid (cache loads when the persistent cache hits)."""


def read(run):
    return run["metrics_open"]["compileSeconds"]
