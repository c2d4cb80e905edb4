"""fullEncodes / (fullEncodes + deltaEncodes + cachedEncodes) over the
window, from GET /api/v1/metrics."""

from readers import delta


def read(run):
    full = delta(run, "fullEncodes")
    total = full + delta(run, "deltaEncodes") + delta(run, "cachedEncodes")
    return full / total if total else None
