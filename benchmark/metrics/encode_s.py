"""Mean `pass.encode` span per pass of the window (engine/encode.py,
encode_rel.py, delta.py, behind server/service.py `_encode_current`)."""

from readers import mean, spans_in_passes


def read(run):
    return mean([sum(s["dur"] for s in spans) for p, spans in spans_in_passes(run, "pass.encode") if spans])
