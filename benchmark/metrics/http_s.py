"""Mean over the window's passes of the client round trip minus the same
pass's `pass.gang` span: request parsing, queueing for the session's
pass slot, response, and the connection."""

from readers import mean, spans_in_passes


def read(run):
    return mean([
        (p["t_recv"] - p["t_send"]) - spans[0]["dur"]
        for p, spans in spans_in_passes(run, "pass.gang") if len(spans) == 1
    ])
