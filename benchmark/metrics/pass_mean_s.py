"""Mean client round trip of POST /api/v1/schedule?mode=gang&record=0 over
every pass of the window: all round trips summed, over the passes. The
window holds whole rounds of the mix, so every run weighs each count
alike."""


def read(run):
    rts = [p["t_recv"] - p["t_send"] for p in run["window"]]
    return sum(rts) / len(rts) if rts else None
