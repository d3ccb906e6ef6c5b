"""Device milliseconds per commit: the summed device time of the sampling
chunk programs (line "XLA Modules", the jitted ``chunk``) in the traced
window, over the commits the window made, per chip."""

from chipbench import traces

CHUNK = r"chunk"


def read(layer: dict):
    tr = layer.get("trace")
    if not tr or not layer.get("commits"):
        return None
    ns = traces.named_ns(tr, CHUNK, line="modules")
    if ns <= 0:
        return None
    return ns / len(tr["devices"]) / layer["commits"] / 1e6
