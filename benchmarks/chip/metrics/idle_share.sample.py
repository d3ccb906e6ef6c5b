"""Device idle share of the sampling window: 1 - the union of the device's
operation intervals over the traced window, in percent."""

from chipbench import traces


def read(layer: dict):
    tr = layer.get("trace")
    if not tr or not tr["devices"]:
        return None
    return traces.idle_share(tr)
