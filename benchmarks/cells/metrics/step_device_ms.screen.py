"""Device busy time per launch of the model step, in milliseconds: the
union of device-op time in the traced window (mean over the cell's
chips) over the launches of the packed program in that window (one
batch, or one wave of shards)."""


def read(view):
    t = view.trace
    if t is None or not view.ans["launches"]:
        return None
    return 1e3 * t["busy_s"] / view.ans["launches"]
