"""device.idle_share: the share of the traced call in which no
operation ran on the device (1 − union of operation intervals / call
length), averaged over the cell's chips."""


def read(rec):
    tr = rec["trace"]
    if tr.window_s <= 0.0 or not tr.ops:
        return None
    return 100.0 * (1.0 - tr.busy_s(tr.window) / tr.window_s)
