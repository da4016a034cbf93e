"""Share of the traced window, in %, in which no operation ran on the
device (mean over the chips the cell uses): 1 - busy / window."""


def read(rd):
    tr = rd.get("trace")
    if tr is None or tr.window_s <= 0 or not tr.chips:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
