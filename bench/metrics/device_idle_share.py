"""Share of the traced window in which no operation ran on the device
(one minus the union of its busy intervals), in percent."""


def read(run):
    red = run.reduction
    if red is None or red.busy_s <= 0:
        return None
    return 100.0 * red.idle_share
