"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses."""


def read(view):
    if view.summary is None:
        return None
    return 100 * view.summary.idle_share
