"""device_idle_share: the share of the traced window in which no
operation ran on the device, in percent."""

import readings


def read(run):
    if readings.device_plane(run) is None:
        return None
    bw = readings.busy_window(run)
    return 100.0 * (1.0 - bw["busy_s"] / bw["window_s"])
