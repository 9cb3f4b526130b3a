"""Share of the traced window in which no operation ran on the device
(averaged over the devices), in percent."""


def read(ev):
    if ev.busy is None or ev.busy["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ev.busy["busy_s"] / ev.busy["window_s"])
