"""device_idle.train: the share of the traced window in which no operation
runs on the device, in % (1 - busy union / window, averaged over chips)."""


def read(ctx):
    s = ctx["summary"]
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
