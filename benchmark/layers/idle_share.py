"""idle_share.<cell kind>: the share of the traced window in which no
operation ran on the device, in % (device_trace)."""


def read(name, run):
    red = run.reduced
    if not red or red["busy_s"] <= 0 or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
