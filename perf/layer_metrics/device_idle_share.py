"""Device: 1 − (seconds in which any operation ran on the device, averaged
over the chips) ÷ the traced window, in per cent."""


def read(obs):
    t = obs.trace
    if not t or not t.get("devices") or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
