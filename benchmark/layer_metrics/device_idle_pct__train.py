"""device_idle_pct.train — 1 - union of device-op intervals / traced
window, averaged over the chips used. Source: the device trace."""


def read(rec):
    tr = rec["window"].get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
