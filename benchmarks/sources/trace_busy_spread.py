"""Fullest minus emptiest of the devices' busy shares of the traced
window, in percent: how unevenly the chips were loaded."""


def read(env, args):
    tr = env["trace"]
    if not tr or not tr.get("window_s") or len(tr.get("devices", [])) < 2:
        return None
    busy = [d["busy_s"] / tr["window_s"] for d in tr["devices"]]
    return 100.0 * (max(busy) - min(busy))
