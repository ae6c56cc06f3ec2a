"""Share of the traced window in which no operation ran on the device,
worst device, in percent."""


def read(env, args):
    tr = env["trace"]
    if not tr or tr.get("idle_share_worst") is None:
        return None
    return 100.0 * tr["idle_share_worst"]
