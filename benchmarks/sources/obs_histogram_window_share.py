"""Sum of one of the program's histograms of seconds over the window,
as a share of the window's own length (the driver's `t0` to `t1`), in
percent: the share of the window in which the bracketed thing was
going on (one thread's brackets: they do not overlap)."""


def read(env, args):
    a, b = env["obs0"].get(args["name"]), env["obs1"].get(args["name"])
    res = env.get("res") or {}
    if not a or not b or res.get("t0") is None or res.get("t1") is None \
            or res["t1"] <= res["t0"]:
        return None
    return 100.0 * (b["sum"] - a["sum"]) / (res["t1"] - res["t0"])
