"""Sum of several of the program's histograms over the window, per
observation of one of them: for example host seconds spent per step."""


def read(env, args):
    def grown(name):
        a, b = env["obs0"].get(name), env["obs1"].get(name)
        if not a or not b:
            return None
        return b["sum"] - a["sum"], b["count"] - a["count"]
    per = grown(args["per"])
    parts = [grown(name) for name in args["sum"]]
    if per is None or not per[1] or None in parts:
        return None
    return sum(p[0] for p in parts) / per[1] * args.get("scale", 1.0)
