"""Growth of one of the program's counters (or monotone gauges) over the
window, per unit of growth of the SUM of several others: a share of a
whole that the program counts in parts (cold members of hot + cold), or
a cost per unit of work counted in two places (rows examined per row
promoted or demoted)."""


def read(env, args):
    def delta(name):
        a, b = env["obs0"].get(name), env["obs1"].get(name)
        return None if a is None or b is None else b - a
    num = delta(args["num"])
    parts = [delta(name) for name in args["of"]]
    if num is None or None in parts or not sum(parts):
        return None
    return num / sum(parts) * args.get("scale", 1.0)
