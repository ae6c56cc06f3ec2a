"""Growth of one of the program's counters over the window, per unit of
growth of another."""


def read(env, args):
    def delta(name):
        a, b = env["obs0"].get(name), env["obs1"].get(name)
        return None if a is None or b is None else b - a
    num, den = delta(args["num"]), delta(args["den"])
    if num is None or not den:
        return None
    return num / den
