"""Growth of one of the program's counters over the window per unit of
growth of another, after a third's growth is taken from both: the share
of a whole with a part left out that belongs to it by construction."""


def read(env, args):
    def delta(name):
        a, b = env["obs0"].get(name), env["obs1"].get(name)
        return None if a is None or b is None else b - a
    num, den, less = (delta(args[k]) for k in ("num", "den", "less"))
    if num is None or den is None or less is None or den == less:
        return None
    return (num - less) / (den - less)
