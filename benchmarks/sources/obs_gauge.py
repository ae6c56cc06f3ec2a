"""One of the program's gauges as it stood at the end of the window: a
level, not a rate."""


def read(env, args):
    return env["obs1"].get(args["name"])
