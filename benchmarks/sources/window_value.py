"""A reading the driver took itself during the window (for example how
late the load generator issued requests)."""


def read(env, args):
    return env["res"].get(args["key"])
