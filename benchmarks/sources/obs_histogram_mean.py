"""Mean of one of the program's histograms over the window."""


def read(env, args):
    a, b = env["obs0"].get(args["name"]), env["obs1"].get(args["name"])
    if not a or not b or b["count"] == a["count"]:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"]) \
        * args.get("scale", 1.0)
