"""Growth of one of the program's counters over the window, per
observation of one of its histograms: for example keys relocated per
fused step."""


def read(env, args):
    a, b = env["obs0"].get(args["num"]), env["obs1"].get(args["num"])
    h0, h1 = env["obs0"].get(args["per"]), env["obs1"].get(args["per"])
    if a is None or b is None or not h0 or not h1 \
            or h1["count"] == h0["count"]:
        return None
    return (b - a) / (h1["count"] - h0["count"]) * args.get("scale", 1.0)
