"""jax's compile requests (jax.monitoring): seconds spent before the
window (set-up), or how many fell inside it."""


def read(env, args):
    comp, res = env["ctx"].compiles, env["res"]
    if args["when"] == "in_window":
        return float(len(comp.between(res["t0"], res["t1"])))
    return float(sum(e[2] for e in comp.events if e[0] <= res["t0"]))
