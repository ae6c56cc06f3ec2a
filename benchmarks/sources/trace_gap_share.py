"""Of the device's idle seconds in the traced window, the share that
trace_reduce gave to host events whose name starts with a prefix (the
program's own spans), in percent."""


def read(env, args):
    gaps = (env["trace"] or {}).get("idle_gaps") or []
    ours = [s for name, s in gaps if name.startswith(args["prefix"])]
    total = sum(s for _, s in gaps)
    if not ours or not total:
        return None     # a program without such spans: nothing to read
    return 100.0 * sum(ours) / total
