"""Device time of the compiled programs whose name matches, from the
profiler trace, per run of the program (or per dispatched serve batch where
`per` says so), in milliseconds."""
import re


def matching(env, pattern):
    tr = env["trace"]
    if not tr or not tr.get("programs"):
        return 0.0, 0.0
    rx = re.compile(pattern)
    hit = [p for n, p in tr["programs"].items() if rx.search(n)]
    return sum(p["seconds"] for p in hit), sum(p["count"] for p in hit)


def read(env, args):
    seconds, runs = matching(env, args["program"])
    if not runs:
        return None
    if args.get("per", "run") == "serve_batch":
        a = env["obs0"].get("serve.batches_total")
        b = env["obs1"].get("serve.batches_total")
        runs = None if a is None or b is None else b - a
    return seconds / runs * 1e3 if runs else None
