"""Device time of the operations whose NAMES the driver lists in its
result (`res[args["names"]]`, read off the compiled program's own text),
from the `XLA Ops` line of the profiler trace, per run of a compiled
program, in milliseconds. On a TPU a matrix product is a `convolution`
inside a `fusion`, so neither its opcode nor its shapes name it in the
trace; the compiled text says which fusions hold one."""
from sources import trace_program_time


def matching(env, args):
    """(seconds of the named operations, runs of the program)."""
    tr, names = env["trace"], (env["res"] or {}).get(args["names"])
    if not tr or not names:
        return 0.0, 0.0
    _, runs = trace_program_time.matching(env, args["per_program"])
    names = set(names)
    return sum(s for op, s in tr["device_ops"]
               if op.split(" ")[0] in names), runs


def read(env, args):
    seconds, runs = matching(env, args)
    return seconds / runs * 1e3 if runs and seconds else None
