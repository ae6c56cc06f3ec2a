"""Device time of the operations whose HLO opcode matches, from the `XLA
Ops` line of the profiler trace (mean over the devices), per run of a
compiled program, in milliseconds."""
import re

from sources import trace_program_time


def read(env, args):
    tr = env["trace"]
    _, runs = trace_program_time.matching(env, args["per_program"])
    if not tr or not runs:
        return None
    # trace_reduce.short_op: "<name> <opcode> <result> <- <operands>"
    rx = re.compile(args["opcode"])
    seconds = sum(s for op, s in tr["device_ops"]
                  if len(op.split(" ")) > 1 and rx.search(op.split(" ")[1]))
    return seconds / runs * 1e3
