"""A program's share of its roofline: the least time the chip could take
for the bytes the program has to move (counts.py, from shapes) over the
device time the trace shows for it, in percent. Bandwidth-bound."""
import counts
from sources import trace_program_time


def read(env, args):
    seconds, runs = trace_program_time.matching(env, args["program"])
    if not runs:
        return None
    need = counts.fused_step_bytes(**counts.step_shape(env["ctx"].cfg))
    peak = counts.peaks(env["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (seconds / runs)
