"""The score program's share of its roofline: the least time the chip
could take for the bytes a score dispatch has to read (counts_mf.py, from
shapes) over the device time the trace shows for it, in percent.
Bandwidth-bound."""
import counts
import counts_mf
from sources import trace_program_time


def read(env, args):
    seconds, runs = trace_program_time.matching(env, args["program"])
    if not runs:
        return None
    cfg = env["ctx"].cfg
    need = counts_mf.score_bytes(cfg["batch_size"], cfg["step"]["row_bytes"])
    peak = counts.peaks(env["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (seconds / runs)
