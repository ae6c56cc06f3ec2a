"""The fused gather+pool programs' share of their roofline: the least
time the chip could take for the bytes the window's bag batches have to
move (counts_bags.py, from the members and bags the program's own
histograms say were asked for) over the device time the trace shows for
the programs, in percent. Bandwidth-bound."""
import counts
import counts_bags
from sources import trace_program_time


def read(env, args):
    seconds, runs = trace_program_time.matching(env, args["program"])

    def grown(name):
        a, b = env["obs0"].get(name), env["obs1"].get(name)
        return b["sum"] - a["sum"] if a and b else None
    members, bags = (grown("serve.bag_batch_members"),
                     grown("serve.bag_batch_bags"))
    if not runs or not seconds or not members or not bags:
        return None
    need = counts_bags.bag_read_bytes(
        members, bags, env["ctx"].cfg["step"]["row_bytes"])
    peak = counts.peaks(env["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / seconds
