"""The DLRM step's shares of the chip's peaks, in percent (counts_dlrm.py,
from shapes; peaks.json). `of` = "step_bytes": the least time the chip
could take for the bytes the step has to move over the device time the
trace shows for the program, bandwidth-bound. `of` = "dense_flops": the
least time for the dense network's matrix products at the chip's bfloat16
peak over the device time of the operations that hold them
(`trace_named_op_time`): the configuration computes in float32 at
Precision.HIGHEST, six bfloat16 passes a product, so this share cannot
pass a sixth (16.7%) and says how far the float32 step is from what
bfloat16 products would allow."""
import counts
import counts_dlrm
from sources import trace_named_op_time, trace_program_time


def read(env, args):
    by_bytes = args["of"] == "step_bytes"
    seconds, runs = trace_program_time.matching(env, args["program"]) \
        if by_bytes else trace_named_op_time.matching(env, args)
    if not runs or not seconds:
        return None
    cfg, peaks = env["ctx"].cfg, counts.peaks(env["device"]["kind"])
    least = counts_dlrm.step_bytes(cfg) / peaks["hbm_bytes_per_s"] \
        if by_bytes else \
        counts_dlrm.dense_flops(cfg) / peaks["bf16_flops_per_s"]
    return 100.0 * least / (seconds / runs)
