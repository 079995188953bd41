"""The whole step's share of the chip's peak with host time taken out:
model FLOPs of one step (forward and backward, recomputation not counted)
over the device time of one step, over the peak. Device trace."""
import statistics

from lib import readers


def read(run):
    step = readers.program(run, "step")
    if not step or not run.get("step_flops"):
        return None
    device_s = statistics.fmean(step["busy_s"])
    return 100.0 * run["step_flops"] / device_s / \
        run["peaks"]["bf16_flops_per_s"]
