"""The flash attention kernels' share of their roofline: the least time the
chip could take for the step's flash calls (forward, dq and dkv in every
layer; for each the larger of FLOPs over peak and bytes over bandwidth, from
lib/counts.py) over the device time the trace gives those kernels. The
traffic file names the kernels' operations (``kernels.flash``). Device
trace."""
from lib import counts, readers


def read(run):
    tr, cfg = run["traffic"], run["cfg"]
    step = readers.program(run, "step")
    pats = tr.get("kernels", {}).get("flash")
    if not step or not pats or "seq_len" not in tr:
        return None
    seconds, found = readers.op_seconds(run, pats)
    if not found or seconds <= 0:
        return None
    peaks = run["peaks"]
    calls = counts.flash_calls(cfg, tr["batch"], tr["seq_len"])
    least = sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                    c["bytes"] / peaks["hbm_bytes_per_s"])
                for c in calls.values())
    least *= cfg["num_hidden_layers"] * step["runs"]
    return 100.0 * least / seconds
