"""The flash attention calls' share of their roofline, by the work any
score-free attention must do: for every layer the forward's 2 products and
the backward's 5 over the pairs its band holds (a window layer's fewer than a
global layer's), each pass the larger of FLOPs over peak and bytes over
bandwidth (lib/counts_smallthinker.py ``band_flash_calls``), over the device
time the trace gives the operations the traffic file names as
``kernels.flash``. Device trace."""
from lib import counts_smallthinker as counts
from lib import readers


def read(run):
    tr, cfg = run["traffic"], run["cfg"]
    step = readers.program(run, "step")
    pats = tr.get("kernels", {}).get("flash")
    if not step or not pats or "sliding_window_layout" not in cfg:
        return None
    seconds, found = readers.op_seconds(run, pats)
    if not found or seconds <= 0:
        return None
    peaks = run["peaks"]
    least = sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                    c["bytes"] / peaks["hbm_bytes_per_s"])
                for layer in counts.band_flash_calls(cfg, tr["batch"],
                                                     tr["seq_len"])
                for c in layer.values())
    return 100.0 * least * step["runs"] / seconds
