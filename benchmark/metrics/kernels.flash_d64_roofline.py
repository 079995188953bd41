"""The attention calls' share of their roofline at head size 64, by the work
any score-free attention must do AT THAT HEAD SIZE: for every attention layer
the forward's 2 products and the backward's 5 over the causal pairs, each
pass the larger of FLOPs over peak and bytes over bandwidth
(lib/counts_lfm2.py ``flash_calls``), over the device time the trace gives
the operations the traffic file names as ``kernels.flash``, matched by their
own names (lib/own_names.py). Kernels that pad a head to 128 lanes, or that
fill half of the MXU's 128 x 128 at a head of 64, do the counted work in
more time: this reads what a head of 64 costs here. Device trace."""
from lib import counts_lfm2 as counts
from lib import own_names


def read(run):
    tr, cfg = run["traffic"], run["cfg"]
    if cfg.get("family") != "lfm2_moe_lm":
        return None
    return own_names.roofline_pct(run, "flash", lambda: [
        c for layer in counts.flash_calls(cfg, tr["batch"], tr["seq_len"])
        for c in layer.values()])
