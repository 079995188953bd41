"""The gated short convolution's kernels' share of their roofline: for every
convolution layer the bytes the elementwise part between the op's two
projections must move in a training step, forward (read B, C and X, write the
gated result) and backward (read them and the incoming gradient, write three
gradients) (lib/counts_lfm2.py ``short_conv_passes``), over HBM bandwidth,
over the device time of the operations the traffic file names as
``kernels.short_conv``, matched by their own names (lib/own_names.py: the
repo's ``short_conv_fwd`` and ``short_conv_bwd``). A program that leaves the
part to XLA's fusions has no such operation, and there is nothing to read:
a fusion's name tells nothing of its graph node. Device trace."""
from lib import counts_lfm2 as counts
from lib import own_names


def read(run):
    tr, cfg = run["traffic"], run["cfg"]
    if cfg.get("family") != "lfm2_moe_lm":
        return None
    return own_names.roofline_pct(
        run, "short_conv", lambda: counts.conv_layers(cfg)
        * counts.short_conv_passes(cfg, tr["batch"] * tr["seq_len"]))
