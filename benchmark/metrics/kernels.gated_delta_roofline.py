"""The gated delta rule's kernels' share of their roofline, by the work any
chunked implementation of the rule must do: for every Gated DeltaNet layer
the forward's products in the chunked (WY) form at a fixed chunk of 64
tokens and the backward's twice them, each pass the larger of FLOPs over
the MXU peak and the bytes it must move over HBM bandwidth (q, k, v, g,
beta in and o out; the backward also dO in and five gradients out)
(lib/counts_qwen3next.py ``gated_delta_calls``), over the device time of
the operations the traffic file names as ``kernels.gated_delta``, matched
by their own names (lib/own_names.py: the repo's ``gated_delta_fwd``,
``gated_delta_states`` and ``gated_delta_bwd``). The same work whatever
chunk or algorithm the program uses; a program that runs the rule in XLA's
fusions has no such operation, and there is nothing to read. Device
trace."""
from lib import counts_qwen3next as counts
from lib import own_names


def read(run):
    tr, cfg = run["traffic"], run["cfg"]
    if cfg.get("family") != "qwen3_next_lm":
        return None
    return own_names.roofline_pct(
        run, "gated_delta", lambda: [
            c for layer in counts.gated_delta_calls(cfg, tr["batch"],
                                                    tr["seq_len"])
            for c in layer.values()])
