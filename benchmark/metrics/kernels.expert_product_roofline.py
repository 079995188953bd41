"""The grouped expert products' share of their roofline: for every layer the
gate, up and down matrices' forward, dX and dW products at the EXPECTED held
assignments (routing is data), each the larger of FLOPs over peak and bytes
over bandwidth with the held experts' weights crossing HBM once a product
(lib/counts_smallthinker.py ``expert_products``), over the device time the
trace gives the operations the traffic file names as ``kernels.experts``. A
forward that the backward recomputes adds seconds and no work. Device
trace."""
from lib import counts_smallthinker as counts
from lib import readers


def read(run):
    tr, cfg = run["traffic"], run["cfg"]
    step = readers.program(run, "step")
    pats = tr.get("kernels", {}).get("experts")
    if not step or not pats or "moe_num_primary_experts" not in cfg:
        return None
    seconds, found = readers.op_seconds(run, pats)
    if not found or seconds <= 0:
        return None
    peaks = run["peaks"]
    least = sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                    c["bytes"] / peaks["hbm_bytes_per_s"])
                for c in counts.expert_products(
                    cfg, tr["batch"] * tr["seq_len"]))
    least *= cfg["num_hidden_layers"] * step["runs"]
    return 100.0 * least / seconds
