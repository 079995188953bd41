"""The attention calls' share of their roofline at head size 256, by the work
any score-free attention must do AT THAT HEAD SIZE: for every attention layer
(the multi-token-prediction module's among them) the forward's 2 products and
the backward's 5 over the causal pairs, each pass the larger of FLOPs over
peak and bytes over bandwidth (lib/counts_glm.py ``flash_calls``), over the
device time of the Mosaic calls traced under ``MultiHeadAttention`` nodes, by
the program's record (``lib/groups.py``: the ``flash`` group), or, without
a record, of the operations the traffic file names under ``kernels.flash`` by
their own names (lib/own_names.py). Device trace."""
from lib import counts_glm as counts
from lib import groups, own_names, programs, readers


def _kernel_seconds(run):
    """(seconds, how many operations) of the flash group by the record, or
    None where there is no record to read."""
    rec = programs.record()
    if rec is None or rec["ops"] is None:
        return None
    by_node = groups.node_groups(rec["nodes"])
    mine = {op["name"] for op in rec["ops"]
            if groups.group_of(rec["nodes"], by_node, op) == "flash"}
    hit = [s for line, s in run["trace"]["op_seconds"].items()
           if own_names.own_name(line) in mine]
    return sum(hit), len(hit)


def read(run):
    tr, cfg = run["traffic"], run["cfg"]
    step = readers.program(run, "step")
    if cfg.get("family") != "glm_moe_lite_lm" or not step or \
            not step["runs"]:
        return None
    seconds, found = _kernel_seconds(run) or own_names.op_seconds(
        run, tr.get("kernels", {}).get("flash"))
    if not found or seconds <= 0:
        return None
    peaks = run["peaks"]
    least = sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                    c["bytes"] / peaks["hbm_bytes_per_s"])
                for layer in counts.flash_calls(cfg, tr["batch"],
                                                tr["seq_len"])
                for c in layer.values())
    return 100.0 * least * step["runs"] / seconds
