"""The grouped expert products' share of their roofline in the
``glm_moe_lite_lm`` family: for every expert layer (the
multi-token-prediction module's among them) the routed experts' gate, up and
down matrices' forward, dX and dW products at the EXPECTED held assignments
(routing is data), each the larger of FLOPs over peak and bytes over
bandwidth with the held experts' weights crossing HBM once a product
(lib/counts_glm.py ``expert_products``, as ``kernels.expert_product_roofline``
counts them, from this configuration's own keys), over the device time the
trace gives the operations the traffic file names as ``kernels.experts``,
matched by their own names (lib/own_names.py: the repo's ``expert_gmm`` and
``expert_tgmm``, or the compiler's ``ragged-dot``). The shared expert is a
dense feed-forward and not counted here. The products walk a worst-case
buffer, so the rows walked over the rows expected
(``moe.dispatch_rows_ratio``) bound this from above. Device trace."""
from lib import counts_glm as counts
from lib import own_names


def read(run):
    tr, cfg = run["traffic"], run["cfg"]
    if cfg.get("family") != "glm_moe_lite_lm":
        return None
    return own_names.roofline_pct(
        run, "experts", lambda: counts.expert_layers(cfg)
        * counts.expert_products(cfg, tr["batch"] * tr["seq_len"]))
