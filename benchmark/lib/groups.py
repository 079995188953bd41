"""From a graph node to what it is for: the groups the ``step.ms.*`` metrics
split a training step's device time into.

The program says of every device operation which graph node it was traced
from, and of every graph node its operator and the names of what it reads
(``mxnet_tpu.telemetry.programs()``: a record's ``ops`` and ``nodes``). The
rules here turn that into a group, from the node's operator and, for a
product, from what it feeds or is fed by:

- ``flash``: a Mosaic call under a ``MultiHeadAttention`` node;
- ``attention_rest``: the rest of such a node (rotation, head norms, the
  relayouts around the kernels) and the products that feed it or read it;
- ``feed_forward``: a dense layer's products, its activation and gate;
- ``expert_products``: the grouped products, by their own names (the repo's
  ``expert_gmm`` / ``expert_tgmm``, the compiler's ``ragged-dot``, whose tile
  metadata carries no graph node's name);
- ``expert_moves``: everything else under an ``ExpertFFN`` node;
- ``short_conv``: a ``ShortConv`` node, products and kernels;
- ``embedding``; ``rest`` (norms and residual adds); ``head_loss`` (every
  other node: the head's product, the loss and what is around them);
- ``update``: an operation the program traced outside every node (the
  optimizer's rule, the executor's casts);
- ``unattributed``: an operation of the trace that the record does not
  hold (``lib/programs.py``).

``tools/step_ops.py`` and ``tools/cell_ops.py`` print by the same rules.
"""
import collections

GROUPS = ("flash", "attention_rest", "feed_forward", "expert_products",
          "expert_moves", "short_conv", "head_loss", "embedding", "update",
          "rest", "unattributed")
PRODUCTS = ("ragged-dot", "expert_gmm", "expert_tgmm")
BY_OPERATOR = {"Embedding": "embedding", "LayerNorm": "rest",
               "RMSNorm": "rest", "ExpertFFN": "expert_moves",
               "MultiHeadAttention": "attention_rest",
               "ShortConv": "short_conv", "Activation": "feed_forward",
               "elemwise_add": "rest", "_plus": "rest",
               "broadcast_add": "rest"}


def node_groups(nodes):
    """Graph node name -> group, for a record's ``nodes``."""
    feeds = collections.defaultdict(set)  # node -> operators that read it
    for n in nodes.values():
        for child in n["inputs"]:
            feeds[child].add(n["op"])
    out = {}
    for name, n in nodes.items():
        near = feeds[name] | {nodes[c]["op"] for c in n["inputs"]
                              if c in nodes}
        if n["op"] == "FullyConnected" and "MultiHeadAttention" in near:
            out[name] = "attention_rest"
        elif n["op"] == "FullyConnected" and near & {"Activation",
                                                     "broadcast_mul"}:
            out[name] = "feed_forward"  # a gated one's up and down too
        elif n["op"] == "broadcast_mul" and "Activation" in near:
            out[name] = "feed_forward"
        else:
            out[name] = BY_OPERATOR.get(n["op"], "head_loss")
    return out


def group_of(nodes, groups, op):
    """The group of ``op``, one of a record's ``ops``; ``groups`` is
    ``node_groups(nodes)``."""
    if op["name"].startswith(PRODUCTS):
        return "expert_products"
    node = nodes.get(op["node"])
    if op["kernel"] and node and node["op"] == "MultiHeadAttention":
        return "flash"
    return groups.get(op["node"], "update")
