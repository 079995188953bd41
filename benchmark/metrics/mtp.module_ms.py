"""The multi-token-prediction module: device milliseconds a step of the
operations the program's record traced from its nodes (``mtp_*``: the
embedding of the labels, its two input norms and ``eh_proj``, its expert
layer with latent attention, its final norm, its product with the shared
head and ``MultiTokenLoss``), forward and backward (lib/node_ms.py). A part
across the ``step.ms.*`` groups, as ``loop.exit_objective_ms`` is, so they
still add up. A program without such nodes gives None. Device trace."""
from lib.node_ms import node_ms


def read(run):
    return node_ms(run, lambda node: node.startswith("mtp_"))
