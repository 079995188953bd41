"""The Gated DeltaNet mixers' own work: device milliseconds a step of the
operations the program's record traced from the nodes named ``_gdn_``,
forward and backward (``<layer>_gdn_*``: the q|k|v, z and b|a projections,
the ``GatedDeltaRule`` core with its convolution, norms,
gates and the rule's kernels, the gated RMSNorm over each value head and
silu(z), the out projection), in every Gated DeltaNet layer
(lib/node_ms.py). The block's residual adds and norms are not in it. The
operations lie in the ``step.ms.*`` groups their operators give them
(``lib/groups.py``), so the groups still add up. A program without such
nodes gives None. Device trace."""
from lib.node_ms import node_ms


def read(run):
    return node_ms(run, lambda node: "_gdn_" in node)
