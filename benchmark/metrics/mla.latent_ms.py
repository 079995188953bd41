"""Latent attention's own work: device milliseconds a step of the operations
the program's record traced from the latent projections' nodes, forward and
backward (``<layer>_mla_*``: the q down-projection, its RMSNorm and the q
up-projection; the kv down-projection, the latent's slice and RMSNorm, its
up-projection and ``LatentKV``'s assembly of the key), in every layer and
in the multi-token-prediction module's (lib/node_ms.py). The o projection
and the attention node itself are not in it. The operations lie in the
``step.ms.*`` groups their operators give them (``lib/groups.py``), so the
groups still add up. A program without such nodes gives None. Device
trace."""
from lib.node_ms import node_ms


def read(run):
    return node_ms(run, lambda node: "_mla_" in node)
