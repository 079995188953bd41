"""Operator library: importing this package registers every operator.

The registry (registry.py) is the single source of truth from which the
imperative (ndarray) and symbolic (symbol) user APIs are generated — the
TPU-native analogue of the reference's runtime op registry + generated
Python functions (python/mxnet/ndarray.py:28-39).
"""
from . import registry  # noqa: F401
from .registry import OP_REGISTRY, OpContext, OpDef, defop, get_op, alias  # noqa: F401

# Import order only matters for aliases; each module self-registers.
from . import elemwise  # noqa: F401
from . import broadcast_reduce  # noqa: F401
from . import matrix  # noqa: F401
from . import nn  # noqa: F401
from . import init_random  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import sequence  # noqa: F401
from . import shape_rules  # noqa: F401
from . import rnn_fused  # noqa: F401
from . import attention  # noqa: F401
from . import moe  # noqa: F401
from . import shortconv  # noqa: F401
from . import contrib  # noqa: F401
from . import custom  # noqa: F401
