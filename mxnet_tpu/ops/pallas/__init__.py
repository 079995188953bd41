"""Hand-written Pallas TPU kernels — the framework's fast-path layer.

Layering mirrors the reference's cuDNN strategy (SURVEY §2.1 #16,
src/operator/cudnn_*.h): every op has a portable XLA reference
implementation, and a Pallas kernel is selected when the backend is TPU and
the shapes qualify; otherwise the reference path runs. The platform half
of every kernel's gate is :func:`on_tpu`; the shape half lives with each
kernel (the analogue of the cudnn_algoreg autotune gate,
cudnn_algoreg-inl.h).
"""
import jax

from . import flash_attention  # noqa: F401
from . import grouped_matmul  # noqa: F401
from . import lstm  # noqa: F401


def on_tpu() -> bool:
    """True when the process's default JAX backend is the TPU. A backend
    that fails to initialise raises here, at the selection site: a kernel
    is never dropped for the reference path because the device could not
    be reached."""
    return jax.default_backend() == "tpu"
