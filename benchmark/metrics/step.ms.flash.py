"""The attention kernels: the Mosaic calls under a ``MultiHeadAttention`` node
(forward, and the fused backward or its dq and dkv kernels), whatever their
head size or band. Device milliseconds a step of the operations the
program's record (``telemetry.programs()``) puts in the group ``flash``
(lib/groups.py), joined to the trace by their own names (lib/programs.py);
the ``step.ms.*`` metrics add up to ``step.device_ms``. Device trace."""
from lib import programs


def read(run):
    return programs.group_ms(run, "flash")
