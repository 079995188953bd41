"""The ``ShortConv`` nodes: the six products a layer and the two kernels of
the elementwise part (or XLA's fusions in their place). Device milliseconds
a step of the operations the program's record (``telemetry.programs()``)
puts in the group ``short_conv`` (lib/groups.py), joined to the trace by
their own names (lib/programs.py); the ``step.ms.*`` metrics add up to
``step.device_ms``. Device trace."""
from lib import programs


def read(run):
    return programs.group_ms(run, "short_conv")
