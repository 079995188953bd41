"""A dense layer's products, its activation and its gate (a GELU MLP; SwiGLU).
Device milliseconds a step of the operations the program's record
(``telemetry.programs()``) puts in the group ``feed_forward``
(lib/groups.py), joined to the trace by their own names (lib/programs.py);
the ``step.ms.*`` metrics add up to ``step.device_ms``. Device trace."""
from lib import programs


def read(run):
    return programs.group_ms(run, "feed_forward")
