"""Norms and residual adds. Device milliseconds a step of the operations the
program's record (``telemetry.programs()``) puts in the group ``rest``
(lib/groups.py), joined to the trace by their own names (lib/programs.py);
the ``step.ms.*`` metrics add up to ``step.device_ms``. Device trace."""
from lib import programs


def read(run):
    return programs.group_ms(run, "rest")
