"""The head's products and the loss (and every node that is in no other
group). Device milliseconds a step of the operations the program's record
(``telemetry.programs()``) puts in the group ``head_loss`` (lib/groups.py),
joined to the trace by their own names (lib/programs.py); the ``step.ms.*``
metrics add up to ``step.device_ms``. Device trace."""
from lib import programs


def read(run):
    return programs.group_ms(run, "head_loss")
