"""What the program traced outside every graph node: the optimizer's updates
and the executor's casts (``node: ""`` in the record). Device milliseconds
a step of the operations the program's record (``telemetry.programs()``)
puts in the group ``update`` (lib/groups.py), joined to the trace by their
own names (lib/programs.py); the ``step.ms.*`` metrics add up to
``step.device_ms``. Device trace."""
from lib import programs


def read(run):
    return programs.group_ms(run, "update")
