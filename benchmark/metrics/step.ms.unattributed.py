"""Operations of the trace that the program's record does not hold: the
asynchronous copies, which it leaves out, and everything, should the names
of the program that runs ever part from the record's. The check of the
join. Device milliseconds a step of the operations the program's record
(``telemetry.programs()``) puts in the group ``unattributed``
(lib/groups.py), joined to the trace by their own names (lib/programs.py);
the ``step.ms.*`` metrics add up to ``step.device_ms``. Device trace."""
from lib import programs


def read(run):
    return programs.group_ms(run, "unattributed")
