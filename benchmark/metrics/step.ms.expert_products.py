"""The expert layers' grouped products, by their own names: the repo's
``expert_gmm`` / ``expert_tgmm`` kernels, or the compiler's ``ragged-dot``.
Device milliseconds a step of the operations the program's record
(``telemetry.programs()``) puts in the group ``expert_products``
(lib/groups.py), joined to the trace by their own names (lib/programs.py);
the ``step.ms.*`` metrics add up to ``step.device_ms``. Device trace."""
from lib import programs


def read(run):
    return programs.group_ms(run, "expert_products")
