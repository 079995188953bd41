"""How many times the step runs each layer it holds: the mixers traced
over the layers held, told apart by the leaves they read
(``loop_steps``), a static attribute of the program's
``executor.train_step`` span where a ``LoopExitLoss`` was traced
(``executor.py`` ``_loop_attrs``, with ``loop_layers`` and ``loop_exits``).
``lib/counts_ouro.py`` counts every layer ``total_ut_steps`` times a step,
and this says the program did so: 4 for the Ouro cell. A program without
the attributes (a parent commit, a stack run once) gives None. Program
span."""
from lib import spans


def read(run):
    for r in reversed(spans.records()):
        a = r["args"]
        if r["name"] == spans.STEP and a.get("loop_steps"):
            return float(a["loop_steps"])
    return None
