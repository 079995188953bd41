"""The exits' objective: device milliseconds a step of the operations the
program's record (``telemetry.programs()``) traced from a ``LoopExitLoss``
node, forward and backward (every exit's float32 logsumexp and its
gradient, the exit distribution and its entropy), joined to the trace by
their own names (lib/programs.py). The four heads' products are the head
nodes' and stay out; all of this is also within ``step.ms.head_loss``. A
program without such a node (a parent commit, a stack run once) gives None.
Device trace."""
from lib import own_names, programs, readers


def read(run):
    step, rec = readers.program(run, "step"), programs.record()
    if not step or not step["runs"] or rec is None or rec["ops"] is None:
        return None
    nodes = {n for n, v in rec["nodes"].items() if v["op"] == "LoopExitLoss"}
    mine = {op["name"] for op in rec["ops"] if op["node"] in nodes}
    if not mine:
        return None
    seconds = sum(s for line, s in run["trace"]["op_seconds"].items()
                  if own_names.own_name(line) in mine)
    return 1e3 * seconds / step["runs"]
