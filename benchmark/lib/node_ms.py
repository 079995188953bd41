"""Device milliseconds a step of the operations the program's record
(``telemetry.programs()``) traced from graph nodes that a rule picks by
name, forward and backward, joined to the trace by their own names
(``lib/programs.py``). None where the trace holds no run of the step, the
record no ``ops``, or the rule no node (a parent commit, a model without
such nodes)."""
from lib import own_names, programs, readers


def node_ms(run, picked):
    """``picked(node name)`` -> whether the node's operations count."""
    step, rec = readers.program(run, "step"), programs.record()
    if not step or not step["runs"] or rec is None or rec["ops"] is None:
        return None
    mine = {op["name"] for op in rec["ops"] if op["node"]
            and picked(op["node"])}
    if not mine:
        return None
    seconds = sum(s for line, s in run["trace"]["op_seconds"].items()
                  if own_names.own_name(line) in mine)
    return 1e3 * seconds / step["runs"]
