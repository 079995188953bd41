"""The program's own record of the step it built, joined with a trace.

``mxnet_tpu.telemetry.programs()`` (read in the driver's process, as
``lib/spans.py`` reads the ring) holds one record a step program the
executor built: every device operation's own name with the graph node it
was traced from (``ops``), every node's operator and inputs (``nodes``),
what the layers built and the bytes the compiled program wants
(``memory``). A trace's ``op_seconds`` is keyed by each operation's whole
line, whose head is that own name (``lib/own_names.py``): the join is by
name and by nothing else. An operation of the trace that the record does
not hold (the asynchronous copies, which the record leaves out; everything,
should the names ever part) is ``unattributed``.

Every device operation of the window falls in exactly one group
(``lib/groups.py``), so the ``step.ms.*`` metrics add up to the seconds of
all operations over the step's runs: ``step.device_ms`` but for what runs
beside other work. A program that keeps no such record (a parent commit from
before it, ``MXNET_TELEMETRY=0``, a step built by plain ``jax.jit``) gives
None everywhere, and the harness leaves the metrics out.
"""
from lib import groups, own_names, readers


def record():
    """The newest ``train_step`` record, or None."""
    try:
        from mxnet_tpu import telemetry
        found = [r for r in telemetry.programs()
                 if r["program"] == "train_step"]
    except Exception:
        return None
    return found[-1] if found else None


def group_seconds(op_seconds, rec):
    """``{group: seconds}`` over a trace's ``op_seconds`` (every group of
    ``groups.GROUPS`` is there) and ``{own name: group}`` for its
    operations."""
    by_node = groups.node_groups(rec["nodes"])
    known = {op["name"]: groups.group_of(rec["nodes"], by_node, op)
             for op in rec["ops"]}
    total, where = dict.fromkeys(groups.GROUPS, 0.0), {}
    for line, seconds in op_seconds.items():
        name = own_names.own_name(line)
        where[name] = known.get(name, "unattributed")
        total[where[name]] += seconds
    return total, where


def group_ms(run, group):
    """Milliseconds a step of ``group``'s operations, or None where the
    trace holds no run of the step or the record no ``ops``."""
    step, rec = readers.program(run, "step"), record()
    if not step or not step["runs"] or rec is None or rec["ops"] is None:
        return None
    total, _ = group_seconds(run["trace"]["op_seconds"], rec)
    return 1e3 * total[group] / step["runs"]


def memory(key):
    """A number of the newest record's ``memory``, or None."""
    rec = record()
    return rec["memory"].get(key) if rec and rec.get("memory") else None
