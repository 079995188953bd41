"""Device operations picked by their OWN names. In a trace every operation's
name is its whole line of the program: ``%name = type opcode(operands),
attributes``. A Mosaic call is named for its kernel (``expert_gmm.7``,
``jvp_layer1_attn_.1``), and a fusion that reads a kernel's result carries
that name too, among its operands; an attribute that every Pallas call
carries (``kernel_metadata``) tells no kernel from another. What stands
before `` = `` is the operation's own."""


def own_name(line):
    return line.split(" = ")[0].strip().lstrip("%")


def op_seconds(run, patterns):
    """Device seconds of the operations whose OWN name holds one of
    ``patterns``, and how many such operations there were."""
    trace = run.get("trace")
    if not trace or not patterns:
        return 0.0, 0
    hit = [v for n, v in trace["op_seconds"].items()
           if any(p in own_name(n) for p in patterns)]
    return sum(hit), len(hit)


def roofline_pct(run, kernels, calls):
    """100 x the least seconds the work ``calls()`` (an iterable of
    ``{"flops", "bytes"}`` a step: each call the larger of FLOPs over peak
    and bytes over bandwidth) takes, over the device seconds of the
    operations the traffic file names under ``kernels`` by their own names.
    None where the trace holds no run of the step or no such operation."""
    from lib import readers

    step = readers.program(run, "step")
    seconds, found = op_seconds(
        run, run["traffic"].get("kernels", {}).get(kernels))
    if not step or not found or seconds <= 0:
        return None
    peaks = run["peaks"]
    least = sum(max(c["flops"] / peaks["bf16_flops_per_s"],
                    c["bytes"] / peaks["hbm_bytes_per_s"]) for c in calls())
    return 100.0 * least * step["runs"] / seconds
