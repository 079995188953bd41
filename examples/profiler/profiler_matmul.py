#!/usr/bin/env python
"""Profile a matmul loop and inspect the trace (reference example/profiler).

The reference brackets iterations 50-70 of a 4096x4096 `dot` loop with
``profiler_set_state('run'/'stop')`` and writes chrome://tracing JSON
(reference example/profiler/profiler_matmul.py:19-46). Same flow here:
the profiler maps onto jax.profiler's XLA trace, annotated per-iteration
with `TraceAnnotation` (the per-op OprExecStat naming analogue); the
example then verifies the trace directory actually contains events.

    python examples/profiler/profiler_matmul.py --iters 20
"""
import argparse
import glob
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--profile-begin", type=int, default=5)
    p.add_argument("--profile-end", type=int, default=15)
    p.add_argument("--size", type=int, default=512)
    args = p.parse_args()

    import mxnet_tpu as mx

    workdir = tempfile.mkdtemp(prefix="mxtpu_profile_")
    profile_file = os.path.join(workdir, "profile_matmul.json")
    mx.profiler.profiler_set_config(mode="symbolic", filename=profile_file)
    print("profile trace will be saved under %s" % workdir)

    A = mx.sym.Variable("A")
    B = mx.sym.Variable("B")
    C = mx.sym.dot(A, B)
    exe = C.simple_bind(mx.cpu(), A=(args.size, args.size),
                        B=(args.size, args.size), grad_req="null")
    exe.arg_dict["A"][:] = mx.nd.uniform(low=-1, high=1,
                                         shape=(args.size, args.size))
    exe.arg_dict["B"][:] = mx.nd.uniform(low=-1, high=1,
                                         shape=(args.size, args.size))

    for i in range(args.iters):
        if i == args.profile_begin:
            mx.profiler.profiler_set_state("run")
        with mx.profiler.TraceAnnotation("matmul_iter_%d" % i):
            out = exe.forward(is_train=False)[0]
            out.wait_to_read()
        if i == args.profile_end:
            mx.profiler.profiler_set_state("stop")
    mx.profiler.dump_profile()

    traces = glob.glob(os.path.join(workdir, "jax_trace", "**", "*"),
                       recursive=True)
    trace_files = [t for t in traces if os.path.isfile(t)]
    total = sum(os.path.getsize(t) for t in trace_files)
    print("trace contains %d files, %d bytes" % (len(trace_files), total))
    assert trace_files and total > 0
    print("profiler OK")


if __name__ == "__main__":
    main()
