#!/usr/bin/env python3
"""A builder's probe, no verdict: the expert layer's grouped products, each
alone, at the shape of the benchmark cell ``smallthinker_train_8k`` (49152
sorted rows, 16 held experts, 2560 x 768, bfloat16): the compiler's
``jax.lax.ragged_dot`` and its two transposes beside the repo's own
kernels (``ops/pallas/grouped_matmul.py``), milliseconds a call and how
far the kernels' results lie from the compiler's.

    chiprun -- python tools/bench_grouped_matmul.py [--sweep]

``--sweep`` also times other tiles than the shipped ones (a variant that
does not fit a kernel's VMEM says so and is passed over). One line of JSON
a reading.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(f, args, reps):
    """Milliseconds a call: the least of three blocks of ``reps`` calls."""
    import jax
    out = jax.block_until_ready(f(*args))
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            r = f(*args)
        jax.block_until_ready(r)
        t = 1e3 * (time.perf_counter() - t0) / reps
        best = t if best is None else min(best, t)
    return best, out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=49152)
    ap.add_argument("--experts", type=int, default=16)
    ap.add_argument("--d", type=int, default=2560)
    ap.add_argument("--f", type=int, default=768)
    ap.add_argument("--held", type=int, default=12288,
                    help="rows that hold an assignment; the last group is "
                         "stretched over the others, as the layer does")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--interpret", action="store_true",
                    help="no TPU: the kernels interpreted (tiny shapes)")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops.pallas import grouped_matmul as gm

    rng = np.random.default_rng(args.seed)
    r, e, d, f = args.rows, args.experts, args.d, args.f
    cuts = np.sort(rng.integers(0, args.held + 1, e - 1))
    sizes = np.diff(np.concatenate([[0], cuts, [args.held]]))
    sizes[-1] += r - args.held
    sizes = jnp.asarray(sizes, jnp.int32)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32),
                           jnp.bfloat16)

    x_d, x_f = rand(r, d), rand(r, f)
    w_fd, w_df = rand(e, f, d) * 0.02, rand(e, d, f) * 0.02
    interp = args.interpret
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "rows": r, "sizes": np.asarray(sizes).tolist()}))

    def ragged(x, w, s):  # w (E, n, k), as the layer held it at PR 33
        return jax.lax.ragged_dot(x, jnp.swapaxes(w, 1, 2), s,
                                  preferred_element_type=x.dtype)

    def ragged_dx(dy, w, s):
        return jax.lax.ragged_dot(dy, w, s, preferred_element_type=dy.dtype)

    def ragged_dw(dy, x, w, s):
        return jax.vjp(lambda w: ragged(x, w, s), w)[1](dy)[0]

    # (what, the compiler's, its arguments, the kernel's, its arguments)
    cases = [
        ("fwd gate/up (R,2560)->(R,768)", ragged, (x_d, w_fd, sizes),
         "gmm", (x_d, w_fd, sizes), {}),
        ("fwd down (R,768)->(R,2560)", ragged, (x_f, w_df, sizes),
         "gmm", (x_f, w_df, sizes), {}),
        ("dX gate/up (R,768)->(R,2560)", ragged_dx, (x_f, w_fd, sizes),
         "gmm", (x_f, w_fd, sizes), {"transposed": False}),
        ("dX down (R,2560)->(R,768)", ragged_dx, (x_d, w_df, sizes),
         "gmm", (x_d, w_df, sizes), {"transposed": False}),
        ("dW gate/up (E,768,2560)", ragged_dw, (x_f, x_d, w_fd, sizes),
         "tgmm", (x_f, x_d, sizes), {}),
        ("dW down (E,2560,768)", ragged_dw, (x_d, x_f, w_df, sizes),
         "tgmm", (x_d, x_f, sizes), {}),
    ]
    sweeps = {"gmm": [{}], "tgmm": [{}]}
    if args.sweep:
        sweeps["gmm"] += [{"chunk": 128}, {"chunk": 768}, {"tm": 128},
                          {"tm": 512}, {"tm": 512, "chunk": 128}]
        sweeps["tgmm"] += [{"tm": 512}, {"wide": 640},
                           {"tm": 1024, "wide": 640}, {"wide": 2560}]
    for what, ref, ref_args, kind, k_args, fixed in cases:
        flops = 2.0 * r * d * f
        line = {"what": what}
        if not interp:
            ms, want = timed(jax.jit(ref), ref_args, args.reps)
            line.update(compiler_ms=round(ms, 4),
                        compiler_mxu_pct=round(flops / ms / 197e9 * 100, 1))
        else:
            want = ref(*ref_args)
        print(json.dumps(line), flush=True)
        want = np.asarray(want, np.float32)
        for tiles in sweeps[kind]:
            kw = dict(fixed, interpret=interp, **tiles)
            line = {"what": what, "kernel": kind, "tiles": tiles}
            try:
                ms, got = timed(lambda *a: getattr(gm, kind)(*a, **kw),
                                k_args, 1 if interp else args.reps)
            except Exception as err:  # does not fit VMEM, or does not lower
                line["refused"] = str(err).splitlines()[0][:200]
                print(json.dumps(line), flush=True)
                continue
            got = np.asarray(got, np.float32)
            line.update(ms=round(ms, 4),
                        mxu_pct=round(flops / ms / 197e9 * 100, 1),
                        diff=float(np.linalg.norm(got - want)
                                   / np.linalg.norm(want)))
            if interp:
                del line["ms"], line["mxu_pct"]
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
