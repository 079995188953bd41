#!/usr/bin/env python
"""Sequence/context parallelism: ring attention over a device mesh.

The reference handles long sequences with bucketing + unrolling only
(SURVEY §5.7 — it predates sequence parallelism); this framework adds the
modern mechanism as a first-class citizen: ``parallel.ring_attention``
shards the sequence across a mesh axis and rotates K/V blocks around the
ring with ``ppermute`` over ICI, computing attention in an online-softmax
accumulator so the full attention matrix never materializes.

Run on a virtual mesh:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long-context/ring_attention_demo.py --seq-len 2048
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--head-dim", type=int, default=32)
    p.add_argument("--batch", type=int, default=2)
    args = p.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from mxnet_tpu.parallel.ring_attention import ring_attention

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("seq",))
    rng = np.random.RandomState(0)
    shape = (args.batch, args.heads, args.seq_len, args.head_dim)
    q = jnp.asarray(rng.randn(*shape).astype(np.float32))
    k = jnp.asarray(rng.randn(*shape).astype(np.float32))
    v = jnp.asarray(rng.randn(*shape).astype(np.float32))

    out = ring_attention(q, k, v, mesh, causal=True)
    out = np.asarray(out)

    # reference: plain causal attention on one device
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(args.head_dim)
    mask = np.tril(np.ones((args.seq_len, args.seq_len), bool))
    s = np.where(mask, s, -1e30)
    p_ = np.exp(s - s.max(-1, keepdims=True))
    p_ /= p_.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p_, v)

    err = np.abs(out - ref).max()
    print("ring attention over %d devices, seq %d: max err vs dense %.2e"
          % (len(devs), args.seq_len, err))
    assert err < 2e-4


if __name__ == "__main__":
    main()
