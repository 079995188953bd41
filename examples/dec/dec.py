#!/usr/bin/env python
"""Deep Embedded Clustering (reference example/dec/dec.py).

The reference pretrains an autoencoder, initializes cluster centers with
k-means over the embeddings, then alternates: compute the Student-t soft
assignment q and the sharpened target p = q²/f (normalized), and train
encoder + centers against KL(p||q) — the loss implemented as a NumpyOp
(reference dec.py:29-63) with centers as a trainable weight
(`dec_mu`, dec.py:104). TPU-natively the whole DEC objective is
expressible in symbols — broadcast ops build the pairwise distances and
`MakeLoss` turns the KL expression into the training head (no host
callback in the hot loop); the centers stay a plain trainable Variable.
Cluster accuracy is checked against the known blob labels through the
Hungarian assignment, as the reference's cluster_acc does (dec.py:18-26).

    python examples/dec/dec.py --steps 80
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

LATENT = 4
K = 4  # clusters


def encoder(data):
    import mxnet_tpu as mx
    h = mx.sym.FullyConnected(data, num_hidden=32, name="enc1")
    h = mx.sym.Activation(h, act_type="relu")
    return mx.sym.FullyConnected(h, num_hidden=LATENT, name="enc2")


def ae_symbol():
    import mxnet_tpu as mx
    z = encoder(mx.sym.Variable("data"))
    h = mx.sym.Activation(z, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=32, name="dec1")
    h = mx.sym.Activation(h, act_type="relu")
    out = mx.sym.FullyConnected(h, num_hidden=16, name="dec2")
    return mx.sym.LinearRegressionOutput(
        out, mx.sym.Variable("recon_label"), name="recon")


def dec_symbol(alpha=1.0):
    """q_ij ∝ (1 + ||z_i − mu_j||²/α)^−(α+1)/2 (Student-t, reference
    dec.py:35-41), KL(p||q) as the MakeLoss head; outputs [loss, q]."""
    import mxnet_tpu as mx

    z = encoder(mx.sym.Variable("data"))                  # (N, L)
    # trainable centers; the *_weight suffix routes default init
    # (the reference names it dec_mu and dodges init by assigning
    # the k-means result directly, dec.py:104 — same as below)
    mu = mx.sym.Variable("dec_mu_weight", shape=(K, LATENT))
    zb = mx.sym.expand_dims(z, axis=1)                    # (N, 1, L)
    mub = mx.sym.Reshape(mu, shape=(1, K, LATENT))        # (1, K, L)
    d2 = mx.sym.sum(mx.sym.square(mx.sym.broadcast_sub(zb, mub)),
                    axis=2)                               # (N, K)
    qu = (1.0 + d2 / alpha) ** (-(alpha + 1.0) / 2.0)
    q = mx.sym.broadcast_div(qu, mx.sym.sum(qu, axis=1, keepdims=True))
    p = mx.sym.Variable("p")                              # target (N, K)
    kl = mx.sym.mean(mx.sym.sum(
        p * (mx.sym.log(p + 1e-10) - mx.sym.log(q + 1e-10)), axis=1))
    return mx.sym.Group([mx.sym.MakeLoss(kl, name="kl"),
                         mx.sym.BlockGrad(q, name="q")])


def kmeans(z, k, rng, iters=30, n_init=10):
    """Lloyd's with restarts, best inertia kept (the reference leans on
    sklearn KMeans(n_init=20), dec.py:102 — single-init k-means merges
    clusters often enough to matter)."""
    import numpy as np

    best, best_inertia = None, np.inf
    for _ in range(n_init):
        centers = z[rng.choice(len(z), k, replace=False)].copy()
        for _ in range(iters):
            d2 = ((z[:, None, :] - centers[None]) ** 2).sum(2)
            assign = d2.argmin(1)
            for j in range(k):
                pts = z[assign == j]
                if len(pts):
                    centers[j] = pts.mean(0)
        inertia = ((z - centers[assign]) ** 2).sum()
        if inertia < best_inertia:
            best, best_inertia = centers, inertia
    return best


def cluster_acc(pred, y):
    """Best one-to-one cluster↔label matching (reference dec.py:18-26)."""
    import numpy as np
    from scipy.optimize import linear_sum_assignment

    w = np.zeros((K, K))
    for c, t in zip(pred, y.astype(int)):
        w[int(c), t] += 1
    r, cidx = linear_sum_assignment(-w)
    return w[r, cidx].sum() / len(pred)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--update-interval", type=int, default=20)
    args = ap.parse_args()

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch, DataDesc

    rng = np.random.RandomState(0)
    n = 1024
    centers16 = rng.normal(0, 2.0, (K, 16)).astype(np.float32)
    y = rng.randint(0, K, n).astype(np.float32)
    x = (centers16[y.astype(int)]
         + rng.normal(0, 0.4, (n, 16))).astype(np.float32)

    # 1) autoencoder pretraining (reference setup(), dec.py:66-91)
    it = mx.io.NDArrayIter(x, x, batch_size=args.batch_size, shuffle=True,
                           label_name="recon_label")
    ae = mx.mod.Module(ae_symbol(), label_names=("recon_label",))
    ae.fit(it, num_epoch=12, optimizer="adam",
           optimizer_params={"learning_rate": 3e-3},
           initializer=mx.initializer.Xavier())
    ae_params, _ = ae.get_params()

    # 2) embed all data, k-means init of dec_mu (dec.py:102-104)
    dec = mx.mod.Module(dec_symbol(), data_names=("data", "p"),
                        label_names=())
    dec.bind(data_shapes=[DataDesc("data", (args.batch_size, 16)),
                          DataDesc("p", (args.batch_size, K))])
    dec.init_params(mx.initializer.Xavier())
    dec.set_params({k: v for k, v in ae_params.items()
                    if k.startswith("enc")}, {}, allow_missing=True)

    def embed_all():
        zs = []
        emb = mx.mod.Module(encoder(mx.sym.Variable("data")),
                            label_names=())
        emb.bind(data_shapes=[DataDesc("data", (args.batch_size, 16))],
                 for_training=False)
        params, _ = dec.get_params()
        emb.set_params({k: v for k, v in params.items()
                        if k.startswith("enc")}, {})
        for s in range(0, n, args.batch_size):
            xb = x[s:s + args.batch_size]
            if len(xb) < args.batch_size:
                break
            emb.forward(DataBatch(data=[mx.nd.array(xb)]), is_train=False)
            zs.append(emb.get_outputs()[0].asnumpy())
        return np.concatenate(zs)

    z0 = embed_all()
    dec.set_params({"dec_mu_weight": mx.nd.array(kmeans(z0, K, rng))}, {},
                   allow_missing=True)
    dec.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})

    # 3) DEC refinement: freeze target p every update_interval steps
    def soft_assign_all():
        qs = []
        for s in range(0, n, args.batch_size):
            xb = x[s:s + args.batch_size]
            if len(xb) < args.batch_size:
                break
            dec.forward(DataBatch(
                data=[mx.nd.array(xb),
                      mx.nd.zeros((args.batch_size, K))]), is_train=False)
            qs.append(dec.get_outputs()[1].asnumpy())
        return np.concatenate(qs)

    p_full = None
    losses = []
    m = (n // args.batch_size) * args.batch_size
    for step in range(args.steps):
        if step % args.update_interval == 0:
            q_full = soft_assign_all()
            w = q_full ** 2 / q_full.sum(0, keepdims=True)
            p_full = (w / w.sum(1, keepdims=True)).astype(np.float32)
        idx = rng.randint(0, m, args.batch_size)
        dec.forward_backward(DataBatch(
            data=[mx.nd.array(x[idx]), mx.nd.array(p_full[idx])]))
        dec.update()
        losses.append(float(dec.get_outputs()[0].asnumpy()))

    q_full = soft_assign_all()
    acc = cluster_acc(q_full.argmax(1), y[:m])
    print("dec: KL %.4f -> %.4f, cluster accuracy %.3f"
          % (np.mean(losses[:5]), np.mean(losses[-5:]), acc))
    assert acc > 0.85, acc
    print("dec OK")


if __name__ == "__main__":
    main()
