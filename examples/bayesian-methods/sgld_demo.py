#!/usr/bin/env python
"""Bayesian posterior sampling with SGLD (reference example/bayesian-methods).

The reference's bdk_demo runs stochastic-gradient Langevin dynamics —
`mx.optimizer.create('sgld')` plus a decaying step size — to draw
posterior samples on synthetic and MNIST problems, keeping a sample pool
for Bayesian model averaging (reference
example/bayesian-methods/bdk_demo.py:287-318, algos.py:152-210). This
example runs the CI-checkable version of that capability: SGLD over a
Bayesian linear-regression posterior whose exact Gaussian answer is known
in closed form, with minibatch gradients rescaled to the full-data
potential and the prior supplied as weight decay. The empirical mean and
covariance of the SGLD chain must match the analytic posterior.

    python examples/bayesian-methods/sgld_demo.py --iters 4000
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--iters", type=int, default=4000)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--n", type=int, default=512, help="dataset size")
    p.add_argument("--dim", type=int, default=3)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(3)
    np.random.seed(3)  # SGLD noise stream
    alpha, beta = 1.0, 4.0  # prior / noise precision
    w_true = rng.normal(0, 1, (args.dim,)).astype(np.float32)
    X = rng.normal(0, 1, (args.n, args.dim)).astype(np.float32)
    y = (X @ w_true + rng.normal(0, 1 / np.sqrt(beta), args.n)).astype(
        np.float32)

    # analytic Gaussian posterior: Sigma = (aI + b X'X)^-1, mu = b Sigma X'y
    Sigma = np.linalg.inv(alpha * np.eye(args.dim) + beta * X.T @ X)
    mu = beta * Sigma @ X.T @ y

    # SGLD chain: grad of the full-data negative log posterior, estimated
    # from minibatches (x N/B), prior via wd=alpha; step size decayed by
    # FactorScheduler toward the paper's polynomial schedule.
    opt = mx.optimizer.create(
        "sgld", learning_rate=5e-4, wd=alpha,
        rescale_grad=float(args.n) / args.batch_size,
        lr_scheduler=mx.lr_scheduler.FactorScheduler(step=1000, factor=0.7))
    w = mx.nd.zeros((args.dim,))
    samples = []
    burn = args.iters // 4
    for it in range(args.iters):
        idx = rng.randint(0, args.n, args.batch_size)
        xb, yb = mx.nd.array(X[idx]), mx.nd.array(y[idx])
        resid = mx.nd.dot(xb, w.reshape((args.dim, 1))).reshape(
            (args.batch_size,)) - yb
        grad = beta * mx.nd.dot(resid.reshape((1, args.batch_size)),
                                xb).reshape((args.dim,))
        opt.update(0, w, grad, None)
        if it >= burn:
            samples.append(w.asnumpy().copy())
    S = np.stack(samples)
    emp_mu, emp_cov = S.mean(0), np.cov(S.T)

    mu_err = float(np.abs(emp_mu - mu).max())
    sd_ratio = np.sqrt(np.diag(emp_cov)) / np.sqrt(np.diag(Sigma))
    print("SGLD chain (%d kept samples):" % len(S))
    print("  posterior mean  analytic %s  empirical %s  (max err %.4f)"
          % (np.round(mu, 3), np.round(emp_mu, 3), mu_err))
    print("  posterior sd ratio (empirical/analytic per dim): %s"
          % np.round(sd_ratio, 2))
    assert mu_err < 4 * float(np.sqrt(np.diag(Sigma)).max()), mu_err
    assert 0.5 < sd_ratio.min() and sd_ratio.max() < 2.5, sd_ratio
    print("sgld posterior OK")


if __name__ == "__main__":
    main()
