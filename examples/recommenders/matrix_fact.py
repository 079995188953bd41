#!/usr/bin/env python
"""Matrix-factorization recommender (reference example/recommenders).

The reference's demo1-MF trains user/item `Embedding` factors whose dot
product predicts ratings, through the legacy `FeedForward` estimator with
a custom RMSE metric (reference example/recommenders/matrix_fact.py:19-45,
demo1-MF.ipynb). Same capability here on a synthetic low-rank rating
matrix: two Embedding tables, an elementwise-product-and-sum score,
LinearRegressionOutput loss, FeedForward.fit with CustomMetric(RMSE), and
a multi-input NDArrayIter (user, item) -> rating.

    python examples/recommenders/matrix_fact.py --epochs 8
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def mf_symbol(num_users, num_items, factor):
    import mxnet_tpu as mx

    user = mx.sym.Variable("user")
    item = mx.sym.Variable("item")
    uemb = mx.sym.Embedding(user, input_dim=num_users, output_dim=factor,
                            name="user_embed")
    iemb = mx.sym.Embedding(item, input_dim=num_items, output_dim=factor,
                            name="item_embed")
    score = mx.sym.sum(uemb * iemb, axis=1, keepdims=True)
    score = mx.sym.Flatten(score)
    return mx.sym.LinearRegressionOutput(score, mx.sym.Variable("score"),
                                         name="lro")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--factor", type=int, default=8)
    p.add_argument("--users", type=int, default=50)
    p.add_argument("--items", type=int, default=40)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(0)
    # ground-truth low-rank ratings + noise
    U = rng.normal(0, 1, (args.users, args.factor)).astype(np.float32)
    V = rng.normal(0, 1, (args.items, args.factor)).astype(np.float32)
    users = rng.randint(0, args.users, 4096).astype(np.float32)
    items = rng.randint(0, args.items, 4096).astype(np.float32)
    ratings = ((U[users.astype(int)] * V[items.astype(int)]).sum(1)
               + rng.normal(0, 0.05, 4096)).astype(np.float32)

    n_train = 3584
    def make_iter(sl, shuffle=False):
        return mx.io.NDArrayIter(
            {"user": users[sl], "item": items[sl]},
            {"score": ratings[sl]}, batch_size=args.batch_size,
            shuffle=shuffle)

    def rmse(label, pred):
        return float(np.sqrt(((label.reshape(-1) - pred.reshape(-1)) ** 2)
                             .mean()))

    model = mx.model.FeedForward(
        symbol=mf_symbol(args.users, args.items, args.factor),
        num_epoch=args.epochs, optimizer="adam", learning_rate=0.02,
        initializer=mx.initializer.Normal(0.1))
    model.fit(X=make_iter(slice(0, n_train), shuffle=True),
              eval_data=make_iter(slice(n_train, None)),
              eval_metric=mx.metric.CustomMetric(rmse, name="rmse"))

    pred = model.predict(make_iter(slice(n_train, None)))
    err = rmse(ratings[n_train:][:len(pred)], np.asarray(pred))
    base = float(np.sqrt((ratings[n_train:] ** 2).mean()))
    print("matrix-fact test RMSE %.4f (predict-zero baseline %.4f)"
          % (err, base))
    assert err < 0.5 * base, (err, base)
    print("recommender OK")


if __name__ == "__main__":
    main()
