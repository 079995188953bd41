#!/usr/bin/env python
"""REINFORCE policy gradient — fully imperative training loop.

Analogue of the reference's example/reinforcement-learning family
(a3c/policy-gradient): no Module, no fit() — the agent interacts with
an environment step by step, and the update is pure imperative
autograd: ``attach_grad`` on the policy weights, roll out under
``autograd.record()``, ``backward()`` on the REINFORCE surrogate,
manual SGD. This is the API surface the estimator-style examples never
touch: dynamic episode lengths and a training signal (sampled actions,
returns) that only exists at Python level.

Environment: a 1-D corridor of length N. Start in the middle; +1 reward
at the right end, 0 at the left; episode ends at either end or after
max_steps. Optimal policy: always move right.

    python examples/reinforcement-learning/reinforce_gridworld.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


class Corridor:
    def __init__(self, n=7, max_steps=24):
        self.n = n
        self.max_steps = max_steps

    def reset(self):
        self.pos = self.n // 2
        self.t = 0
        return self.pos

    def step(self, action):           # 0 = left, 1 = right
        self.pos += 1 if action == 1 else -1
        self.t += 1
        if self.pos >= self.n - 1:
            return self.pos, 1.0, True
        if self.pos <= 0 or self.t >= self.max_steps:
            return self.pos, 0.0, True
        return self.pos, 0.0, False


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--episodes", type=int, default=150)
    p.add_argument("--lr", type=float, default=0.5)
    p.add_argument("--gamma", type=float, default=0.95)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    env = Corridor()
    rng = np.random.RandomState(args.seed)
    # linear policy over one-hot state: (n_states, 2) logits table
    w = mx.nd.array(rng.randn(env.n, 2).astype(np.float32) * 0.01)
    w.attach_grad()

    def softmax_np(z):
        e = np.exp(z - z.max())
        return e / e.sum()

    returns_hist = []
    for ep in range(args.episodes):
        states, actions, rewards = [], [], []
        s = env.reset()
        done = False
        w_np = w.asnumpy()      # one readback per episode, not per step
        while not done:
            probs = softmax_np(w_np[s])
            a = int(rng.rand() < probs[1])
            s2, r, done = env.step(a)
            states.append(s)
            actions.append(a)
            rewards.append(r)
            s = s2
        # discounted returns, normalized baseline
        G, g = [], 0.0
        for r in reversed(rewards):
            g = r + args.gamma * g
            G.append(g)
        G = np.asarray(G[::-1], np.float32)
        returns_hist.append(float(G[0]))
        adv = G - G.mean()
        if np.allclose(adv, 0):
            continue
        # imperative surrogate: -sum(adv_t * log pi(a_t | s_t))
        sv = mx.nd.array(np.asarray(states, np.float32))
        av = mx.nd.array(np.asarray(actions, np.float32))
        advv = mx.nd.array(adv)
        with autograd.record():
            logits = mx.nd.take(w, sv)                    # (T, 2)
            logp = mx.nd.log_softmax(logits, axis=-1)
            chosen = mx.nd.pick(logp, av, axis=1)
            loss = -mx.nd.sum(advv * chosen)
        loss.backward()
        w._data = w._data - args.lr * w.grad._data
        w.attach_grad()            # fresh grad buffer for the next episode
    early = np.mean(returns_hist[:20])
    late = np.mean(returns_hist[-20:])
    print("reinforce OK: mean return %.3f -> %.3f over %d episodes"
          % (early, late, args.episodes))
    assert late > max(0.5, early + 0.1), (early, late)


if __name__ == "__main__":
    main()
