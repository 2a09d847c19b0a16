"""REINFORCE policy gradient on a self-contained CartPole — the reference's
``example/reinforcement-learning`` family (parallel_actor_critic / dqn) in
its simplest policy-gradient form, with the environment implemented inline
(no gym dependency, same dynamics equations as the classic task).

What it exercises: a stochastic policy head sampled OUTSIDE autograd, the
log-prob trick (loss = -sum log pi(a|s) * return) recorded inside, reward
normalization, and episodic training where the episode length varies: the
update runs on the episode padded to the environment's 200-step cap with
return 0 on the padding (which adds 0 to the loss), so every recorded op
keeps one shape and compiles once instead of once per new length.

Reference parity: /root/reference/example/reinforcement-learning/
parallel_actor_critic/ (policy-gradient loss over episode returns).
"""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn


class CartPole:
    """Classic cart-pole dynamics (Barto-Sutton-Anderson), 200-step cap."""

    MAX_STEPS = 200

    def __init__(self, rng):
        self.rng = rng
        self.g, self.mc, self.mp, self.l = 9.8, 1.0, 0.1, 0.5
        self.dt, self.fmag = 0.02, 10.0
        self.reset()

    def reset(self):
        self.s = self.rng.uniform(-0.05, 0.05, 4)
        self.t = 0
        return self.s.copy()

    def step(self, action):
        x, xd, th, thd = self.s
        f = self.fmag if action == 1 else -self.fmag
        ct, st = np.cos(th), np.sin(th)
        mtot = self.mc + self.mp
        tmp = (f + self.mp * self.l * thd ** 2 * st) / mtot
        thacc = (self.g * st - ct * tmp) / (
            self.l * (4.0 / 3.0 - self.mp * ct ** 2 / mtot))
        xacc = tmp - self.mp * self.l * thacc * ct / mtot
        self.s = np.array([x + self.dt * xd, xd + self.dt * xacc,
                           th + self.dt * thd, thd + self.dt * thacc])
        self.t += 1
        done = (abs(self.s[0]) > 2.4 or abs(self.s[2]) > 0.21
                or self.t >= self.MAX_STEPS)
        return self.s.copy(), 1.0, done


def run_episode(env, net, rng):
    states, actions = [], []
    s = env.reset()
    done = False
    while not done:
        p = net(mx.nd.array(s.reshape(1, -1))).asnumpy().ravel()
        p = np.exp(p - p.max())
        p /= p.sum()
        a = int(rng.rand() < p[1])
        states.append(s)
        actions.append(a)
        s, _, done = env.step(a)
    return np.array(states, "float32"), np.array(actions), len(actions)


def train(episodes=120, gamma=0.99, lr=0.01, seed=0, verbose=True):
    """Returns (first_avg_len, last_avg_len) episode lengths (max 200)."""
    rng = np.random.RandomState(seed)
    mx.random.seed(seed)
    env = CartPole(rng)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(2))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": lr})
    lens = []
    for _ in range(episodes):
        states, actions, T = run_episode(env, net, rng)
        lens.append(T)
        # discounted returns, normalized
        rets = np.zeros(T, "float32")
        acc = 0.0
        for t in reversed(range(T)):
            acc = 1.0 + gamma * acc
            rets[t] = acc
        rets = (rets - rets.mean()) / (rets.std() + 1e-6)
        pad = env.MAX_STEPS - T
        states = np.pad(states, ((0, pad), (0, 0)))
        actions, rets = np.pad(actions, (0, pad)), np.pad(rets, (0, pad))
        with autograd.record():
            logits = net(mx.nd.array(states))
            logp = mx.nd.log_softmax(logits, axis=1)
            chosen = mx.nd.pick(logp, mx.nd.array(actions), axis=1)
            loss = -mx.nd.sum(chosen * mx.nd.array(rets))
        loss.backward()
        trainer.step(T)
    first = float(np.mean(lens[:20]))
    last = float(np.mean(lens[-20:]))
    if verbose:
        print(f"episode length: {first:.1f} -> {last:.1f}")
    return first, last


if __name__ == "__main__":
    train()
