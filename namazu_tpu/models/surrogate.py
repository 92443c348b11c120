"""Learned reward surrogate: predict bug-reproduction probability from
schedule features.

The experiment oracle (validate script) is binary and costs a whole
wall-clock run (SURVEY.md section 7, "reward sparsity"). This small flax
MLP is trained online on (features, reproduced?) pairs from executed runs
and provides a dense score used to re-rank GA elites before paying for
real replays — the "learned surrogate" of BASELINE.json config 5.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn


class SurrogateMLP(nn.Module):
    hidden: int = 128

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(self.hidden)(x)
        x = nn.relu(x)
        x = nn.Dense(self.hidden // 2)(x)
        x = nn.relu(x)
        return nn.Dense(1)(x)[..., 0]  # logits


class SurrogateState(NamedTuple):
    params: dict
    opt_state: optax.OptState


@functools.partial(jax.jit, static_argnames=("k",))
def top_rows(fitness, feats, delays, faults, k: int):
    """A scored population's fitness top-k, best first, on the device:
    features averaged over the T traces [k, K], tables, faults, fitness."""
    top = jnp.argsort(-fitness)[:k]
    return feats[top].mean(axis=1), delays[top], faults[top], fitness[top]


@functools.lru_cache(maxsize=None)
def _programs(hidden: int, lr: float):
    """``(model, optimiser, train, predict, pick)`` of one ``(hidden,
    lr)``: a process's surrogates share ONE compiled program per shape."""
    model, tx = SurrogateMLP(hidden=hidden), optax.adam(lr)

    def loss_fn(params, feats, labels, weight):
        per = optax.sigmoid_binary_cross_entropy(
            model.apply(params, feats), labels)
        # mean over the REAL rows: a partial batch's padding has weight 0
        return (per * weight).sum() / jnp.maximum(weight.sum(), 1.0)

    def train(state: SurrogateState, feats, labels, idx):
        """(state, last real step's loss) after one scan of minibatch
        steps over rows ``idx`` i32[S, B] (-1 = padding) of ``feats``."""
        def step(carry, rows):
            state = carry[0]
            weight = (rows >= 0).astype(jnp.float32)
            rows = jnp.maximum(rows, 0)
            loss, grads = jax.value_and_grad(loss_fn)(
                state.params, feats[rows] * weight[:, None],
                labels[rows] * weight, weight)
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            new = SurrogateState(
                optax.apply_updates(state.params, updates), opt_state)
            # a step of padding keeps the WHOLE old state: on a zero
            # gradient Adam still decays, counts and moves the parameters
            return jax.tree.map(
                lambda a, b: jnp.where(weight.sum() > 0, a, b),
                (new, loss), carry), None

        return jax.lax.scan(step, (state, jnp.zeros(())), idx)[0]

    def predict(params, feats):
        return jax.nn.sigmoid(model.apply(params, feats))

    def pick(params, fitness, feats, delays, faults, k: int):
        cand, *rows = top_rows(fitness, feats, delays, faults, k)
        winner = jnp.argmax(predict(params, cand))
        return tuple(x[winner] for x in rows)

    return (model, tx, jax.jit(train), jax.jit(predict),
            jax.jit(pick, static_argnames=("k",)))


class RewardSurrogate:
    def __init__(self, K: int, hidden: int = 128, lr: float = 1e-3,
                 seed: int = 0):
        (self.model, self.tx, self._train, self._predict,
         self._pick) = _programs(hidden, lr)
        params = self.model.init(jax.random.PRNGKey(seed), jnp.zeros((1, K)))
        self.state = SurrogateState(params, self.tx.init(params))

    def train(self, feats: np.ndarray, labels: np.ndarray,
              epochs: int = 1, batch: int = 256, seed: int = 0,
              capacity: int = 0) -> jax.Array:
        """Fit (feats [N, K], labels [N] in {0, 1}) in ONE compiled call;
        the last loss stays on the device (nothing waits for it). Per
        epoch the minibatches of ``RandomState(seed).permutation``, their
        count padded to a power of two holding ``capacity`` rows."""
        n = len(feats)
        steps = 1 << max(-(-max(n, capacity) // batch) - 1, 0).bit_length()
        pad = steps * batch - n
        idx = np.full((epochs, n + pad), -1, np.int32)
        rng = np.random.RandomState(seed)
        for e in range(epochs):
            idx[e, :n] = rng.permutation(n)
        self.state, loss = self._train(
            self.state, np.pad(feats, ((0, pad), (0, 0))),
            np.pad(labels, (0, pad)), idx.reshape(epochs * steps, batch))
        return loss

    def predict(self, feats: np.ndarray) -> np.ndarray:
        """P(reproduce bug) per feature vector."""
        return np.asarray(self._predict(self.state.params, feats))

    def pick(self, fitness, feats, delays, faults, k: int):
        """``(table, faults, fitness)`` of the likeliest top-k row."""
        return self._pick(self.state.params, fitness, feats, delays,
                          faults, k=k)
