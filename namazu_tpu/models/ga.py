"""Genetic algorithm over schedule genomes — fully jittable.

One generation (tournament selection -> uniform crossover -> gaussian/flip
mutation -> elitism) is a pure function of (population, fitness, PRNG key),
so it vmaps/shard_maps cleanly: per-device islands evolve independently and
exchange elites over ICI (namazu_tpu/parallel/islands.py).

Genome layout: ``delays f32[P,H]`` in [0, max_delay], ``faults f32[P,H]``
in [0, max_fault].
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class GAConfig(NamedTuple):
    max_delay: float = 0.1  # seconds; genome delay range
    max_fault: float = 0.0  # per-hint fault probability cap (0 = off)
    tournament_size: int = 3
    crossover_rate: float = 0.6
    mutation_sigma: float = 0.01  # gaussian sigma on delays, seconds
    mutation_rate: float = 0.15  # per-gene mutation probability
    elite_frac: float = 0.0625  # top fraction copied through unchanged


class Population(NamedTuple):
    delays: jax.Array  # f32[P, H]
    faults: jax.Array  # f32[P, H]


def init_population(key: jax.Array, P: int, H: int,
                    cfg: GAConfig) -> Population:
    kd, kf = jax.random.split(key)
    delays = jax.random.uniform(kd, (P, H), jnp.float32, 0.0, cfg.max_delay)
    faults = jax.random.uniform(kf, (P, H), jnp.float32, 0.0, cfg.max_fault)
    return Population(delays, faults)


def tournament_select(key: jax.Array, fitness: jax.Array, n: int,
                      k: int) -> jax.Array:
    """n winners of size-k tournaments -> indices int32[n]."""
    P = fitness.shape[0]
    cand = jax.random.randint(key, (n, k), 0, P)
    return cand[jnp.arange(n), jnp.argmax(fitness[cand], axis=-1)]


def _uniform_crossover(key: jax.Array, a: jax.Array, b: jax.Array,
                       rate: float) -> jax.Array:
    km, kr = jax.random.split(key)
    do = jax.random.uniform(kr, (a.shape[0], 1)) < rate
    mask = jax.random.bernoulli(km, 0.5, a.shape)
    child = jnp.where(mask, a, b)
    return jnp.where(do, child, a)


def _mutate(key: jax.Array, x: jax.Array, sigma: float, rate: float,
            lo: float, hi: float, rate_scale=None) -> jax.Array:
    """``rate_scale`` (f32[H], optional) multiplies the per-gene
    mutation probability — the guidance plane's mutation bias
    (doc/search.md): buckets participating in uncovered/one-sided
    ordering relations mutate more often. ``None`` (and all-ones) is
    bit-identical to the unbiased kernel: ``bernoulli(p)`` is
    ``uniform < p`` either way, and the draw count is unchanged."""
    kn, km = jax.random.split(key)
    noise = jax.random.normal(kn, x.shape) * sigma
    p = rate if rate_scale is None \
        else jnp.clip(rate * rate_scale, 0.0, 1.0)
    mask = jax.random.bernoulli(km, p, x.shape)
    return jnp.clip(x + jnp.where(mask, noise, 0.0), lo, hi)


@functools.partial(jax.jit, static_argnames=("cfg",))
def ga_generation(key: jax.Array, pop: Population, fitness: jax.Array,
                  cfg: GAConfig, delay_bias=None) -> Population:
    """Evolve one generation. Elites (top elite_frac by fitness) survive
    unchanged in the first slots; the rest are tournament offspring.

    ``delay_bias`` (f32[H], optional) scales the DELAY half's per-gene
    mutation rate (clipped to [0, 1]) — coverage guidance concentrating
    perturbation on the buckets whose relations are untested. The fault
    half is untouched: fault flips change which events EXIST, not their
    order, so ordering-coverage bias has nothing to say about them.

    Draw-order contract (the search plane's analogue of
    ``ScheduledQueue.put_many``'s): one generation consumes exactly the
    splits/draws derived from its ``key``, and the per-generation key is
    always ``fold_in(base_key, gen)`` — whether generations run one
    jitted dispatch at a time or many in one ``lax.scan``
    (parallel/islands.py). That is what makes a search's result
    independent of its chunk length (tests/test_fused_loop.py)."""
    P, H = pop.delays.shape
    n_elite = max(1, int(P * cfg.elite_frac))
    ks = jax.random.split(key, 6)

    elite_idx = jax.lax.top_k(fitness, n_elite)[1]

    pa = tournament_select(ks[0], fitness, P, cfg.tournament_size)
    pb = tournament_select(ks[1], fitness, P, cfg.tournament_size)
    child_d = _uniform_crossover(ks[2], pop.delays[pa], pop.delays[pb],
                                 cfg.crossover_rate)
    child_f = _uniform_crossover(ks[2], pop.faults[pa], pop.faults[pb],
                                 cfg.crossover_rate)
    child_d = _mutate(ks[3], child_d, cfg.mutation_sigma, cfg.mutation_rate,
                      0.0, cfg.max_delay, rate_scale=delay_bias)
    child_f = _mutate(ks[4], child_f, cfg.mutation_sigma * 0.5,
                      cfg.mutation_rate, 0.0, cfg.max_fault)

    # overwrite the first n_elite children with the elites
    child_d = child_d.at[:n_elite].set(pop.delays[elite_idx])
    child_f = child_f.at[:n_elite].set(pop.faults[elite_idx])
    return Population(child_d, child_f)
