"""Island-model GA over a device mesh.

Each device evolves an independent population shard ("island"); every
generation it

* scores its local genomes (vmap -> VPU/MXU),
* evolves one GA generation locally,
* migrates its elite genomes (the leading rows after ``ga_generation``)
  to the next island along the mesh's one axis (``ppermute`` over
  ICI), landing them in the neighbor's tail rows so the neighbor's own
  preserved elites are never overwritten,
* and agrees on the global best via ``all_gather`` (tiny: one genome per
  island).

Everything device-to-device rides XLA collectives; the host only sees the
replicated global best. This is the TPU-native replacement for the
reference's single-process random exploration (SURVEY.md section 2.9).

There is one step shape: ``make_fused_island_step`` runs G generations
device-side — ``lax.scan`` inside ONE jitted, shard_mapped,
buffer-donated program. Population/best buffers never round-trip to the
host between generations; the per-generation global-best history comes
back as one f32[G] array so the host can log convergence without extra
syncs (doc/performance.md "Fused search loop"). ``generations=1`` is
the per-generation dispatch, through the same code. The generation's
PRNG key is ``fold_in(base_key, gen)`` whatever the chunk length, so G
generations in one call equal G calls of one (the chunk-independence
contract tests/test_fused_loop.py pins).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from namazu_tpu.models.ga import GAConfig, Population, ga_generation, init_population
from namazu_tpu.ops.schedule import (
    ScoreWeights,
    TraceArrays,
    normalize_fault_trace,
    replicated_trace_specs,
    score_population_multi,
    trace_tables,
)


class IslandState(NamedTuple):
    pop: Population  # delays/faults f32[P, H], sharded over the mesh
    gen: jax.Array  # int32 scalar, replicated
    best_fitness: jax.Array  # f32 scalar, replicated
    best_delays: jax.Array  # f32[H], replicated
    best_faults: jax.Array  # f32[H], replicated


def init_island_state(key: jax.Array, P_total: int, H: int,
                      cfg: GAConfig) -> IslandState:
    pop = init_population(key, P_total, H, cfg)
    return IslandState(
        pop=pop,
        gen=jnp.zeros((), jnp.int32),
        best_fitness=jnp.full((), -jnp.inf, jnp.float32),
        best_delays=jnp.zeros((H,), jnp.float32),
        best_faults=jnp.zeros((H,), jnp.float32),
    )


def _make_local_step(mesh: Mesh, axis: str, cfg: GAConfig,
                     weights: ScoreWeights, migrate_k: int):
    """The per-device generation body: score -> local best -> GA
    generation -> ring migration -> global-best all_gather."""
    n_islands = mesh.shape[axis]

    def _local_step(key, pop, trace, tables, pairs, archive, failure_feats,
                    novelty_scale, mutation_bias, coin=None):
        # named scopes mark the per-phase op regions in any captured
        # device profile (xprof/perfetto) — the in-jit counterpart of the
        # host-side obs.search_phase timers (obs/spans.py): host timers
        # can only see the whole fused dispatch, these label its parts
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))

        with jax.named_scope("nmz_score"):
            fitness, _feats = score_population_multi(
                pop.delays, trace, pairs, archive, failure_feats, weights,
                faults=None if coin is None else pop.faults, coin=coin,
                novelty_scale=novelty_scale, tables=tables,
            )
        # local best before evolution (elites survive anyway)
        best_i = jnp.argmax(fitness)
        local_best_fit = fitness[best_i]
        local_best_d = pop.delays[best_i]
        local_best_f = pop.faults[best_i]

        with jax.named_scope("nmz_mutate"):
            new_pop = ga_generation(key, pop, fitness, cfg,
                                    delay_bias=mutation_bias)

        # Migration: after ga_generation the island's elites occupy rows
        # [0:n_elite) of new_pop (sorted best-first), so migrants are the
        # leading rows (elites, then offspring if migrate_k > n_elite),
        # and they land in the *tail* rows of the neighbor — elites are
        # transported verbatim and the neighbor's preserved elites are
        # never overwritten. The count clamps so the landing region
        # stays clear of the elite rows (shapes are static at trace
        # time).
        rows = pop.delays.shape[0]
        n_elite = max(1, int(rows * cfg.elite_frac))
        kk = min(migrate_k, max(0, rows - n_elite))
        with jax.named_scope("nmz_migrate"):
            if n_islands > 1 and kk > 0:
                perm = [(j, (j + 1) % n_islands) for j in range(n_islands)]
                dst = rows - kk
                mig_d = jax.lax.ppermute(new_pop.delays[:kk], axis, perm)
                mig_f = jax.lax.ppermute(new_pop.faults[:kk], axis, perm)
                new_pop = Population(
                    delays=new_pop.delays.at[dst:dst + kk].set(mig_d),
                    faults=new_pop.faults.at[dst:dst + kk].set(mig_f),
                )

        # replicated global best: gather one candidate per island
        with jax.named_scope("nmz_select"):
            all_fit = jax.lax.all_gather(local_best_fit, axis)
            all_d = jax.lax.all_gather(local_best_d, axis)
            all_f = jax.lax.all_gather(local_best_f, axis)
        all_fit = all_fit.reshape(-1)
        all_d = all_d.reshape(-1, all_d.shape[-1])
        all_f = all_f.reshape(-1, all_f.shape[-1])
        g = jnp.argmax(all_fit)
        return new_pop, all_fit[g], all_d[g], all_f[g]

    return _local_step


def _prep_step_inputs(state: IslandState, trace: TraceArrays, coin,
                      novelty_scale, mutation_bias, cfg: GAConfig):
    """Input normalization at the step's entry: a single trace becomes
    a batch of one, and the optional inputs take their neutral
    values."""
    if trace.hint_ids.ndim == 1:  # single trace -> batch of one
        trace = jax.tree.map(lambda x: x[None], trace)
    trace = normalize_fault_trace(trace, coin)
    if coin is None and cfg.max_fault > 0:
        # without the coin the fault half would evolve unscored —
        # exactly the round-1 bug config 4 exists to fix
        raise ValueError(
            "fault search is enabled (max_fault > 0) but no fault "
            "coin was passed to the island step; build one with "
            "trace_encoding.fault_coin(seed, H)"
        )
    if novelty_scale is None:
        novelty_scale = jnp.ones((), jnp.float32)
    else:
        novelty_scale = jnp.asarray(novelty_scale, jnp.float32)
    if mutation_bias is None:
        # all-ones bias == the unbiased kernel bit-for-bit (the
        # bernoulli threshold values are identical), so guidance-off
        # callers keep the pre-guidance populations exactly
        mutation_bias = jnp.ones(
            (state.pop.delays.shape[1],), jnp.float32)
    else:
        mutation_bias = jnp.asarray(mutation_bias, jnp.float32)
    return trace, novelty_scale, mutation_bias


def make_fused_island_step(
    mesh: Mesh,
    cfg: GAConfig,
    weights: ScoreWeights = ScoreWeights(),
    migrate_k: int = 8,
    generations: int = 16,
):
    """The whole generation loop in ONE device program:
    ``(state, base_key, trace, pairs, archive, failure_feats, ...) ->
    (state, fit_hist f32[generations])``.

    ``lax.scan`` steps the local-step body ``generations`` times
    inside one shard_mapped jit with the state pytree DONATED — the
    population, best-so-far, and generation buffers live on device for
    the scan's whole span and the input state's buffers are reused in
    place instead of round-tripping HBM->host->HBM per generation.
    ``fit_hist[g]`` is the replicated global-best fitness of generation
    ``state.gen + g`` (the per-generation convergence record the host
    would otherwise pay one sync each for). Every generation an
    island's ``migrate_k`` *leading* rows (elites first —
    ``ga_generation`` sorts them into the first ``n_elite`` slots —
    then tournament offspring when ``migrate_k > n_elite``) go to the
    next island of the mesh's ring.

    Chunk-independence contract (pinned by tests/test_fused_loop.py):
    the per-generation PRNG key is ``fold_in(base_key, gen)`` whatever
    ``generations`` is, so G generations in one call produce populations,
    best tables and ``fit_hist`` identical to G calls at
    ``generations=1`` from the same state, the way
    ``ScheduledQueue.put_many`` keeps the sequential path's draw order.

    CAUTION: donation invalidates the caller's input state; keep only
    the returned state (models/search.py replaces ``self._state``).
    """
    if generations < 1:
        raise ValueError(f"generations must be >= 1, got {generations}")
    if len(mesh.axis_names) != 1:
        raise ValueError(
            "the island step runs on a 1-D mesh (parallel.mesh.make_mesh)"
            f", got axes {tuple(mesh.axis_names)}")
    (axis,) = mesh.axis_names
    _local_step = _make_local_step(mesh, axis, cfg, weights, migrate_k)
    pop_spec = Population(delays=P(axis, None), faults=P(axis, None))
    fault_trace_spec, nofault_trace_spec = replicated_trace_specs()
    state_spec = IslandState(pop=pop_spec, gen=P(), best_fitness=P(),
                             best_delays=P(), best_faults=P())

    def _fused_local(state, base_key, trace, pairs, archive, failure_feats,
                     novelty_scale, mutation_bias, coin=None):
        # what the scorer needs of the traces and of no population:
        # once a dispatch, outside the generation loop
        with jax.named_scope("nmz_score"):
            tables = trace_tables(trace, state.pop.delays.shape[-1],
                                  weights, coin is not None)

        def body(carry, i):
            pop, gen, bf, bd, bfa = carry
            key = jax.random.fold_in(base_key, gen)
            new_pop, fit, d, f = _local_step(
                key, pop, trace, tables, pairs, archive, failure_feats,
                novelty_scale, mutation_bias,
                *(() if coin is None else (coin,)))
            improved = fit > bf
            carry = (new_pop, gen + 1,
                     jnp.where(improved, fit, bf),
                     jnp.where(improved, d, bd),
                     jnp.where(improved, f, bfa))
            return carry, fit

        init = (state.pop, state.gen, state.best_fitness,
                state.best_delays, state.best_faults)
        (pop, gen, bf, bd, bfa), fit_hist = jax.lax.scan(
            body, init, jnp.arange(generations, dtype=jnp.int32))
        return IslandState(pop=pop, gen=gen, best_fitness=bf,
                           best_delays=bd, best_faults=bfa), fit_hist

    def fused_specs(trace_spec, with_coin: bool):
        specs = (
            state_spec,
            P(),  # base key
            trace_spec,
            P(),  # pairs
            P(),  # archive
            P(),  # failure feats
            P(),  # novelty anneal scale
            P(),  # mutation bias
        )
        return specs + ((P(),) if with_coin else ())

    sharded_fault = jax.shard_map(
        _fused_local,
        mesh=mesh,
        in_specs=fused_specs(fault_trace_spec, True),
        out_specs=(state_spec, P()),
        check_vma=False,
    )
    sharded_nofault = jax.shard_map(
        _fused_local,
        mesh=mesh,
        in_specs=fused_specs(nofault_trace_spec, False),
        out_specs=(state_spec, P()),
        check_vma=False,
    )

    # the leading IslandState is donated: the population buffers are
    # reused in place across calls, not copied
    @functools.partial(jax.jit, donate_argnums=(0,))
    def fused(state: IslandState, base_key, trace: TraceArrays, pairs,
              archive, failure_feats, coin=None,
              novelty_scale=None, mutation_bias=None):
        trace, novelty_scale, mutation_bias = _prep_step_inputs(
            state, trace, coin, novelty_scale, mutation_bias, cfg)
        if coin is None:
            return sharded_nofault(state, base_key, trace, pairs, archive,
                                   failure_feats, novelty_scale,
                                   mutation_bias)
        return sharded_fault(state, base_key, trace, pairs, archive,
                             failure_feats, novelty_scale, mutation_bias,
                             coin)

    return fused
