"""Island-model GA over a device mesh.

Each device evolves an independent population shard ("island"); every step

* scores its local genomes (vmap -> VPU/MXU),
* evolves one GA generation locally,
* migrates its elite genomes (the leading rows after ``ga_generation``)
  to the next island along one or more ring axes (``ppermute`` — over ICI
  for the chip axis, over DCN for the host axis of a hybrid mesh),
  landing them in the neighbor's tail rows so the neighbor's own
  preserved elites are never overwritten,
* and agrees on the global best via ``all_gather`` (tiny: one genome per
  island).

Everything device-to-device rides XLA collectives; the host only sees the
replicated global best. This is the TPU-native replacement for the
reference's single-process random exploration (SURVEY.md section 2.9).

Two step shapes share one local-step body (same math, same PRNG draw
order — the bit-exactness contract tests/test_fused_loop.py pins):

* ``make_multiaxis_island_step`` — the per-generation step: one jitted
  dispatch per generation, host round trip between generations. The
  general form for hybrid host x chip meshes; ``make_island_step`` is
  its one-ring special case.
* ``make_fused_island_step`` — the whole generation loop device-side:
  ``lax.scan`` over G generations inside ONE jitted, shard_mapped,
  buffer-donated program. Population/best buffers never round-trip to
  the host between generations; the per-generation global-best history
  comes back as one f32[G] array so the host can log convergence
  without extra syncs (doc/performance.md "Fused search loop").

Migration cadence is decoupled from the generation count: each ring is
``(axis, k)`` or ``(axis, k, every)`` — the ring's ppermute only runs on
generations where ``gen % every == 0`` (``lax.cond``, predicate
replicated, so every device takes the same branch and a skipped
generation pays zero ICI/DCN bandwidth). ``every=1`` (the default) is
the pre-cadence behavior bit-for-bit.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

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


def _norm_rings(rings: Sequence[Tuple]) -> Tuple[Tuple[str, int, int], ...]:
    """Rings as ``(axis, k, every)``; 2-tuples get ``every=1``."""
    out = []
    for r in rings:
        if len(r) == 2:
            ax, k = r
            every = 1
        else:
            ax, k, every = r
        out.append((str(ax), int(k), max(1, int(every))))
    return tuple(out)


def _make_local_step(mesh: Mesh, cfg: GAConfig, weights: ScoreWeights,
                     rings: Sequence[Tuple]):
    """The per-device generation body shared by the per-generation and
    fused step factories: score -> local best -> GA generation ->
    ring migration -> global-best all_gather. ``gen`` (replicated i32)
    drives the per-ring migration cadence."""
    axes = tuple(mesh.axis_names)
    rings = _norm_rings(rings)

    def _local_step(key, gen, pop, trace, pairs, archive, failure_feats,
                    novelty_scale, mutation_bias, coin=None):
        # named scopes mark the per-phase op regions in any captured
        # device profile (xprof/perfetto) — the in-jit counterpart of the
        # host-side obs.search_phase timers (obs/spans.py): host timers
        # can only see the whole fused dispatch, these label its parts
        for ax in axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(ax))

        with jax.named_scope("nmz_score"):
            fitness, _feats = score_population_multi(
                pop.delays, trace, pairs, archive, failure_feats, weights,
                faults=None if coin is None else pop.faults, coin=coin,
                novelty_scale=novelty_scale,
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
        # and they land in the *tail* rows of the neighbor — successive
        # rings take successive tail slices, so elites are transported
        # verbatim and a later, thinner ring (e.g. DCN) never overwrites
        # an earlier ring's arrivals or the neighbor's preserved elites.
        rows = pop.delays.shape[0]
        n_elite = max(1, int(rows * cfg.elite_frac))
        offset = 0
        plan = []  # (axis, k, landing offset from the tail, every)
        for ax, k, every in rings:
            kk = min(k, max(0, rows - n_elite - offset))
            if mesh.shape[ax] > 1 and kk > 0:
                plan.append((ax, kk, offset, every))
                offset += kk
        with jax.named_scope("nmz_migrate"):
            for ax, kk, off, every in plan:
                n_ax = mesh.shape[ax]
                perm = [(j, (j + 1) % n_ax) for j in range(n_ax)]
                dst = rows - off - kk

                def _migrate(p, _ax=ax, _kk=kk, _perm=perm, _dst=dst):
                    mig_d = jax.lax.ppermute(p.delays[:_kk], _ax, _perm)
                    mig_f = jax.lax.ppermute(p.faults[:_kk], _ax, _perm)
                    return Population(
                        delays=p.delays.at[_dst:_dst + _kk].set(mig_d),
                        faults=p.faults.at[_dst:_dst + _kk].set(mig_f),
                    )

                if every > 1:
                    # gen is replicated, so every device takes the same
                    # branch and a skipped generation moves zero bytes
                    # over this ring's fabric
                    new_pop = jax.lax.cond(
                        gen % every == 0, _migrate, lambda p: p, new_pop)
                else:
                    new_pop = _migrate(new_pop)

        # replicated global best: gather one candidate per island, axis by
        # axis (innermost first, so ICI gathers before any DCN hop)
        with jax.named_scope("nmz_select"):
            all_fit, all_d, all_f = local_best_fit, local_best_d, local_best_f
            for ax in reversed(axes):
                all_fit = jax.lax.all_gather(all_fit, ax)
                all_d = jax.lax.all_gather(all_d, ax)
                all_f = jax.lax.all_gather(all_f, ax)
        all_fit = all_fit.reshape(-1)
        all_d = all_d.reshape(-1, all_d.shape[-1])
        all_f = all_f.reshape(-1, all_f.shape[-1])
        g = jnp.argmax(all_fit)
        return new_pop, all_fit[g], all_d[g], all_f[g]

    return _local_step, axes


def _pop_spec(axes) -> Population:
    return Population(delays=P(axes, None), faults=P(axes, None))


def _jit_donate_state(fn):
    """``jax.jit`` with the leading IslandState donated — the whole point
    of the fused step: population buffers are reused in place across the
    scan instead of allocating a fresh copy per call. One home so the
    donation contract (keep only the RETURNED state) is greppable."""
    return jax.jit(fn, donate_argnums=(0,))


def _prep_step_inputs(state: IslandState, trace: TraceArrays, coin,
                      novelty_scale, mutation_bias, cfg: GAConfig):
    """Input normalization shared by the per-generation and fused entry
    points — identical defaults keep the two paths bit-exact."""
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


def make_multiaxis_island_step(
    mesh: Mesh,
    cfg: GAConfig,
    weights: ScoreWeights = ScoreWeights(),
    rings: Sequence[Tuple] = (("i", 8),),
):
    """Build the jitted sharded step:
    (state, base_key, trace, pairs, archive, failure_feats) -> state.

    ``rings`` is a sequence of ``(mesh_axis, migrate_k)`` or
    ``(mesh_axis, migrate_k, every)``: each entry runs a ring over that
    axis migrating the island's *leading* rows of ``new_pop`` (elites
    first — ``ga_generation`` sorts them into the first ``n_elite``
    slots — then best-effort tournament offspring when
    ``migrate_k > n_elite``), on generations where ``gen % every == 0``.
    Migrants land in successive *tail* slices of the neighbor's
    population, so the neighbor's own preserved elites are never
    overwritten and a later, thinner ring (e.g. DCN) never clobbers an
    earlier ring's arrivals. Counts clamp so the landing region stays
    clear of the elite rows (shapes are static at trace time). The global
    best is gathered over every mesh axis and replicated.
    """
    _local_step, axes = _make_local_step(mesh, cfg, weights, rings)
    pop_spec = _pop_spec(axes)
    fault_trace_spec, nofault_trace_spec = replicated_trace_specs()

    def base_specs(trace_spec):
        return (
            P(),  # key
            P(),  # gen (replicated scalar; migration cadence)
            pop_spec,
            trace_spec,
            P(),  # pairs
            P(),  # archive
            P(),  # failure feats
            P(),  # novelty anneal scale (replicated scalar)
            P(),  # mutation bias f32[H] (replicated; guidance plane)
        )

    sharded_fault = jax.shard_map(
        _local_step,
        mesh=mesh,
        in_specs=base_specs(fault_trace_spec) + (P(),),  # + fault coin
        out_specs=(pop_spec, P(), P(), P()),
        check_vma=False,
    )
    sharded_nofault = jax.shard_map(
        _local_step,
        mesh=mesh,
        in_specs=base_specs(nofault_trace_spec),
        out_specs=(pop_spec, P(), P(), P()),
        check_vma=False,
    )

    @jax.jit
    def step(state: IslandState, base_key, trace: TraceArrays, pairs,
             archive, failure_feats, coin=None,
             novelty_scale=None, mutation_bias=None) -> IslandState:
        trace, novelty_scale, mutation_bias = _prep_step_inputs(
            state, trace, coin, novelty_scale, mutation_bias, cfg)
        key = jax.random.fold_in(base_key, state.gen)
        if coin is None:
            # static no-fault variant: the drop-mask/penalty branch is
            # never compiled into the hot loop when faults are off
            new_pop, fit, bd, bf = sharded_nofault(
                key, state.gen, state.pop, trace, pairs, archive,
                failure_feats, novelty_scale, mutation_bias
            )
        else:
            new_pop, fit, bd, bf = sharded_fault(
                key, state.gen, state.pop, trace, pairs, archive,
                failure_feats, novelty_scale, mutation_bias, coin
            )
        improved = fit > state.best_fitness
        return IslandState(
            pop=new_pop,
            gen=state.gen + 1,
            best_fitness=jnp.where(improved, fit, state.best_fitness),
            best_delays=jnp.where(improved, bd, state.best_delays),
            best_faults=jnp.where(improved, bf, state.best_faults),
        )

    return step


def make_fused_island_step(
    mesh: Mesh,
    cfg: GAConfig,
    weights: ScoreWeights = ScoreWeights(),
    rings: Sequence[Tuple] = (("i", 8),),
    generations: int = 16,
):
    """The whole generation loop in ONE device program:
    ``(state, base_key, trace, pairs, archive, failure_feats, ...) ->
    (state, fit_hist f32[generations])``.

    ``lax.scan`` steps the shared local-step body ``generations`` times
    inside one shard_mapped jit with the state pytree DONATED — the
    population, best-so-far, and generation buffers live on device for
    the scan's whole span and the input state's buffers are reused in
    place instead of round-tripping HBM->host->HBM per generation.
    ``fit_hist[g]`` is the replicated global-best fitness of generation
    ``state.gen + g`` (the per-generation convergence record the host
    would otherwise pay one sync each for).

    Bit-exactness contract (pinned by tests/test_fused_loop.py): the
    per-generation PRNG key is ``fold_in(base_key, gen)`` — the same
    fold the per-generation step applies — so N fused generations
    produce populations and fitness identical to N calls of
    ``make_multiaxis_island_step``'s step from the same state, the way
    ``ScheduledQueue.put_many`` keeps the sequential path's draw order.

    CAUTION: donation invalidates the caller's input state; keep only
    the returned state (models/search.py replaces ``self._state``).
    """
    if generations < 1:
        raise ValueError(f"generations must be >= 1, got {generations}")
    _local_step, axes = _make_local_step(mesh, cfg, weights, rings)
    pop_spec = _pop_spec(axes)
    fault_trace_spec, nofault_trace_spec = replicated_trace_specs()
    state_spec = IslandState(pop=pop_spec, gen=P(), best_fitness=P(),
                             best_delays=P(), best_faults=P())

    def _fused_local(state, base_key, trace, pairs, archive, failure_feats,
                     novelty_scale, mutation_bias, coin=None):
        def body(carry, i):
            pop, gen, bf, bd, bfa = carry
            key = jax.random.fold_in(base_key, gen)
            new_pop, fit, d, f = _local_step(
                key, gen, pop, trace, pairs, archive, failure_feats,
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

    @_jit_donate_state
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


def make_island_step(
    mesh: Mesh,
    cfg: GAConfig,
    weights: ScoreWeights = ScoreWeights(),
    migrate_k: int = 8,
    axis: str = "i",
    migrate_every: int = 1,
):
    """Flat single-axis island step: one elite ring over ``axis``,
    migrating every ``migrate_every`` generations."""
    return make_multiaxis_island_step(
        mesh, cfg, weights, rings=((axis, migrate_k, migrate_every),))
