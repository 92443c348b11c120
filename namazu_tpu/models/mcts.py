"""Jittable Monte-Carlo tree search over schedule genomes (config 5).

The GA (models/ga.py) treats the genome as a flat vector; MCTS instead
*sequentialises* it: hint buckets are ordered by importance (frequency in
the reference traces), each tree level picks one of ``D`` quantised delay
levels for the next bucket, and leaf values come from batched random
rollouts (complete the remaining buckets uniformly, score the whole batch
with the same counterfactual-interleaving scorer the GA uses —
ops/schedule.py). The search therefore concentrates simulation budget on
the few buckets that actually flip precedence features, which is exactly
the regime where flat GA mutation wastes samples.

TPU-first design, in the style of DeepMind's mctx: the tree lives in
fixed-shape arrays (parent/children/visit/value), one simulation =
select (``lax.while_loop`` descent by normalised UCT) -> expand (one node)
-> rollout (``[R, H]`` delay matrix scored in one vmap/MXU batch) ->
backprop (``lax.while_loop`` up the parent chain), and the whole
``simulations``-iteration search is a single ``lax.fori_loop`` under
``jit``. No Python control flow touches the hot loop; root-parallel trees
across devices ride ``shard_map`` + ``all_gather`` like the GA islands.

The reference has no counterpart (its exploration is one random schedule
per wall-clock run, SURVEY.md §2.3/§2.9); this is the "MCTS variant"
called for by SURVEY.md §7 step 6 / BASELINE.json config 5.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from namazu_tpu.ops.schedule import (
    ScoreWeights,
    TraceArrays,
    normalize_fault_trace,
    replicated_trace_specs,
    score_population_multi,
)

NO_CHILD = jnp.int32(-1)


class MCTSConfig(NamedTuple):
    tree_depth: int = 24  # buckets decided by the tree (most important first)
    n_levels: int = 8  # quantised delay levels per bucket
    simulations: int = 256  # tree expansions per search call
    rollouts: int = 64  # random completions scored per leaf (one batch)
    c_uct: float = 1.25  # exploration constant (on [0,1]-normalised values)
    max_delay: float = 0.1  # seconds; level j = j/(D-1) * max_delay
    max_fault: float = 0.0  # rollout fault-probability cap (0 = off)


class Tree(NamedTuple):
    """Fixed-capacity search tree, N = simulations + 1 nodes."""

    parent: jax.Array  # i32[N]
    action: jax.Array  # i32[N] level chosen on the edge into this node
    depth: jax.Array  # i32[N] root = 0
    children: jax.Array  # i32[N, D], NO_CHILD where unexpanded
    visit: jax.Array  # f32[N]
    value_sum: jax.Array  # f32[N]
    n_nodes: jax.Array  # i32 scalar


class MCTSResult(NamedTuple):
    best_fitness: jax.Array  # f32 scalar
    best_delays: jax.Array  # f32[H]
    best_faults: jax.Array  # f32[H]
    tree_visits: jax.Array  # f32[N] (diagnostics: visit counts)
    root_child_visits: jax.Array  # f32[D] (diagnostics)


def init_tree(cfg: MCTSConfig) -> Tree:
    N, D = cfg.simulations + 1, cfg.n_levels
    return Tree(
        parent=jnp.full((N,), NO_CHILD),
        action=jnp.full((N,), NO_CHILD),
        depth=jnp.zeros((N,), jnp.int32),
        children=jnp.full((N, D), NO_CHILD),
        visit=jnp.zeros((N,), jnp.float32),
        value_sum=jnp.zeros((N,), jnp.float32),
        n_nodes=jnp.ones((), jnp.int32),  # node 0 = root
    )


def _ucb_scores(tree: Tree, node: jax.Array, vmin: jax.Array,
                vmax: jax.Array, c: float) -> jax.Array:
    """Normalised-UCT score per child slot; unexpanded slots get +inf so
    every action is tried once before any is revisited."""
    kids = tree.children[node]  # i32[D]
    safe = jnp.maximum(kids, 0)
    v = tree.visit[safe]
    q = tree.value_sum[safe] / jnp.maximum(v, 1.0)
    # until two distinct values exist (vmax==vmin, or still +-inf), all
    # visited children tie at 0.5 and exploration alone drives selection
    denom = vmax - vmin
    q01 = jnp.where(
        denom > 1e-9, (q - vmin) / jnp.maximum(denom, 1e-9), 0.5
    )
    q01 = jnp.where(jnp.isfinite(q01), q01, 0.5)
    explore = c * jnp.sqrt(jnp.log(tree.visit[node] + 1.0)
                           / jnp.maximum(v, 1.0))
    scored = q01 + explore
    return jnp.where(kids == NO_CHILD, jnp.inf, scored)


class _SearchCarry(NamedTuple):
    tree: Tree
    key: jax.Array
    vmin: jax.Array  # running min of rollout values (for UCT normalisation)
    vmax: jax.Array
    best_fitness: jax.Array
    best_delays: jax.Array
    best_faults: jax.Array


def _make_rollout(trace: TraceArrays, pairs, archive, failure_feats,
                  hint_order, level_values, H: int, cfg: MCTSConfig,
                  weights: ScoreWeights, coin=None, seeds=None):
    """Returns rollout(key, levels i32[tree_depth]) ->
    (mean_fitness, best_fitness, best_delays, best_faults).

    When ``cfg.max_fault > 0`` (and a fault ``coin`` is given), the random
    fault matrices participate in the counterfactual score — the returned
    best fault table is *selected*, not an unselected random draw.

    ``seeds f32[S, H]`` (S may be 0) are demonstration delay tables —
    recorded failures' injected delays, same source as the GA's
    population seeding: up to half of each rollout batch completes the
    unpinned buckets from a noise-perturbed seed instead of uniform
    noise, so leaf values reflect what the demonstrations reach from
    this tree prefix and the tree is steered toward them."""
    n_seeds = 0 if seeds is None else seeds.shape[0]
    n_seeded_rows = min(cfg.rollouts // 2, max(0, n_seeds * 4))

    def rollout(key, levels):
        kd, kf, ks = jax.random.split(key, 3)
        R = cfg.rollouts
        delays = jax.random.uniform(kd, (R, H), jnp.float32, 0.0,
                                    cfg.max_delay)
        if n_seeded_rows > 0:
            rep = jnp.tile(seeds, (-(-n_seeded_rows // n_seeds), 1))
            rep = rep[:n_seeded_rows]
            noise = jax.random.normal(ks, (n_seeded_rows, H)) * (
                0.05 * cfg.max_delay)
            delays = delays.at[:n_seeded_rows].set(
                jnp.clip(rep + noise, 0.0, cfg.max_delay))
        faults = jax.random.uniform(kf, (R, H), jnp.float32, 0.0,
                                    cfg.max_fault)
        # pin the tree-assigned buckets
        assigned = levels >= 0  # bool[tree_depth]
        val = level_values[jnp.maximum(levels, 0)]  # f32[tree_depth]
        pin_val = jnp.zeros((H,), jnp.float32).at[hint_order].set(val)
        pin_mask = jnp.zeros((H,), bool).at[hint_order].set(assigned)
        delays = jnp.where(pin_mask[None, :], pin_val[None, :], delays)
        score_faults = faults if (cfg.max_fault > 0 and coin is not None) \
            else None
        fitness, _ = score_population_multi(
            delays, trace, pairs, archive, failure_feats, weights,
            faults=score_faults, coin=coin,
        )  # f32[R]
        b = jnp.argmax(fitness)
        return fitness.mean(), fitness[b], delays[b], faults[b]

    return rollout


def mcts_search(
    key: jax.Array,
    trace: TraceArrays,  # stacked [T, L] arrays (see stack_traces)
    pairs: jax.Array,  # i32[K, 2]
    archive: jax.Array,  # f32[A, K]
    failure_feats: jax.Array,  # f32[F, K]
    hint_order: jax.Array,  # i32[tree_depth] bucket ids, important first
    H: int,
    cfg: MCTSConfig = MCTSConfig(),
    weights: ScoreWeights = ScoreWeights(),
    coin: jax.Array | None = None,  # f32[H] deterministic fault coin
    seeds: jax.Array | None = None,  # f32[S, H] demonstration tables
) -> MCTSResult:
    """Run one full MCTS; pure function of its inputs (jit-safe)."""
    if coin is None and cfg.max_fault > 0:
        # without the coin the rollout fault tables would be returned
        # unscored — the round-1 bug config 4 fixes. Guarded here (not
        # just in make_parallel_mcts) so every public entry enforces it.
        raise ValueError(
            "fault search is enabled (max_fault > 0) but no fault coin "
            "was passed; build one with trace_encoding.fault_coin(seed, H)"
        )
    D, Td = cfg.n_levels, cfg.tree_depth
    level_values = jnp.linspace(0.0, cfg.max_delay, D).astype(jnp.float32)
    rollout = _make_rollout(trace, pairs, archive, failure_feats,
                            hint_order, level_values, H, cfg, weights,
                            coin=coin, seeds=seeds)

    def simulate(i, carry: _SearchCarry) -> _SearchCarry:
        tree, key = carry.tree, carry.key
        key, ksel, kroll = jax.random.split(key, 3)

        # -- selection: descend by UCT until an unexpanded slot or max depth
        def sel_cond(s):
            _node, _levels, done, _act = s
            return ~done

        def sel_body(s):
            node, levels, _done, _act = s
            d = tree.depth[node]
            at_max = d >= Td

            def pick():
                scores = _ucb_scores(tree, node, carry.vmin, carry.vmax,
                                     cfg.c_uct)
                a = jnp.argmax(scores).astype(jnp.int32)
                child = tree.children[node, a]
                lv = levels.at[d].set(a)
                # child exists -> keep descending; else stop and expand
                nxt = jnp.where(child == NO_CHILD, node, child)
                return nxt, lv, child == NO_CHILD, a

            def stop():  # terminal leaf: rollout from here, no expansion
                return node, levels, jnp.bool_(True), NO_CHILD

            return jax.lax.cond(at_max, stop, pick)

        levels0 = jnp.full((Td,), NO_CHILD)
        node, levels, _done, act = jax.lax.while_loop(
            sel_cond, sel_body,
            (jnp.int32(0), levels0, jnp.bool_(False), NO_CHILD),
        )

        # -- expansion: allocate one node (skip when terminal, act < 0)
        expand = act >= 0
        new = tree.n_nodes
        safe_act = jnp.maximum(act, 0)
        tree = Tree(
            parent=tree.parent.at[new].set(
                jnp.where(expand, node, tree.parent[new])),
            action=tree.action.at[new].set(
                jnp.where(expand, act, tree.action[new])),
            depth=tree.depth.at[new].set(
                jnp.where(expand, tree.depth[node] + 1, tree.depth[new])),
            children=tree.children.at[node, safe_act].set(
                jnp.where(expand, new, tree.children[node, safe_act])),
            visit=tree.visit,
            value_sum=tree.value_sum,
            n_nodes=tree.n_nodes + expand.astype(jnp.int32),
        )
        leaf = jnp.where(expand, new, node)

        # -- rollout: batch of random completions under the pinned prefix
        mean_v, roll_fit, roll_d, roll_f = rollout(kroll, levels)

        # -- backprop to root
        def bp_cond(s):
            n, _t = s
            return n != NO_CHILD

        def bp_body(s):
            n, t = s
            t = Tree(
                parent=t.parent, action=t.action, depth=t.depth,
                children=t.children,
                visit=t.visit.at[n].add(1.0),
                value_sum=t.value_sum.at[n].add(mean_v),
                n_nodes=t.n_nodes,
            )
            return t.parent[n], t

        _, tree = jax.lax.while_loop(bp_cond, bp_body, (leaf, tree))

        improved = roll_fit > carry.best_fitness
        return _SearchCarry(
            tree=tree,
            key=key,
            vmin=jnp.minimum(carry.vmin, mean_v),
            vmax=jnp.maximum(carry.vmax, mean_v),
            best_fitness=jnp.where(improved, roll_fit, carry.best_fitness),
            best_delays=jnp.where(improved, roll_d, carry.best_delays),
            best_faults=jnp.where(improved, roll_f, carry.best_faults),
        )

    carry0 = _SearchCarry(
        tree=init_tree(cfg),
        key=key,
        vmin=jnp.full((), jnp.inf, jnp.float32),
        vmax=jnp.full((), -jnp.inf, jnp.float32),
        best_fitness=jnp.full((), -jnp.inf, jnp.float32),
        best_delays=jnp.zeros((H,), jnp.float32),
        best_faults=jnp.zeros((H,), jnp.float32),
    )
    out = jax.lax.fori_loop(0, cfg.simulations, simulate, carry0)
    return MCTSResult(
        best_fitness=out.best_fitness,
        best_delays=out.best_delays,
        best_faults=out.best_faults,
        tree_visits=out.tree.visit,
        root_child_visits=out.tree.visit[
            jnp.maximum(out.tree.children[0], 0)
        ] * (out.tree.children[0] != NO_CHILD),
    )


@functools.partial(jax.jit, static_argnames=("H", "cfg", "weights"))
def mcts_search_jit(key, trace, pairs, archive, failure_feats, hint_order,
                    H: int, cfg: MCTSConfig = MCTSConfig(),
                    weights: ScoreWeights = ScoreWeights(),
                    coin=None, seeds=None) -> MCTSResult:
    return mcts_search(key, trace, pairs, archive, failure_feats,
                       hint_order, H, cfg, weights, coin=coin,
                       seeds=seeds)


def make_parallel_mcts(mesh, H: int, cfg: MCTSConfig = MCTSConfig(),
                       weights: ScoreWeights = ScoreWeights()):
    """Root-parallel MCTS over a device mesh: each device grows an
    independent tree from a folded key (rollout batches keep the MXU busy
    per device), then the per-device bests are ``all_gather``-ed and the
    argmax is replicated — same collective shape as the GA islands'
    global-best agreement (parallel/islands.py). The key is folded with
    every mesh axis and the gather runs axis by axis."""
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)

    def _local(key, trace, pairs, archive, failure_feats, hint_order,
               coin, seeds):
        for ax in axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(ax))
        res = mcts_search(key, trace, pairs, archive, failure_feats,
                          hint_order, H, cfg, weights, coin=coin,
                          seeds=seeds)
        all_fit, all_d, all_f = (res.best_fitness, res.best_delays,
                                 res.best_faults)
        for ax in reversed(axes):
            all_fit = jax.lax.all_gather(all_fit, ax)
            all_d = jax.lax.all_gather(all_d, ax)
            all_f = jax.lax.all_gather(all_f, ax)
        all_fit = all_fit.reshape(-1)
        all_d = all_d.reshape(-1, all_d.shape[-1])
        all_f = all_f.reshape(-1, all_f.shape[-1])
        g = jnp.argmax(all_fit)
        return all_fit[g], all_d[g], all_f[g]

    def make_sharded(trace_spec):
        return jax.shard_map(
            _local,
            mesh=mesh,
            in_specs=(P(), trace_spec, P(), P(), P(), P(), P(), P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )

    fault_trace_spec, nofault_trace_spec = replicated_trace_specs()
    sharded_fault = make_sharded(fault_trace_spec)
    sharded_nofault = make_sharded(nofault_trace_spec)

    @jax.jit
    def run(key, trace: TraceArrays, pairs, archive, failure_feats,
            hint_order, coin=None, seeds=None):
        if trace.hint_ids.ndim == 1:
            trace = jax.tree.map(lambda x: x[None], trace)
        if seeds is None:  # static absence -> 0-row array, one code path
            seeds = jnp.zeros((0, H), jnp.float32)
        had_coin = coin is not None
        trace = normalize_fault_trace(trace, coin)
        if not had_coin:
            if cfg.max_fault > 0:
                # mcts_search would raise the same error, but only after
                # the ones-substitution below had masked it — check first
                raise ValueError(
                    "fault search is enabled (max_fault > 0) but no "
                    "fault coin was passed; build one with "
                    "trace_encoding.fault_coin(seed, H)"
                )
            # coin >= 1 never beats a fault probability in [0, 1]
            coin = jnp.ones((H,), jnp.float32)
            return sharded_nofault(key, trace, pairs, archive,
                                   failure_feats, hint_order, coin, seeds)
        return sharded_fault(key, trace, pairs, archive, failure_feats,
                             hint_order, coin, seeds)

    return run
