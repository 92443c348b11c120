"""Ahead-of-time Mosaic/XLA:TPU compiles against a described v5e.

libtpu can describe a TPU topology without a chip, and a compile against
it runs the real Mosaic and XLA:TPU pipelines — so a Pallas kernel (or a
whole fused island step) the compiler refuses is caught here, in the CPU
sandbox, before chip time is spent on it. An AOT compile is not a run:
numerics, donation and the ICI ring are ``chip_smoke.py``'s to establish.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from namazu_tpu.models.ga import GAConfig, Population
from namazu_tpu.ops.schedule import ScoreWeights, TraceArrays
from namazu_tpu.parallel.islands import IslandState, make_fused_island_step

MOSAIC = "tpu_custom_call"


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a described v5e 2x2 host."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot describe it
        pytest.skip(f"libtpu cannot describe a v5e:2x2 topology: {e!r}")
    assert len(topo.devices) == 4
    return list(topo.devices)


@pytest.fixture
def tpu_paths(monkeypatch):
    """Trace the paths the chip takes: the ops pick Pallas + bf16 from
    ``jax.default_backend()``, which is "cpu" in this sandbox."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _on(mesh, shape, dtype=jnp.float32, spec=P()):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


# (P, A, F, K): the bench's width, the policy's default per-device
# width, the default width times 4 reference traces, a small one
KERNEL_SHAPES = [(8192, 1024, 64, 256), (4096, 512, 64, 256),
                 (16384, 512, 64, 256), (512, 512, 64, 64)]


@pytest.mark.parametrize("shape", KERNEL_SHAPES)
def test_pallas_kernels_compile_for_v5e(v5e, tpu_paths, shape):
    from namazu_tpu.ops.pallas_score import (
        min_sq_distance_pair_pallas,
        min_sq_distance_pallas,
    )

    p, a, f, k = shape
    mesh = Mesh(np.array(v5e[:1]), ("i",))
    feats, archive, failures = (_on(mesh, (p, k)), _on(mesh, (a, k)),
                                _on(mesh, (f, k)))
    single = min_sq_distance_pallas.lower(feats, archive).compile()
    assert MOSAIC in single.as_text()
    pair = min_sq_distance_pair_pallas.lower(
        feats, archive, failures).compile()
    assert MOSAIC in pair.as_text()


def _compile_fused(devices, weights=ScoreWeights(), max_fault=0.0,
                   L=256, T=4):
    """The fused island step at the policy's default width (population
    4096, H 256, K 256, archive 512 / failures 64, 16-generation
    chunks, migrate_k 8) on a mesh of ``devices``, as text."""
    pop, H, K, A, F, G = 4096, 256, 256, 512, 64, 16
    mesh = Mesh(np.array(devices), ("i",))
    fused = make_fused_island_step(
        mesh, GAConfig(max_delay=0.1, max_fault=max_fault), weights,
        migrate_k=8, generations=G)
    genomes = _on(mesh, (pop, H), spec=P("i"))
    state = IslandState(
        pop=Population(delays=genomes, faults=genomes),
        gen=_on(mesh, (), jnp.int32),
        best_fitness=_on(mesh, ()),
        best_delays=_on(mesh, (H,)),
        best_faults=_on(mesh, (H,)),
    )
    fault = max_fault > 0
    trace = TraceArrays(
        _on(mesh, (T, L), jnp.int32), _on(mesh, (T, L)),
        _on(mesh, (T, L), jnp.bool_),
        _on(mesh, (T, L), jnp.bool_) if fault else None)
    key = _on(mesh, (2,), jnp.uint32)
    compiled = fused.lower(
        state, key, trace, _on(mesh, (K, 2), jnp.int32),
        _on(mesh, (A, K)), _on(mesh, (F, K)),
        _on(mesh, (H,)) if fault else None, _on(mesh, ()), None,
    ).compile()
    return compiled.as_text()


@pytest.mark.parametrize("n_devices", [1, 4])
def test_fused_island_step_compiles_for_v5e(v5e, tpu_paths, n_devices):
    text = _compile_fused(v5e[:n_devices])
    assert MOSAIC in text  # the Pallas pair kernel, not XLA's stand-in
    if n_devices > 1:
        assert "collective-permute" in text  # the migration ring


def test_fused_island_step_variants_compile_for_v5e(v5e, tpu_paths):
    """The fault half of the genome and the blockwise long-trace path
    (L past LONG_TRACE_THRESHOLD) through the same compiler."""
    assert MOSAIC in _compile_fused(v5e[:1], max_fault=0.2)
    assert MOSAIC in _compile_fused(v5e[:1], L=2048, T=1)


@pytest.mark.slow
def test_fused_reorder_step_compiles_for_v5e(v5e, tpu_paths):
    """Reorder mode's sort + scatter under the population vmap."""
    weights = ScoreWeights(order_mode=True, order_gap=0.002,
                           order_window=0.05, tau=0.001, delay_cost=0.0)
    assert MOSAIC in _compile_fused(v5e[:1], weights=weights)


@pytest.mark.parametrize("L", [128, 1152], ids=["dense", "blockwise"])
def test_batched_embed_and_ring_scatter_compile_for_v5e(v5e, L):
    """Ingest's embed stage at the shipped width (H 256, K 256, chunks
    of 64 runs; every run of both benchmark configurations pads to L
    128) and the donated ring scatter behind it (archive 512, failures
    64)."""
    from namazu_tpu.models import search
    from namazu_tpu.ops.schedule import batched_trace_features

    mesh = Mesh(np.array(v5e[:1]), ("i",))
    C, H, K = search.EMBED_CHUNK, 256, 256
    embed = batched_trace_features(0.005, H).lower(
        _on(mesh, (C, L), jnp.int32), _on(mesh, (C, L)),
        _on(mesh, (C, L), jnp.bool_), _on(mesh, (K, 2), jnp.int32)
    ).compile()
    assert ("while" in embed.as_text()) == (L > 1024)  # the scan
    # the helper builds its jit on first use: one traced call on the
    # CPU, then the same function lowered for the chip
    A, F = 512, 64
    search._device_rows_scatter(
        jnp.zeros((A, K)), jnp.zeros((F, K)), jnp.zeros((C, K)),
        jnp.full((C,), A, jnp.int32), jnp.full((C,), F, jnp.int32))
    search._rows_scatter_jit.lower(
        _on(mesh, (A, K)), _on(mesh, (F, K)), _on(mesh, (C, K)),
        _on(mesh, (C,), jnp.int32), _on(mesh, (C,), jnp.int32)).compile()


def test_fused_island_step_compiles_at_the_zab5_shape(v5e, tpu_paths):
    """``zk2212-zab5``'s step: 4 reference traces of L 1536, so the
    blockwise scan (3 chunks of 512) under the trace and population
    vmaps, with the Pallas pair kernel over 4 x 4096 feature rows."""
    text = _compile_fused(v5e[:1], L=1536, T=4)
    assert MOSAIC in text
    assert "while" in text  # the scan survived as a loop


RERANK_SHAPES = {
    "dense": (1, 128, ScoreWeights()),
    "zab5_blockwise": (4, 1536, ScoreWeights()),
    "reorder": (4, 384, ScoreWeights(
        order_mode=True, order_gap=0.08, order_window=0.5, tau=0.04,
        delay_cost=0.0)),
}


@pytest.mark.parametrize("cell", sorted(RERANK_SHAPES))
def test_the_rerank_programs_compile_for_v5e(v5e, tpu_paths, cell):
    """The reply's re-rank at the shipped width, on ONE chip whatever
    the mesh (``ScheduleSearch._surrogate_pick``): the gather of a
    population sharded over the four chips, the scorer's compiled twin
    with the Pallas pair kernel in it, the pick, and the surrogate's
    fit over the archive's 512 rows (8 steps of 256)."""
    from namazu_tpu.models import surrogate
    from namazu_tpu.models.search import _gathered
    from namazu_tpu.ops import schedule

    T, L, weights = RERANK_SHAPES[cell]
    pop, H, K, A, F = 4096, 256, 256, 512, 64
    mesh = Mesh(np.array(v5e[:1]), ("i",))
    trace = TraceArrays(_on(mesh, (T, L), jnp.int32), _on(mesh, (T, L)),
                        _on(mesh, (T, L), jnp.bool_), None)
    text = schedule._score_population_multi_jit.lower(
        _on(mesh, (pop, H)), trace, _on(mesh, (K, 2), jnp.int32),
        _on(mesh, (A, K)), _on(mesh, (F, K)), weights, None, None,
        _on(mesh, ()), None, None).compile().as_text()
    assert MOSAIC in text
    assert ("while" in text) == (L > 1024)  # the blockwise scan
    four = Mesh(np.array(v5e), ("i",))
    assert "all-gather" in _gathered(four).lower(
        _on(four, (pop, H), spec=P("i"))).compile().as_text()
    model, tx, train, _predict, pick = surrogate._programs(128, 1e-3)
    state = jax.eval_shape(
        lambda: (lambda p: surrogate.SurrogateState(p, tx.init(p)))(
            model.init(jax.random.PRNGKey(0), jnp.zeros((1, K)))))
    state = jax.tree.map(lambda x: _on(mesh, x.shape, x.dtype), state)
    pick.lower(state.params, _on(mesh, (pop,)), _on(mesh, (pop, T, K)),
               _on(mesh, (pop, H)), _on(mesh, (pop, H)), k=16).compile()
    train.lower(state, _on(mesh, (A, K)), _on(mesh, (A,)),
                _on(mesh, (8, 256), jnp.int32)).compile()
