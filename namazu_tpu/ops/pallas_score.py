"""Pallas TPU kernel: fused archive-distance + min reduction.

``min_sq_distance`` (namazu_tpu/ops/schedule.py) is the scoring hot spot:
``d2[p,a] = |f_p|^2 + |a|^2 - 2 f_p.a`` followed by a min over ``a``. In
XLA the [P, A] distance matrix is materialized in HBM before the reduce;
at production sizes (P=8192, A=1024) that is 32 MB of HBM round-trip per
scoring call. This kernel tiles the matmul over (P, A) blocks on the MXU
and folds the min into the epilogue, so only the [P] result ever leaves
VMEM.

The kernel is numerically identical to the XLA path (f32 accumulation;
bf16 operands on TPU). ``min_sq_distance_auto`` dispatches: Pallas on TPU,
plain XLA elsewhere (tests run the kernel in interpret mode either way).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from namazu_tpu.ops import schedule as _sched

BIG = 3.4e38  # min-identity for f32


def _kernel(f_ref, a_ref, f2_ref, a2_ref, out_ref):
    """Grid (P/TP, A/TA). Block shapes: f [TP,K], a [TA,K], f2 [TP,1],
    a2 [TA,1] -> out [TP,1] running min across the A-tile axis."""
    j = pl.program_id(1)

    f = f_ref[:]
    a = a_ref[:]
    cross = jax.lax.dot_general(
        f, a, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [TP, TA]
    d2 = f2_ref[:] + a2_ref[:].reshape(1, -1) - 2.0 * cross
    m = jnp.min(d2, axis=1, keepdims=True)  # [TP, 1]

    @pl.when(j == 0)
    def _init():
        out_ref[:] = m

    @pl.when(j > 0)
    def _acc():
        out_ref[:] = jnp.minimum(out_ref[:], m)


@functools.partial(jax.jit, static_argnames=("tile_p", "tile_a", "interpret"))
def min_sq_distance_pallas(
    feats: jax.Array,  # [P, K] f32
    archive: jax.Array,  # [A, K] f32
    tile_p: int = 256,
    tile_a: int = 256,
    interpret: bool = False,
    valid_n: jax.Array | None = None,  # traced occupancy (None = all)
) -> jax.Array:
    P, K = feats.shape
    A = archive.shape[0]
    if A == 0:
        # min over zero rows is undefined and a zero-length grid axis
        # would leave the output unwritten; callers with a
        # not-yet-populated ring hold a fixed-capacity buffer and mask
        # with valid_n instead (the occupancy contract, ops/schedule.py)
        raise ValueError(
            "min_sq_distance_pallas: empty archive; use a "
            "fixed-capacity buffer with valid_n occupancy masking")
    # pad P and A up to tile multiples; padded archive rows use BIG norms
    # so they never win the min — rows past a ring's occupancy
    # (``valid_n``, a TRACED scalar so occupancy growth never recompiles)
    # are masked the same way
    Pp = -(-P // tile_p) * tile_p
    Ap = -(-A // tile_a) * tile_a
    f = jnp.pad(feats, ((0, Pp - P), (0, 0)))
    a = jnp.pad(archive, ((0, Ap - A), (0, 0)))
    f2 = jnp.sum(f * f, axis=1, keepdims=True)  # [Pp, 1]
    a2 = jnp.sum(a * a, axis=1)
    live = A if valid_n is None else jnp.minimum(valid_n, A)
    a2 = jnp.where(jnp.arange(Ap) < live, a2, BIG).reshape(Ap, 1)

    dt = _sched._matmul_dtype()
    f = f.astype(dt)
    a = a.astype(dt)

    grid = (Pp // tile_p, Ap // tile_a)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_p, K), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_a, K), lambda i, j: (j, 0)),
            pl.BlockSpec((tile_p, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_a, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tile_p, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Pp, 1), jnp.float32),
        interpret=interpret,
    )(f, a, f2, a2)
    return jnp.maximum(out[:P, 0], 0.0)


def min_sq_distance_auto(feats: jax.Array, archive: jax.Array,
                         valid_n: jax.Array | None = None) -> jax.Array:
    """Pallas on TPU, XLA elsewhere."""
    if jax.default_backend() == "tpu":
        return min_sq_distance_pallas(feats, archive, valid_n=valid_n)
    return _sched.min_sq_distance(feats, archive, valid_n=valid_n)


# -- fused pair distance: score epilogue of the fused search loop ----------


def _pair_kernel(na_tiles, f_ref, c_ref, f2_ref, c2_ref,
                 nov_ref, bug_ref):
    """Grid (P/TP, (Ap+Fp)/TA) over the CONCATENATED archive+failure
    buffer. Each feats tile is loaded once per column tile and streamed
    through whichever running min (novelty vs bug) the column tile
    belongs to — the segment boundary sits on a tile multiple by
    construction, so a tile never straddles both archives. One kernel
    launch scores both distances; neither [P, A] nor [P, F] ever leaves
    VMEM (the "pallas-fused score" half of score->select fusion; the
    select — argmax over the [P] fitness — is XLA's, inside the same
    jitted scan program)."""
    j = pl.program_id(1)

    f = f_ref[:]
    c = c_ref[:]
    cross = jax.lax.dot_general(
        f, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [TP, TA]
    d2 = f2_ref[:] + c2_ref[:].reshape(1, -1) - 2.0 * cross
    m = jnp.min(d2, axis=1, keepdims=True)  # [TP, 1]

    @pl.when(j == 0)
    def _init_nov():
        nov_ref[:] = m

    @pl.when((j > 0) & (j < na_tiles))
    def _acc_nov():
        nov_ref[:] = jnp.minimum(nov_ref[:], m)

    @pl.when(j == na_tiles)
    def _init_bug():
        bug_ref[:] = m

    @pl.when(j > na_tiles)
    def _acc_bug():
        bug_ref[:] = jnp.minimum(bug_ref[:], m)


@functools.partial(jax.jit, static_argnames=("tile_p", "tile_a", "interpret"))
def min_sq_distance_pair_pallas(
    feats: jax.Array,  # [P, K] f32
    archive: jax.Array,  # [A, K] f32
    failures: jax.Array,  # [F, K] f32
    tile_p: int = 256,
    tile_a: int = 256,
    interpret: bool = False,
    archive_n: jax.Array | None = None,  # traced occupancies
    failure_n: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(min d2 vs archive [P], min d2 vs failures [P]) in one pass.

    Both buffers pad to tile multiples (padded/over-occupancy rows carry
    BIG norms, never winning a min) and concatenate along the row axis;
    the kernel routes each column tile into the right running min by its
    static tile index. Numerically identical to two
    :func:`min_sq_distance_pallas` calls (same tile shapes, same f32
    accumulation) — the win is one launch and one feats read per column
    tile instead of two kernels with separate feats streams."""
    P, K = feats.shape
    A = archive.shape[0]
    F = failures.shape[0]
    if A == 0 or F == 0:
        # an empty segment would break the tile-index routing: with
        # na_tiles == 0 the j == 0 tile would initialize BOTH mins from
        # failures rows, and an empty failures segment would return
        # bug_ref unwritten. Empty-ring callers hold fixed-capacity
        # buffers and mask with archive_n/failure_n instead.
        raise ValueError(
            "min_sq_distance_pair_pallas: empty archive/failures; use "
            "fixed-capacity buffers with archive_n/failure_n occupancy "
            "masking")
    Pp = -(-P // tile_p) * tile_p
    Ap = -(-A // tile_a) * tile_a
    Fp = -(-F // tile_a) * tile_a
    f = jnp.pad(feats, ((0, Pp - P), (0, 0)))
    a = jnp.pad(archive, ((0, Ap - A), (0, 0)))
    fl = jnp.pad(failures, ((0, Fp - F), (0, 0)))
    f2 = jnp.sum(f * f, axis=1, keepdims=True)  # [Pp, 1]
    a2 = jnp.sum(a * a, axis=1)
    live_a = A if archive_n is None else jnp.minimum(archive_n, A)
    a2 = jnp.where(jnp.arange(Ap) < live_a, a2, BIG)
    fl2 = jnp.sum(fl * fl, axis=1)
    live_f = F if failure_n is None else jnp.minimum(failure_n, F)
    fl2 = jnp.where(jnp.arange(Fp) < live_f, fl2, BIG)
    cat = jnp.concatenate([a, fl])  # [Ap + Fp, K]
    cat2 = jnp.concatenate([a2, fl2]).reshape(Ap + Fp, 1)

    dt = _sched._matmul_dtype()
    f = f.astype(dt)
    cat = cat.astype(dt)

    na_tiles = Ap // tile_a
    grid = (Pp // tile_p, (Ap + Fp) // tile_a)
    nov, bug = pl.pallas_call(
        functools.partial(_pair_kernel, na_tiles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_p, K), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_a, K), lambda i, j: (j, 0)),
            pl.BlockSpec((tile_p, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_a, 1), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_p, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_p, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Pp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Pp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(f, cat, f2, cat2)
    return (jnp.maximum(nov[:P, 0], 0.0), jnp.maximum(bug[:P, 0], 0.0))


def min_sq_distance_pair_auto(
    feats: jax.Array, archive: jax.Array, failures: jax.Array,
    archive_n: jax.Array | None = None,
    failure_n: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Pallas pair kernel on TPU, two XLA mins elsewhere."""
    if jax.default_backend() == "tpu":
        return min_sq_distance_pair_pallas(
            feats, archive, failures,
            archive_n=archive_n, failure_n=failure_n)
    return (
        _sched.min_sq_distance(feats, archive, valid_n=archive_n),
        _sched.min_sq_distance(feats, failures, valid_n=failure_n),
    )
