"""The population scorer as the tests call it: a list of ``[L]``
traces lifted to the ``[T, L]`` stack ``score_population_multi`` takes,
and at T 1 the ``[P, 1, K]`` features squeezed to ``[P, K]``."""

import jax
import jax.numpy as jnp

from namazu_tpu.ops.schedule import TraceArrays, score_population_multi


def stack(*traces: TraceArrays) -> TraceArrays:
    """``[L]`` traces of one length (and one ``faultable`` presence)
    as one ``[T, L]`` stack."""
    return jax.tree.map(lambda *x: jnp.stack(x), *traces)


def score_one(delays, trace: TraceArrays, *args, **kwargs):
    """(fitness [P], features [P, K]) of a population against ONE
    trace: the scorer at T 1."""
    fitness, feats = score_population_multi(delays, stack(trace), *args,
                                            **kwargs)
    return fitness, feats[:, 0]
