"""Pallas gather-of-versions for the ParameterDB delta ring buffer.

The JAX backend (pdb/jax_backend.py) keeps the last ``delta + 1``
parameter versions stacked along a leading axis.  Reading the version at
admissible delay ``d`` is one row-gather ``hist[(ptr - d) % size]`` —
but done leaf-by-leaf (the historical path) it lowers to one
dynamic-slice DMA per pytree leaf, dozens per step for the zoo models.

Here the row index arrives through scalar prefetch
(``PrefetchScalarGridSpec``) and the kernel issues ONE HBM-to-HBM DMA of
that row of one *packed* (size, R, 128) buffer — one kernel launch per
parameter group, regardless of how many leaves the group holds.  Both
operands stay in HBM (``pl.ANY``), so no VMEM block shape constrains the
row: a ring of a few rows and ~10^8 lanes is as legal as a small one.

The packed layout (leaves grouped by (delay, dtype), flattened and
concatenated, padded to whole (8, 128) tiles and stored as (R, 128)
rows) is built once at engine init by pdb/jax_backend.py; values
round-trip bit-exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(idx_ref, hist_ref, out_ref, sem):
    copy = pltpu.make_async_copy(hist_ref.at[idx_ref[0]], out_ref, sem)
    copy.start()
    copy.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def ring_gather(hist: jnp.ndarray, idx: jnp.ndarray,
                interpret: bool = False) -> jnp.ndarray:
    """hist: (size, R, C); idx: scalar int32 in [0, size) -> hist[idx]
    (R, C).  The ring axis must lie outside the two minor dims the TPU
    tiles in HBM, or the row would be a slice across tiles."""
    idx = jnp.asarray(idx, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(hist.shape[1:], hist.dtype),
        interpret=interpret,
    )(idx, hist)
