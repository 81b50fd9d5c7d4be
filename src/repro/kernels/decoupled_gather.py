"""Decoupled gather — the paper's template made EXPLICIT inside one kernel.

Where ``dataflow_matmul`` relies on Pallas's automatic grid pipelining,
this kernel writes the three template roles out by hand, one per §II
concept:

* **access stage**: at grid step *g* the kernel *issues* the async
  HBM→VMEM copies for the rows of group ``g+1`` (the paper's memory stage
  running ahead, "multiple outstanding requests pipelined into the memory
  subsystem");
* **FIFO channel**: a 2-slot VMEM ring buffer + per-slot DMA semaphores —
  the bounded BRAM queue between the stages (depth 2 = double buffering);
* **execute stage**: waits on *this* slot's semaphore and runs the compute
  on the resident rows while the next group is in flight.

Rows move in groups of one sublane tile (8 rows of 32-bit data), so each
grid step writes one whole ``(8, D)`` output tile.  A one-row DMA must
slice an untiled dimension on both sides, so the table is viewed as
``(R, 1, D)`` and the ring as ``(2, 8, 1, D)``.  The gather row index
comes from a scalar-prefetched index array (SMEM), so the address stream
is available ahead of the data stream — exactly the paper's SpMV
structure (index array drives the value fetch).

``fn`` is the per-row compute; the default (tanh scale) stands in for any
long-latency stage.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _make_kernel(fn, rows: int):
    def kernel(idx_ref, table_ref, o_ref, buf_ref, sem_ref):
        g = pl.program_id(0)
        slot = g % 2

        def copies(step, s):
            return [pltpu.make_async_copy(
                table_ref.at[idx_ref[step * rows + r]], buf_ref.at[s, r],
                sem_ref.at[s]) for r in range(rows)]

        # prime the pipeline: the first group's DMAs issued at step 0
        @pl.when(g == 0)
        def _prime():
            for c in copies(0, 0):
                c.start()

        # ACCESS stage: issue the next group's DMAs (runs ahead of compute)
        @pl.when(g + 1 < pl.num_programs(0))
        def _prefetch():
            for c in copies(g + 1, 1 - slot):
                c.start()

        # FIFO pop: wait for this slot's data
        for c in copies(g, slot):
            c.wait()

        # EXECUTE stage
        for r in range(rows):
            o_ref[pl.ds(r, 1), :] = jax.vmap(fn)(buf_ref[slot, r])

    return kernel


@functools.partial(jax.jit, static_argnames=("fn", "interpret"))
def decoupled_gather(
    idx: jax.Array,     # (N,) int32 row indices (the address stream)
    table: jax.Array,   # (R, D) rows in HBM
    *,
    fn=None,
    interpret: bool = False,
) -> jax.Array:
    """out[i] = fn(table[idx[i]]) with explicit access/execute decoupling."""
    if fn is None:
        fn = _default_row_fn
    N = idx.shape[0]
    D = table.shape[1]
    rows = 8 * 4 // table.dtype.itemsize  # one sublane tile of rows
    Np = -(-N // rows) * rows
    idx = jnp.pad(idx.astype(jnp.int32), (0, Np - N))
    out = pl.pallas_call(
        _make_kernel(fn, rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Np // rows,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((rows, D), lambda g, idx: (g, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, rows, 1, D), table.dtype),  # 2-slot FIFO
                pltpu.SemaphoreType.DMA((2,)),           # per-slot tokens
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Np, D), table.dtype),
        interpret=interpret,
    )(idx, table.reshape(table.shape[0], 1, D))
    return out[:N]


def _default_row_fn(row):
    return jnp.tanh(row * 2.0)


def decoupled_gather_ref(idx: jax.Array, table: jax.Array,
                         fn=None) -> jax.Array:
    """Pure-jnp oracle."""
    return jax.vmap(fn or _default_row_fn)(table[idx])


@functools.lru_cache(maxsize=None)
def _staged_gather(fn, backend):
    from repro.dataflow import dataflow_jit

    def gather_fn(idx, table):
        return jax.vmap(fn)(table[idx])

    return dataflow_jit(gather_fn, stream_argnums=(0,), backend=backend)


def decoupled_gather_staged(idx: jax.Array, table: jax.Array, *,
                            fn=None, backend: str = "sequential"
                            ) -> jax.Array:
    """The same decoupling, derived by the compiler driver instead of
    hand-written Pallas: ``repro.dataflow`` partitions the reference
    computation at the gather (Algorithm 1) and executes it on the chosen
    backend.  Portable fallback for hosts where the TPU kernel can't run;
    bit-identical to :func:`decoupled_gather_ref`.

    The driver wrapper is memoized per (fn, backend) so repeated calls
    skip retracing (``fn`` must therefore be a stable function object)."""
    return _staged_gather(fn or _default_row_fn, backend)(idx, table)
