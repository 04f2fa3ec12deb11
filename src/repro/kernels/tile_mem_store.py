"""The fused step's tile-memory store as a Pallas TPU kernel that writes
in place.

A tile memory stored in rows (``(..., rows, lanes)``, see
:func:`repro.netsim_jax.sim.mem_shape`) takes at most one store per
tile and cycle.  An XLA scatter of one word per tile writes every tile,
the current word where nothing is stored, and on a TPU its cost grows
with the whole memory it updates.  This kernel leaves the memory in
HBM (``memory_space=pl.ANY``), aliased input to output, and touches only
the tiles that store: for each, it copies the tile's addressed row into
VMEM, sets the one word, and copies the row back.  Mosaic copies no
slice narrower than the 128-lane tiling, so a row of ``lanes`` words is
the least it can write.

* **Grid.** The per-tile rows, lanes and values come in SMEM blocks of
  :data:`CHUNK` tiles, one grid step each, so SMEM holds one block at a
  time whatever the batch.  Within a step the copies of all storing
  tiles are started before any is waited on: fetch, wait, set and
  write back, wait.  A tile's stores land in its own rows, so no two
  copies of a step overlap.
* **Batching.** ``vmap`` over :func:`tile_mem_store` folds the batch
  into the tiles (``jax.custom_batching.custom_vmap``): a sweep's chunk
  of points is one launch over all their tiles, not one per point.
* **Backends.** ``interpret=None`` resolves through
  :mod:`repro.kernels.backend`; interpret mode runs the TPU interpreter
  (``pltpu.InterpretParams``), which models the copies and semaphores,
  for CPU tests.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

__all__ = ["tile_mem_store", "CHUNK"]

I32 = jnp.int32

# most tiles per grid step: one SMEM block of each per-tile operand, and
# one VMEM row buffer of as many rows.  Past one block it must be a
# multiple of 1,024: XLA lays a 1-D int32 operand out in tiles of 1,024
# words, and Mosaic refuses a block that cuts them.
CHUNK = 1024
# tiles the scalar pass over a block looks at per loop iteration
UNROLL = 8


def _kernel(row_ref, lane_ref, val_ref, mem_in, mem_out, buf, slot_ref,
            sems):
    """One block of tiles: every tile with a row (``row >= 0``) has word
    ``lane`` of memory row ``row`` set to ``val``."""
    del mem_in                      # the same buffer as mem_out

    def copy(k, row, back):
        """Row ``row`` of the memory from or back to buffer row ``k``."""
        if back:
            return pltpu.make_async_copy(buf.at[pl.ds(k, 1)],
                                         mem_out.at[pl.ds(row, 1)], sems.at[1])
        return pltpu.make_async_copy(mem_out.at[pl.ds(row, 1)],
                                     buf.at[pl.ds(k, 1)], sems.at[0])

    unroll = math.gcd(UNROLL, row_ref.shape[0])

    def fetch(i, n):
        for u in range(unroll):
            j = i * unroll + u
            row = row_ref[j]

            @pl.when(row >= 0)
            def _():
                slot_ref[n] = j
                copy(n, row, back=False).start()
            n = n + (row >= 0).astype(I32)
        return n
    n = lax.fori_loop(0, row_ref.shape[0] // unroll, fetch,
                      jnp.asarray(0, I32))

    # every copy has one row's bytes, so any descriptor of the same
    # shape waits for one of them
    def wait(back):
        def body(k, c):
            copy(0, 0, back).wait()
            return c
        lax.fori_loop(0, n, body, 0)
    wait(back=False)

    lane = lax.broadcasted_iota(I32, (1, buf.shape[1]), 1)

    def put(k, c):
        j = slot_ref[k]
        buf[pl.ds(k, 1), :] = jnp.where(lane == lane_ref[j], val_ref[j],
                                        buf[pl.ds(k, 1), :])
        copy(k, row_ref[j], back=True).start()
        return c
    lax.fori_loop(0, n, put, 0)
    wait(back=True)


def _store_rows(mem2d: jax.Array, row: jax.Array, lane: jax.Array,
                val: jax.Array, interpret: bool) -> jax.Array:
    """``mem2d[row[i], lane[i]] = val[i]`` for every ``row[i] >= 0``, in
    place; rows are distinct."""
    chunk = min(CHUNK, row.shape[0])
    pad = -row.shape[0] % chunk
    row = jnp.pad(row, (0, pad), constant_values=-1)
    lane, val = jnp.pad(lane, (0, pad)), jnp.pad(val, (0, pad))

    def block():
        return pl.BlockSpec((chunk,), lambda i: (i,),
                            memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _kernel,
        grid=(row.shape[0] // chunk,),
        in_specs=[block(), block(), block(),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(mem2d.shape, mem2d.dtype),
        scratch_shapes=[pltpu.VMEM((chunk, mem2d.shape[1]), mem2d.dtype),
                        pltpu.SMEM((chunk,), I32),
                        pltpu.SemaphoreType.DMA((2,))],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="tile_mem_store",
    )(row, lane, val, mem2d)


@functools.lru_cache(maxsize=None)
def _store_fn(interpret: bool):
    """The batched-by-folding store, one per interpret mode."""

    @jax.custom_batching.custom_vmap
    def store(mem, addr, val, write):
        *_, rows, lanes = mem.shape
        addr, val, write = (x.reshape(-1) for x in (addr, val, write))
        tile = jnp.arange(addr.shape[0], dtype=I32)
        row = jnp.where(write, tile * rows + addr // lanes, -1)
        out = _store_rows(mem.reshape(-1, lanes), row, addr % lanes, val,
                          interpret)
        return out.reshape(mem.shape)

    @store.def_vmap
    def _fold(axis_size, in_batched, mem, addr, val, write):
        # a batch of memories is more tiles of one memory
        args = [x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, b in zip((mem, addr, val, write), in_batched)]
        out = store(*(x.reshape((-1,) + x.shape[2:]) for x in args))
        return out.reshape(args[0].shape), True
    return store


def tile_mem_store(mem: jax.Array, addr: jax.Array, val: jax.Array,
                   write: jax.Array,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``mem`` with word ``addr`` of each tile set to ``val`` where
    ``write``, every other word as it was.

    ``mem`` is ``tiles + (rows, lanes)`` int32, each tile's words in row
    order; ``addr``, ``val`` and ``write`` are shaped ``tiles``.  Under
    ``vmap`` the batch folds into the tiles, so a batch of memories is
    one launch.  ``interpret=None`` runs the compiled kernel on a TPU and
    the TPU interpreter elsewhere."""
    return _store_fn(resolve_interpret(interpret))(
        mem, jnp.asarray(addr, I32), jnp.asarray(val, I32),
        jnp.asarray(write, bool))
