"""The per-cycle mesh router update as a single Pallas kernel.

The netsim transition (:func:`repro.netsim_jax.sim._step_core`) is a
neighbor-local int32 update over the stacked ``(2, ny, nx, ...)`` FIFO
lattice — routing off the packed header word, round-robin arbitration,
the post-arbitration deliver gate, credit return and one merged stacked
buffer write.  This module runs that whole transition as ONE
``pl.pallas_call``: every ``SimState`` leaf is resident on-chip for the
duration of the launch, and a static ``cycles_per_call`` ``fori_loop``
executes several mesh cycles per launch, amortizing the dispatch the way
``ssd_scan.py`` chunks its recurrence.

Design notes:

* **Shared trace.** The kernel body calls ``_step_core(kernel_safe=True)``
  — the very same function the fused XLA path runs, with its remaining
  traced-index scatter/gather ops swapped for one-hot select/sum forms
  and its bool reshapes widened to int32 (both exact), which Mosaic
  lowers.  There is no second implementation of the router to
  drift; bit-identity is by construction and enforced by
  ``tests/test_router_kernel.py``.
* **State in place.** Every state leaf is passed through
  ``input_output_aliases``, so the launch updates the simulator state
  buffers in place — the Pallas analogue of the ``donate_argnums`` the
  jitted drivers already use.
* **Packing.** Pallas refs want >= 2-D arrays of one dtype: leaves are
  viewed as int32 (bools widen, exactly) and scalars / 1-D leaves get
  leading unit axes; each inner cycle unpacks the output refs to the
  original pytree, steps, and repacks into them.  The per-cycle
  ``done``/``drained`` outputs come back as ``(cycles_per_call, 1)``
  columns so the drivers keep exact per-cycle completion traces and
  drain fences across multi-cycle launches.
* **Backends.** ``interpret=None`` resolves through
  :mod:`repro.kernels.backend`: native Mosaic on TPU; interpret mode
  (same traced program through XLA) for CPU tests, which check
  correctness, not speed.
* **VMEM.** The launch is ungridded: every state leaf (input and
  aliased output) and the whole program sit in VMEM at once, under a
  scoped limit of the v5e's 128 MiB (:data:`VMEM_LIMIT_BYTES`).  The
  compiled kernel refuses a mesh whose (8, 128)-padded state cannot fit
  (:func:`vmem_bytes`, a lower bound: the compiler adds its spill slots)
  with a ``ValueError`` before lowering; 16x32 fits, 32x32 does not.
  Interpret mode runs any mesh.
* **Topologies.** The topology (mesh / torus / ring-mesh / multi-chip,
  :mod:`repro.mesh.topology`) lives in the hashable ``SimConfig`` closed
  over by the kernel body, so every topology the fused step supports runs
  in the kernel — compiled and interpret alike — with no changes here:
  the wrap connectivity is static slice+concat (``jnp.roll`` would not
  lower on Mosaic), the routing function is pure ``where`` arithmetic,
  and the boundary gate compares against ``broadcasted_iota`` columns.
  Cross-topology bit-identity is enforced by ``tests/test_topology.py``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.netsim_jax import sim as _sim
from .backend import resolve_interpret

__all__ = ["router_step_call", "vmem_bytes", "VMEM_LIMIT_BYTES"]

I32 = jnp.int32

# scoped-VMEM limit of one launch: all of a v5e TensorCore's 128 MiB
VMEM_LIMIT_BYTES = 128 * 2**20


def _tile_bytes(shape: Tuple[int, ...]) -> int:
    """Bytes of one packed int32 leaf in VMEM, its two minor dims padded
    to the (8, 128) tile."""
    *lead, sub, lane = shape
    return (int(np.prod(lead)) * -(-sub // 8) * 8 * -(-lane // 128) * 128
            * 4)


def _packed_shape(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """Pallas refs want >= 2 dims: scalars become (1, 1), 1-D (1, n)."""
    return ((1,) * (2 - len(shape)) + tuple(shape)) if len(shape) < 2 \
        else tuple(shape)


def _pack(leaf: jax.Array) -> jax.Array:
    return jnp.asarray(leaf, I32).reshape(_packed_shape(leaf.shape))


def _unpack(val: jax.Array, meta) -> jax.Array:
    shape, dtype = meta
    val = val.reshape(shape)
    return (val != 0) if np.issubdtype(dtype, np.bool_) else val


def vmem_bytes(prog, st, cycles_per_call: int) -> int:
    """Least VMEM bytes the compiled kernel needs: every state leaf twice
    (input and aliased output), the program once and the two per-cycle
    output columns, each padded to (8, 128) int32 tiles."""
    def total(tree):
        return sum(_tile_bytes(_packed_shape(l.shape))
                   for l in jax.tree_util.tree_leaves(tree))
    return (2 * total(st) + total(prog)
            + 2 * _tile_bytes((int(cycles_per_call), 1)))


def _router_kernel(cfg, st_def, st_metas, prog_def, prog_metas,
                   cycles_per_call: int, *refs):
    n_st, n_prog = len(st_metas), len(prog_metas)
    st_in = refs[:n_st]
    prog_in = refs[n_st:n_st + n_prog]
    st_out = refs[n_st + n_prog:n_st + n_prog + n_st]
    done_ref, drained_ref = refs[-2], refs[-1]

    prog = jax.tree_util.tree_unflatten(
        prog_def, [_unpack(r[...], m) for r, m in zip(prog_in, prog_metas)])

    C = cycles_per_call
    # 2-D iota (1-D iotas do not lower on Mosaic): row j of the (C, 1)
    # output columns belongs to inner cycle j
    row = jax.lax.broadcasted_iota(I32, (C, 1), 0)

    # the state stays in the output refs between inner cycles: carried
    # through the loop as vectors it spills (177 MB of VMEM at 16x32)
    for src, dst in zip(st_in, st_out):
        dst[...] = src[...]

    def body(j, carry):
        done, drained_v = carry
        st = jax.tree_util.tree_unflatten(
            st_def, [_unpack(r[...], m) for r, m in zip(st_out, st_metas)])
        st2, done_now = _sim._step_core(cfg, prog, st, kernel_safe=True)
        for ref, leaf in zip(st_out, jax.tree_util.tree_leaves(st2)):
            ref[...] = _pack(leaf)
        hit = row == j
        done = jnp.where(hit, done_now, done)
        drained_v = jnp.where(hit, _sim.drained(st2, prog).astype(I32),
                              drained_v)
        return done, drained_v

    done, drained_v = jax.lax.fori_loop(
        0, C, body, (jnp.zeros((C, 1), I32), jnp.zeros((C, 1), I32)))

    done_ref[...] = done
    drained_ref[...] = drained_v


def router_step_call(cfg, prog, st, cycles_per_call: int, *,
                     interpret: Optional[bool] = None):
    """Run ``cycles_per_call`` mesh cycles in one Pallas kernel launch.

    Returns ``(state', done, drained)``: ``done[j]`` is the completion
    count of inner cycle j and ``drained[j]`` the global drain fence
    *after* that cycle (int32 0/1), both shaped ``(cycles_per_call,)`` —
    exactly what ``cycles_per_call`` launches of the fused step would
    have produced.  ``interpret=None`` picks the right mode for the host
    (:mod:`repro.kernels.backend`).  Compiled, a launch whose
    :func:`vmem_bytes` exceed :data:`VMEM_LIMIT_BYTES` raises a
    ``ValueError`` before lowering; interpret mode has no such limit.
    """
    C = int(cycles_per_call)
    if C < 1:
        raise ValueError(f"cycles_per_call must be >= 1, got {C}")
    st_leaves, st_def = jax.tree_util.tree_flatten(st)
    prog_leaves, prog_def = jax.tree_util.tree_flatten(prog)
    st_metas = tuple((tuple(l.shape), l.dtype) for l in st_leaves)
    prog_metas = tuple((tuple(l.shape), l.dtype) for l in prog_leaves)
    packed_st = [_pack(l) for l in st_leaves]
    packed_prog = [_pack(l) for l in prog_leaves]

    interpret = resolve_interpret(interpret)
    need = vmem_bytes(prog, st, C)
    if not interpret and need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"the ungridded router kernel keeps every state leaf (in and "
            f"out) and the program in VMEM: a {cfg.nx}x{cfg.ny} mesh with "
            f"{prog_leaves[0].shape[-1]} program entries per tile needs at "
            f"least {need} bytes, over the {VMEM_LIMIT_BYTES}-byte limit; "
            f"use impl='fused' for this mesh")
    kernel = functools.partial(_router_kernel, cfg, st_def, st_metas,
                               prog_def, prog_metas, C)
    outs = pl.pallas_call(
        kernel,
        out_shape=([jax.ShapeDtypeStruct(p.shape, I32) for p in packed_st]
                   + [jax.ShapeDtypeStruct((C, 1), I32),
                      jax.ShapeDtypeStruct((C, 1), I32)]),
        # state updates in place: input i aliases output i (the kernel
        # copies every input to its output once up front and then works
        # on the outputs alone, so the aliasing is hazard-free)
        input_output_aliases={i: i for i in range(len(packed_st))},
        name="router_step",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*packed_st, *packed_prog)

    new_st = jax.tree_util.tree_unflatten(
        st_def, [_unpack(o, m) for o, m in zip(outs, st_metas)])
    return new_st, outs[-2][:, 0], outs[-1][:, 0]
