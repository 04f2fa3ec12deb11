"""The batched, block-streamed execution engine behind the sim service.

A :class:`BatchRunner` owns one formed batch: stacked injection programs,
a vmapped stack of simulator states, and the static fence-block schedule
(:func:`repro.netsim_jax.measure.phase_schedule`).  Each ``advance()``
executes ONE jitted vmapped ``simulate`` block for the whole batch —
that is the "one vmapped call per bucket per tick" contract — and emits
one :class:`~repro.netsim_jax.measure.StreamChunk` per lane from the
host-side counter deltas.  ``finalize()`` reduces the phase-boundary
snapshots through a jitted vmapped
:func:`~repro.netsim_jax.measure.reduce_window_stats`, which keeps every
:class:`PhaseStats` field bit-identical to the one-shot
:func:`~repro.netsim_jax.measure.phased_stats` program (the reduce must
run under ``jit`` — eager jnp arithmetic rounds division differently
than the XLA-optimized trace).

Compile accounting mirrors :mod:`repro.dse.runner`'s
``_EXECUTED_SHAPES`` registry: the module-level jit caches plus the
executed-shape sets distinguish a genuinely fresh XLA compilation from a
cache hit, so a *second* service instance in the same process reports 0
compiles, and the service's headline "N same-shape requests compile
once" claim is asserted rather than assumed.  ``sim_compiles`` counts
simulator-block executables (the expensive ones); ``aux_compiles``
counts the tiny state-init and stats-reduce programs.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.netsim_jax.measure import (PhaseStats, StreamChunk, SweepKey,
                                      phase_schedule, reduce_window_stats)
from repro.netsim_jax.sim import init_state, simulate

from .bucketing import BucketKey, stack_lanes
from .request import LaneSpec

__all__ = ["BatchRunner", "clear_service_cache"]

# shapes (block/init/reduce executables) already executed by this
# process — the line between a fresh XLA compilation and a jit-cache hit
_EXECUTED: set = set()


@functools.lru_cache(maxsize=None)
def _block_jit(key: SweepKey, cycles: int):
    """One fence block for a whole batch: vmapped ``simulate`` over the
    lane axis, state donated (the FIFO buffers update in place across
    blocks instead of copying per block)."""
    cfg = key.cfg

    def block(progs, states):
        def one(p, st):
            st, _ = simulate(cfg, p, st, cycles, key.unroll, key.impl,
                             key.cycles_per_call)
            return st
        return jax.vmap(one)(progs, states)
    return jax.jit(block, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _init_jit(key: SweepKey):
    """Vmapped fresh-state builder with the measurement window armed —
    the same two lines :func:`phased_stats` opens with."""
    cfg = key.cfg

    def mk(depth, credits):
        st = init_state(cfg, depth, credits)
        return st._replace(
            measure_start=st.cycle + key.warmup,
            measure_stop=st.cycle + key.warmup + key.measure)
    return jax.jit(jax.vmap(mk))


@functools.lru_cache(maxsize=None)
def _reduce_jit(ntiles: int, measure: int):
    """Vmapped window reduce — jitted, because only the jitted trace of
    :func:`reduce_window_stats` matches :func:`phased_stats` bitwise."""
    def red(hist, d_inj, d_comp, d_util):
        return jax.vmap(
            lambda h, i, c, u: reduce_window_stats(
                ntiles, measure, h, i, c, u))(hist, d_inj, d_comp, d_util)
    return jax.jit(red)


def _note(shape_id) -> bool:
    """Record an executable shape; True when this process compiles it
    fresh (vs hitting the in-process jit cache)."""
    fresh = shape_id not in _EXECUTED
    _EXECUTED.add(shape_id)
    return fresh


def clear_service_cache() -> None:
    """Drop the service's jitted programs AND the executed-shape
    registry — the cold-start reset the benchmarks use (pair with
    ``jax.clear_caches()`` for a fully cold in-process baseline)."""
    _block_jit.cache_clear()
    _init_jit.cache_clear()
    _reduce_jit.cache_clear()
    _EXECUTED.clear()


class BatchRunner:
    """One in-flight batch of a bucket: advance one fence block per call,
    stream per-lane chunk deltas, reduce to per-lane PhaseStats at the
    end.  ``width`` is the padded (pow2) lane count actually executed;
    ``lanes`` the real requests (padding replicates lane 0 and is
    dropped); ``batch`` the serial number its spans carry."""

    def __init__(self, bkey: BucketKey, lanes: Sequence[LaneSpec],
                 width: int, *, batch: int = 0):
        self.bkey = bkey
        self.batch = batch
        self.lanes = list(lanes)
        self.width = width
        key = bkey.key
        self.schedule = phase_schedule(key.warmup, key.measure, key.drain,
                                       bkey.check_every)
        self.idx = 0
        self.cycle = 0
        self.sim_compiles = 0
        self.aux_compiles = 0
        progs, depths, credits = stack_lanes(lanes, bkey.prog_len, width)
        self.progs = progs
        self.aux_compiles += _note(("init", key, width))
        self.states = _init_jit(key)(depths, credits)
        n = len(self.lanes)
        self._prev_inj = np.zeros(n, np.int64)
        self._prev_comp = np.zeros(n, np.int64)
        self._prev_deliv = np.zeros(n, np.int64)
        self._prev_hist = np.asarray(self.states.lat_hist)[:n].copy()
        # phase-boundary snapshots (a zero-length warmup's boundary is
        # the fresh state, exactly like phased_stats' 0-cycle scan)
        self._snap_w = self._snap_m = self._snapshot()

    # ------------------------------------------------------------------
    def _snapshot(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        n = len(self.lanes)
        st = self.states
        inj = np.asarray(st.prog_ptr, np.int64)[:n].reshape(n, -1).sum(1)
        comp = np.asarray(st.completed, np.int64)[:n].reshape(n, -1).sum(1)
        util = np.asarray(st.link_util, np.int64)[:n]
        return inj, comp, util

    @property
    def done(self) -> bool:
        return self.idx >= len(self.schedule)

    def advance(self) -> List[Tuple[int, StreamChunk]]:
        """Execute the next fence block (ONE vmapped call for the whole
        batch); returns ``(lane_index, chunk)`` telemetry deltas."""
        assert not self.done
        with tracing.span("sim_service.block", batch=self.batch):
            phase, cycles = self.schedule[self.idx]
            key = self.bkey.key
            self.sim_compiles += _note(
                ("block", key, cycles, self.width, self.bkey.prog_len))
            self.states = _block_jit(key, cycles)(self.progs, self.states)
            # the wait the first read-back would make, made explicit so
            # the block's device time has a span of its own
            with tracing.span("sim_service.block.wait"):
                jax.block_until_ready(self.states)
            n = len(self.lanes)
            inj, comp, util = self._snapshot()
            hist = np.asarray(self.states.lat_hist)[:n]
            deliv = hist.sum(-1).astype(np.int64)
            out = [(i, StreamChunk(
                phase=phase, start=self.cycle, stop=self.cycle + cycles,
                injected=int(inj[i] - self._prev_inj[i]),
                completed=int(comp[i] - self._prev_comp[i]),
                delivered=int(deliv[i] - self._prev_deliv[i]),
                hist=hist[i] - self._prev_hist[i])) for i in range(n)]
            self._prev_inj, self._prev_comp = inj, comp
            self._prev_deliv, self._prev_hist = deliv, hist
            self.cycle += cycles
            self.idx += 1
            nxt = self.schedule[self.idx][0] if not self.done else None
            if phase == "warmup" and nxt != "warmup":
                self._snap_w = self._snap_m = (inj, comp, util)
            elif phase == "measure" and nxt != "measure":
                self._snap_m = (inj, comp, util)
        return out

    def finalize(self) -> List[PhaseStats]:
        """Per-lane PhaseStats, bit-identical to direct phased_stats."""
        assert self.done
        key, cfg = self.bkey.key, self.bkey.key.cfg
        n = len(self.lanes)
        d_inj = (self._snap_m[0] - self._snap_w[0]).astype(np.int32)
        d_comp = (self._snap_m[1] - self._snap_w[1]).astype(np.int32)
        d_util = (self._snap_m[2] - self._snap_w[2]).astype(np.int32)

        def grow(x):  # pad the reduce back to the executed batch width
            reps = [x[:1]] * (self.width - n)
            return jnp.asarray(np.concatenate([x] + reps)) if reps \
                else jnp.asarray(x)
        ntiles = cfg.nx * cfg.ny
        self.aux_compiles += _note(
            ("reduce", ntiles, key.measure, self.width))
        stats = _reduce_jit(ntiles, key.measure)(
            self.states.lat_hist, grow(d_inj), grow(d_comp), grow(d_util))
        host = PhaseStats(*(np.asarray(f) for f in stats))
        return [PhaseStats(*(f[i] for f in host)) for i in range(n)]
