"""Phased load–latency measurement on the JIT-compiled mesh simulator.

The standard NoC evaluation methodology (Dally & Towles §23.1; the same
battery Ring-Mesh and Epiphany-V report):

1. **warmup** — run the network to steady state; nothing is recorded;
2. **measurement window** — every packet *injected* during the window is
   tagged (via the telemetry gate ``SimState.measure_start/stop`` on the
   packet's injection-cycle tag) and its round-trip latency lands in the
   per-packet histogram; accepted throughput and channel utilization are
   the deltas of the ``completed`` / ``link_util`` counters across the
   window;
3. **drain** — the simulation keeps running for a fixed budget of cycles
   (and keeps *injecting*, so tagged packets experience real contention)
   so the tagged packets can be delivered; only their latencies are in
   the histogram.  A fixed budget keeps the whole program one bounded
   ``lax.scan`` — past saturation some tagged packets may still be in
   flight when it expires, so latency stats there are censored (biased
   low); ``delivered < offered`` in :class:`PhaseStats` exposes exactly
   how much.  Saturation itself is still located correctly: the knee is
   crossed while the network delivers what it is offered.

Everything here is pure JAX: :func:`phased_stats` is one jitted program
(three ``lax.scan`` phases + histogram reductions), and
:func:`load_latency_sweep` ``vmap``s it over a stack of injection
programs — one per offered load — so a full saturation curve for a
traffic pattern costs a single compilation.

The saturation point is located per the usual convention: the first
offered load whose mean latency reaches ``3x`` the zero-load latency
(the latency measured at the lowest swept rate).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.netsim import LAT_BINS
from repro.mesh.config import MeshConfig
from .sim import (FWD, I32, Program, SimConfig, SimState, init_state,
                  load_program, memory_digest, simulate)
from .traffic import make_traffic

__all__ = ["PhaseStats", "phased_stats", "measure_program",
           "stack_rate_programs", "load_latency_sweep", "saturation_point",
           "curve_is_monotone", "curve_record", "hist_quantile",
           "compile_sweep", "SATURATION_FACTOR", "DEFAULT_SWEEP_RATES",
           "sweep_config", "ascii_curve", "SweepKey", "batch_stats_fn",
           "batched_phased_stats", "clear_sweep_cache",
           "StreamChunk", "phase_schedule", "reduce_window_stats",
           "stream_phased_stats"]

# mean latency >= SATURATION_FACTOR * zero-load latency <=> saturated
SATURATION_FACTOR = 3.0

# The canonical saturation-curve setup, shared by the benchmark
# (bench_load_latency_8x8) and examples/load_latency.py so "reproduce the
# figure" runs the exact grid the benchmark validates.
DEFAULT_SWEEP_RATES = (0.02, 0.05, 0.1, 0.15, 0.2, 0.25, 0.3, 0.35,
                       0.4, 0.45, 0.5, 0.55)


def sweep_config(nx: int, ny: int, topology=None) -> MeshConfig:
    """Mesh configuration for saturation sweeps: buffering deep enough
    that flow control, not storage, is the limit.  ``topology`` selects
    the network topology (default: the plain mesh)."""
    return MeshConfig(nx=nx, ny=ny, max_out_credits=128, router_fifo=16,
                      topology=topology)


def _as_simconfig(cfg) -> SimConfig:
    """Public measure entry points take any config flavor (MeshConfig,
    NetConfig, SimConfig); the jitted internals want SimConfig."""
    if isinstance(cfg, SimConfig):
        return cfg
    return MeshConfig.coerce(cfg).to_sim()

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SweepKey:
    """Static identity of one compiled sweep program.

    Everything that determines the trace — the (hashable) simulator
    config, the phase lengths and the execution knobs — in one frozen
    dataclass.  It is the cache key for the jitted sweep programs below
    AND the design-space-exploration bucket key (:mod:`repro.dse` groups
    spec points that share a ``SweepKey`` + program shape so each bucket
    compiles exactly once).  ``cfg`` accepts any config flavor and is
    normalized to :class:`SimConfig`.
    """
    cfg: SimConfig
    warmup: int
    measure: int
    drain: int
    unroll: int = 1
    impl: str = "fused"
    cycles_per_call: int = 1

    def __post_init__(self):
        if not isinstance(self.cfg, SimConfig):
            object.__setattr__(self, "cfg", _as_simconfig(self.cfg))
        for name in ("warmup", "measure", "drain"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 0 or (name == "measure" and v == 0):
                raise ValueError(
                    f"SweepKey.{name} must be a nonnegative int (measure "
                    f"positive), got {v!r}")

    @property
    def horizon(self) -> int:
        """Total simulated cycles per point (warmup + measure + drain)."""
        return self.warmup + self.measure + self.drain


class PhaseStats(NamedTuple):
    """Measurement-window statistics (all jnp scalars except ``hist``).

    Rates are per tile per cycle; latencies are cycles (round trip,
    injection -> registered response)."""
    offered: jax.Array        # packets injected during the window
    accepted: jax.Array       # packets completed during the window
    delivered: jax.Array      # window-injected packets delivered by drain end
    lat_mean: jax.Array
    lat_p50: jax.Array
    lat_p95: jax.Array
    lat_p99: jax.Array
    lat_max: jax.Array
    peak_link_util: jax.Array  # busiest mesh channel (W/E/N/S), fwd network
    hops: jax.Array            # total link crossings (both networks, W/E/N/S)
    hist: jax.Array            # (LAT_BINS,) latency histogram of the window


def hist_quantile(hist: jax.Array, q: float) -> jax.Array:
    """The q-quantile (in bins == cycles) of a counts histogram: smallest
    bin b with cdf(b) >= ceil(q * total).  0 when the histogram is empty."""
    total = hist.sum()
    cdf = jnp.cumsum(hist)
    target = jnp.ceil(q * total).astype(hist.dtype)
    idx = jnp.searchsorted(cdf, jnp.maximum(target, 1))
    return jnp.where(total > 0, jnp.minimum(idx, LAT_BINS - 1), 0).astype(F32)


def reduce_window_stats(ntiles: int, measure: int, hist: jax.Array,
                        d_inj: jax.Array, d_comp: jax.Array,
                        d_util: jax.Array) -> PhaseStats:
    """Reduce raw measurement-window telemetry into :class:`PhaseStats`:
    ``hist`` is the (drain-complete) window latency histogram, ``d_inj`` /
    ``d_comp`` the injected/completed count deltas across the window and
    ``d_util`` the ``link_util`` delta (all int32, so they are exact no
    matter how the phases were chunked).  Traceable — :func:`phased_stats`
    applies it inline after its three phases, and the streaming paths
    (:func:`stream_phased_stats`, :mod:`repro.sim_service`) apply the same
    function under their own ``jit`` to snapshots accumulated block by
    block, which is what keeps streamed results bit-identical to the
    one-shot program."""
    total = hist.sum()
    bins = jnp.arange(LAT_BINS, dtype=F32)
    denom = jnp.maximum(total, 1).astype(F32)
    per_tile_cycle = float(measure * ntiles)
    return PhaseStats(
        offered=d_inj.astype(F32) / per_tile_cycle,
        accepted=d_comp.astype(F32) / per_tile_cycle,
        delivered=total.astype(F32) / per_tile_cycle,
        lat_mean=(bins * hist).sum() / denom,
        lat_p50=hist_quantile(hist, 0.50),
        lat_p95=hist_quantile(hist, 0.95),
        lat_p99=hist_quantile(hist, 0.99),
        lat_max=jnp.max(jnp.where(hist > 0,
                                  jnp.arange(LAT_BINS), 0)).astype(F32),
        peak_link_util=d_util[FWD, ..., 1:].max().astype(F32) / measure,
        # total W/E/N/S crossings on both networks during the window —
        # the hop count the DSE energy model prices (port 0 is P, the
        # tile's own processor port, which is not a mesh wire)
        hops=d_util[..., 1:].sum().astype(F32),
        hist=hist,
    )


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5, 6, 7, 8))
def phased_stats(cfg: SimConfig, prog: Program, state: SimState,
                 warmup: int, measure: int, drain: int,
                 unroll: int = 1, impl: str = "fused",
                 cycles_per_call: int = 1) -> PhaseStats:
    """Run warmup -> measurement window -> drain and reduce the telemetry
    into :class:`PhaseStats`.  ``state`` should be fresh (its histogram
    empty); the measurement window is cycles [warmup, warmup + measure).
    ``unroll`` / ``impl`` / ``cycles_per_call`` select how the underlying
    :func:`repro.netsim_jax.simulate` phases execute (scan unroll; fused
    XLA step vs the Pallas router kernel and its cycles-per-launch) —
    speed knobs that never change results.  No buffer donation here: the
    reduced stats are tiny, so the state has no output to alias with."""
    return _phased(cfg, prog, state, warmup, measure, drain, unroll, impl,
                   cycles_per_call)[0]


def _phased(cfg: SimConfig, prog: Program, state: SimState, warmup: int,
            measure: int, drain: int, unroll: int, impl: str,
            cycles_per_call: int) -> Tuple[PhaseStats, SimState]:
    """:func:`phased_stats` outside its ``jit``, with the final state."""
    ntiles = cfg.nx * cfg.ny
    st = state._replace(
        measure_start=state.cycle + warmup,
        measure_stop=state.cycle + warmup + measure)
    st, _ = simulate(cfg, prog, st, warmup, unroll, impl, cycles_per_call)
    inj0, comp0 = st.prog_ptr.sum(), st.completed.sum()
    util0 = st.link_util
    st, _ = simulate(cfg, prog, st, measure, unroll, impl, cycles_per_call)
    inj1, comp1 = st.prog_ptr.sum(), st.completed.sum()
    util1 = st.link_util
    st, _ = simulate(cfg, prog, st, drain, unroll, impl, cycles_per_call)
    return reduce_window_stats(ntiles, measure, st.lat_hist,
                               inj1 - inj0, comp1 - comp0,
                               util1 - util0), st


# -- per-fence-block streaming -------------------------------------------

class StreamChunk(NamedTuple):
    """Telemetry delta of one fence block of a streamed phased run —
    everything is the *change* during cycles [start, stop), so
    concatenating chunks (summing their fields) reproduces the run's
    totals exactly (all counters are ints)."""
    phase: str          # "warmup" | "measure" | "drain"
    start: int          # first cycle of the block
    stop: int           # one past the last cycle
    injected: int       # program entries issued during the block (all tiles)
    completed: int      # requests completed during the block
    delivered: int      # window-tagged packets delivered during the block
    hist: np.ndarray    # (LAT_BINS,) latency-histogram delta of the block


def phase_schedule(warmup: int, measure: int, drain: int,
                   check_every: int) -> Tuple[Tuple[str, int], ...]:
    """The static fence-block schedule of a streamed phased run:
    ``(phase, cycles)`` per block, each phase split into
    ``check_every``-cycle blocks plus one remainder.  Phase boundaries
    always land on block boundaries, so the warmup/measure snapshots of
    the one-shot :func:`phased_stats` are reproducible from the stream."""
    if check_every < 1:
        raise ValueError(f"check_every must be >= 1, got {check_every}")
    out = []
    for phase, total in (("warmup", warmup), ("measure", measure),
                         ("drain", drain)):
        left = total
        while left > 0:
            c = min(check_every, left)
            out.append((phase, c))
            left -= c
    return tuple(out)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _reduce_window_jit(ntiles: int, measure: int, hist, d_inj, d_comp,
                       d_util) -> PhaseStats:
    return reduce_window_stats(ntiles, measure, hist, d_inj, d_comp, d_util)


def stream_phased_stats(cfg, prog: Program, *, warmup: int = 200,
                        measure: int = 400, drain: int = 400,
                        check_every: int = 100, fifo_depth=None,
                        max_credits=None, unroll: int = 1,
                        impl: str = "fused", cycles_per_call: int = 1):
    """Streaming variant of :func:`phased_stats`: a generator yielding one
    :class:`StreamChunk` per ``check_every``-cycle fence block as the
    phases execute, and *returning* the final :class:`PhaseStats` (read it
    from ``StopIteration.value``, or drive the generator with
    ``yield from``).  The final stats are bit-identical to the one-shot
    :func:`phased_stats` — the phases run through the same
    :func:`repro.netsim_jax.simulate` in block-sized pieces (exact: the
    state transition is pure) and the same :func:`reduce_window_stats`.
    ``cfg`` may be any config flavor; the sim service streams the batched
    equivalent of this loop."""
    cfg = _as_simconfig(cfg)
    # validates the phase recipe exactly like the one-shot entry points
    SweepKey(cfg, warmup, measure, drain, unroll, impl, cycles_per_call)
    st = init_state(cfg, fifo_depth, max_credits)
    st = st._replace(measure_start=st.cycle + warmup,
                     measure_stop=st.cycle + warmup + measure)

    def snapshot(s: SimState) -> Tuple[int, int, np.ndarray]:
        return (int(np.asarray(s.prog_ptr, np.int64).sum()),
                int(np.asarray(s.completed, np.int64).sum()),
                np.asarray(s.link_util, np.int64))

    # phase-boundary snapshots: a zero-length warmup's boundary is the
    # fresh state (exactly what phased_stats' 0-cycle warmup scan sees)
    snap_w = snap_m = snapshot(st)
    prev_inj = prev_comp = prev_deliv = 0
    prev_hist = np.zeros_like(np.asarray(st.lat_hist))
    cycle = 0
    schedule = phase_schedule(warmup, measure, drain, check_every)
    for i, (phase, cycles) in enumerate(schedule):
        st, _ = simulate(cfg, prog, st, cycles, unroll, impl,
                         cycles_per_call)
        inj, comp, util = snapshot(st)
        hist = np.asarray(st.lat_hist)
        deliv = int(hist.sum())
        yield StreamChunk(phase=phase, start=cycle, stop=cycle + cycles,
                          injected=inj - prev_inj,
                          completed=comp - prev_comp,
                          delivered=deliv - prev_deliv,
                          hist=hist - prev_hist)
        prev_inj, prev_comp, prev_deliv = inj, comp, deliv
        prev_hist = hist
        cycle += cycles
        last_of_phase = i + 1 == len(schedule) or schedule[i + 1][0] != phase
        if last_of_phase and phase == "warmup":
            snap_w = snap_m = (inj, comp, util)
        elif last_of_phase and phase == "measure":
            snap_m = (inj, comp, util)
    return _reduce_window_jit(
        cfg.nx * cfg.ny, measure, st.lat_hist,
        jnp.asarray(snap_m[0] - snap_w[0], I32),
        jnp.asarray(snap_m[1] - snap_w[1], I32),
        jnp.asarray(snap_m[2] - snap_w[2], I32))


def measure_program(cfg, entries: Dict[str, np.ndarray], *,
                    warmup: int = 200, measure: int = 400,
                    drain: int = 400, unroll: int = 1,
                    impl: str = "fused",
                    cycles_per_call: int = 1) -> Dict[str, float]:
    """Convenience: phased measurement of one injection program; returns
    plain-python stats (``hist`` as a numpy array).  ``cfg`` may be a
    MeshConfig, NetConfig or SimConfig."""
    cfg = _as_simconfig(cfg)
    stats = phased_stats(cfg, load_program(entries), init_state(cfg),
                         warmup, measure, drain, unroll, impl,
                         cycles_per_call)
    out = {k: float(v) for k, v in stats._asdict().items() if k != "hist"}
    out["hist"] = np.asarray(stats.hist)
    return out


def stack_rate_programs(pattern: str, nx: int, ny: int,
                        rates: Sequence[float], horizon: int,
                        **traffic_kw) -> Program:
    """One injection program per offered load, stacked along a leading
    axis for ``vmap``.  Programs are sized so the *fastest* rate never
    exhausts its entries inside ``horizon`` cycles; slower rates simply
    schedule their tail entries past the horizon (never injected), which
    keeps every program the same shape."""
    length = int(np.ceil(max(rates) * horizon)) + 1
    progs = [load_program(make_traffic(pattern, nx, ny, length,
                                       rate=float(r), **traffic_kw))
             for r in rates]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *progs)


def ascii_curve(rates, lat, sat_idx, width: int = 50) -> str:
    """ASCII load–latency figure: one bar per offered load, bar length ~
    log latency, saturation knee marked.  Shared by
    ``examples/load_latency.py`` and anything else that prints a sweep."""
    lat = np.asarray(lat, float)
    # a rate whose window delivered nothing measures lat 0; clamp the bar
    # scale so the log stays finite instead of aborting the whole figure
    clamped = np.maximum(lat, 1.0)
    scale = width / max(np.log10(clamped.max() / clamped.min()), 1e-9)
    rows = []
    for i, (r, l, lc) in enumerate(zip(rates, lat, clamped)):
        bar = "#" * max(int(np.log10(lc / clamped.min()) * scale), 1)
        mark = "  <- saturation" if i == sat_idx else ""
        rows.append(f"    {r:5.2f} | {bar:<{width}s} {l:8.1f}{mark}")
    return "\n".join(rows)


def saturation_point(lat_mean: np.ndarray,
                     factor: float = SATURATION_FACTOR) -> Optional[int]:
    """Index of the first offered load whose mean latency is >= ``factor``
    times the zero-load latency (``lat_mean[0]``), or None if the sweep
    never saturates."""
    lat = np.asarray(lat_mean, float)
    hits = np.nonzero(lat >= factor * lat[0])[0]
    return int(hits[0]) if hits.size else None


def curve_is_monotone(lat_mean: np.ndarray, rel_tol: float = 0.02,
                      factor: float = SATURATION_FACTOR) -> bool:
    """Is a load–latency curve well formed?  Latency must be monotone
    nondecreasing (within ``rel_tol`` measurement tolerance) up to and
    including the saturation point, and must *stay* saturated
    (>= ``factor`` x zero-load) afterwards.  Beyond saturation the
    open-loop latency is unbounded, so finite-window measurements there
    are noise — the standard curve is only defined up to the knee."""
    lat = np.asarray(lat_mean, float)
    sat = saturation_point(lat, factor)
    knee = len(lat) - 1 if sat is None else sat
    pre = lat[:knee + 1]
    if not np.all(pre[1:] >= pre[:-1] * (1.0 - rel_tol)):
        return False
    return bool(np.all(lat[knee:] >= factor * lat[0] * (1.0 - rel_tol))) \
        if sat is not None else True


def curve_record(out: Dict[str, object]) -> Dict[str, object]:
    """JSON-ready per-pattern record of a :func:`load_latency_sweep`
    result — the one schema written to ``experiments/load_latency.json``
    by both ``benchmarks/run.py`` (the CI artifact) and
    ``examples/load_latency.py``."""
    return {
        "rates": [round(float(r), 3) for r in out["rates"]],
        "offered": np.round(out["offered"], 3).tolist(),
        "accepted": np.round(out["accepted"], 3).tolist(),
        "delivered": np.round(out["delivered"], 3).tolist(),
        "lat_mean": np.round(out["lat_mean"], 2).tolist(),
        "lat_p50": np.round(out["lat_p50"], 1).tolist(),
        "lat_p95": np.round(out["lat_p95"], 1).tolist(),
        "lat_p99": np.round(out["lat_p99"], 1).tolist(),
        "lat_max": np.round(out["lat_max"], 1).tolist(),
        "peak_link_util": np.round(out["peak_link_util"], 3).tolist(),
        "hops": np.asarray(out["hops"]).astype(int).tolist(),
        "zero_load_latency": round(float(out["zero_load_latency"]), 2),
        "saturation_index": out["saturation_index"],
        "saturation_rate": out["saturation_rate"],
        "saturation_throughput": round(float(out["saturation_throughput"]),
                                       3),
        "monotone": bool(out["monotone"]),
    }


@functools.lru_cache(maxsize=None)
def _sweep_jit(key: SweepKey):
    """The jitted, rate-vmapped phased-measurement program, cached per
    :class:`SweepKey` so every traffic pattern of a sweep suite shares
    ONE compilation instead of re-tracing per call."""
    cfg = key.cfg

    def f(progs: Program) -> PhaseStats:
        return jax.vmap(
            lambda p: phased_stats(cfg, p, init_state(cfg), key.warmup,
                                   key.measure, key.drain, key.unroll,
                                   key.impl, key.cycles_per_call))(progs)
    return jax.jit(f)


def batch_stats_fn(key: SweepKey, digest: bool = False):
    """The *traceable* batched phased-measurement function for ``key``:
    ``f(progs, fifo_depths, max_credits) -> PhaseStats``, every argument
    and result carrying a leading batch axis.  ``fifo_depths`` /
    ``max_credits`` are the per-point *dynamic* knobs (must not exceed
    the static ``key.cfg`` capacities); each batch element runs from a
    fresh state.  With ``digest``, ``f`` returns ``(PhaseStats,
    digests)``, the second each point's final tile memory summed up by
    :func:`~repro.netsim_jax.sim.memory_digest`, ``(batch, 2)`` uint32.
    Returned untransformed so callers can compose it with
    ``jit`` / ``lax.map`` chunking / ``shard_map`` fan-out — the DSE
    runner (:mod:`repro.dse.runner`) does all three."""
    cfg = key.cfg
    phases = (key.warmup, key.measure, key.drain, key.unroll, key.impl,
              key.cycles_per_call)

    def one(prog: Program, depth: jax.Array, credits: jax.Array):
        state = init_state(cfg, depth, credits)
        if not digest:
            return phased_stats(cfg, prog, state, *phases)
        stats, st = _phased(cfg, prog, state, *phases)
        return stats, memory_digest(cfg, st.mem)

    def f(progs: Program, fifo_depths: jax.Array, max_credits: jax.Array):
        return jax.vmap(one)(progs, fifo_depths, max_credits)
    return f


@functools.lru_cache(maxsize=None)
def _batched_jit(key: SweepKey):
    return jax.jit(batch_stats_fn(key))


def batched_phased_stats(key, progs: Program, fifo_depths=None,
                         max_credits=None) -> PhaseStats:
    """Batched phased measurement: one vmapped, jitted call over a stack
    of injection programs with per-point dynamic FIFO depths and credit
    allowances.  ``key`` is a :class:`SweepKey` (or any config flavor,
    wrapped with the default phase lengths); depths/credits default to
    the config capacities.  Bit-identical to a Python loop of
    :func:`phased_stats` calls (asserted in ``tests/test_dse.py``)."""
    if not isinstance(key, SweepKey):
        key = SweepKey(cfg=key, warmup=200, measure=400, drain=400)
    n = int(progs.length.shape[0])
    cfg = key.cfg
    depths = jnp.broadcast_to(
        jnp.asarray(cfg.router_fifo if fifo_depths is None else fifo_depths,
                    I32), (n,))
    credits = jnp.broadcast_to(
        jnp.asarray(cfg.max_out_credits if max_credits is None
                    else max_credits, I32), (n,))
    return _batched_jit(key)(progs, depths, credits)


def clear_sweep_cache() -> None:
    """Drop every cached/jitted sweep program (:func:`_sweep_jit` and the
    batched variant).  Long-running DSE services sweep many distinct
    :class:`SweepKey`\\ s; without an occasional clear the jit cache —
    and XLA's per-executable memory — grows without bound."""
    _sweep_jit.cache_clear()
    _batched_jit.cache_clear()


class CompiledSweep(NamedTuple):
    """An AOT-compiled sweep executable plus the :class:`SweepKey` it was
    built for (the shapes alone cannot detect a warmup/measure/drain
    permutation with the same total horizon, so the key is checked)."""
    executable: object
    key: SweepKey

    def __call__(self, progs: Program) -> "PhaseStats":
        return self.executable(progs)


def compile_sweep(cfg, progs: Program, *, warmup: int = 200,
                  measure: int = 400, drain: int = 400, unroll: int = 1,
                  impl: str = "fused", cycles_per_call: int = 1):
    """AOT-compile the vmapped sweep program for ``progs``-shaped input
    via ``jitted.lower(...).compile()``; returns
    ``(CompiledSweep, compile_seconds)``.  Pass the executable to
    :func:`load_latency_sweep` (``compiled=``, same phase lengths) to
    measure pure run time — the benchmark suite uses this to report
    compile and run time separately."""
    import time
    key = SweepKey(_as_simconfig(cfg), warmup, measure, drain, unroll,
                   impl, cycles_per_call)
    fn = _sweep_jit(key)
    t0 = time.perf_counter()
    compiled = fn.lower(progs).compile()
    return CompiledSweep(compiled, key), time.perf_counter() - t0


def load_latency_sweep(pattern: str, nx: int, ny: int,
                       rates: Sequence[float], *,
                       warmup: int = 200, measure: int = 400,
                       drain: int = 400, cfg=None, unroll: int = 1,
                       impl: str = "fused", cycles_per_call: int = 1,
                       compiled=None, **traffic_kw) -> Dict[str, object]:
    """Full load–latency saturation curve for one traffic pattern: the
    phased measurement ``vmap``-ed over offered loads in a single XLA
    program.  Returns numpy arrays keyed like :class:`PhaseStats`, plus
    the rate grid, zero-load latency, and the located saturation point.
    ``cfg`` may be a MeshConfig, NetConfig or SimConfig; ``compiled`` an
    executable from :func:`compile_sweep` (same config/phases/shapes);
    ``impl``/``cycles_per_call`` select the Pallas router kernel as in
    :func:`repro.netsim_jax.simulate` (results identical)."""
    rates = sorted(float(r) for r in rates)
    cfg = SimConfig(nx=nx, ny=ny) if cfg is None else _as_simconfig(cfg)
    # topology-aware patterns (tornado) must see the topology the sim
    # runs on; an explicit traffic_kw["topology"] still wins
    traffic_kw.setdefault("topology", cfg.topology)
    want = SweepKey(cfg, warmup, measure, drain, unroll, impl,
                    cycles_per_call)
    progs = stack_rate_programs(pattern, nx, ny, rates, want.horizon,
                                **traffic_kw)
    if compiled is None:
        run = _sweep_jit(want)
    else:
        key = getattr(compiled, "key", None)
        if key is not None and key != want:
            raise ValueError(
                f"compiled sweep was built for {key}, but "
                f"load_latency_sweep was called with {want}; matching "
                "shapes would execute silently with the wrong "
                "measurement windows")
        run = compiled
    stats = run(progs)
    out: Dict[str, object] = {k: np.asarray(v)
                              for k, v in stats._asdict().items()}
    out["rates"] = np.asarray(rates)
    out["pattern"] = pattern
    out["mesh"] = f"{nx}x{ny}"
    out["topology"] = cfg.topology.kind
    out["zero_load_latency"] = float(out["lat_mean"][0])
    sat = saturation_point(out["lat_mean"])
    out["saturation_index"] = sat
    out["monotone"] = curve_is_monotone(out["lat_mean"])
    out["saturation_rate"] = None if sat is None else float(rates[sat])
    # saturation (peak accepted) throughput, per tile per cycle
    out["saturation_throughput"] = float(np.max(out["accepted"]))
    return out
