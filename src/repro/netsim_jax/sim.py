"""JIT-compiled JAX reimplementation of the cycle-level mesh simulator.

Same semantics as :class:`repro.core.netsim.MeshSim` — 5-port routers with
input FIFOs only, per-output round-robin arbitration, XY dimension-ordered
routing with the reduced crossbar, independent forward/reverse physical
networks, credit-counted standard endpoints — but expressed as a *pure
per-cycle state transition* so the whole simulation compiles to one XLA
program:

* the per-cycle update is :func:`step` (``SimState -> SimState``), driven by
  ``lax.scan`` in :func:`simulate` / ``lax.while_loop`` in
  :func:`run_until_drained`;
* packets are **header-packed**: the five routing/control fields
  (dst/src coordinates + opcode) live in one int32 word
  (:mod:`repro.mesh.encoding`), so a packet is 5 lanes
  (``hdr, addr, data, cmp, tag``) instead of the oracle's 9 fields —
  nearly halving per-cycle FIFO buffer traffic;
* the forward and reverse networks are **fused** into one stacked
  ``(2, ny, nx, ...)`` FIFO pytree; routing, round-robin arbitration and
  the buffer write trace *once* per cycle over the stacked axis instead
  of twice, halving the emitted HLO (and with it XLA compile time).  The
  networks' only semantic difference — whether the port-P output may
  deliver this cycle — enters arbitration as a pure AND on the P output
  column, so it is applied *after* the fused pass (:func:`_finalize`)
  without changing any result bit;
* stateful circular FIFOs become index arithmetic + masked one-hot selects
  (:func:`_fifo_push` / :func:`_fifo_pop`); round-robin arbitration is a
  fixed 5-iteration priority minimisation instead of a data-dependent loop;
* the *effective* router-FIFO depth and credit allowance live in
  ``SimState`` (as scalars) rather than in the static config, so sweeps
  over FIFO depth or ``max_out_credits`` are ``vmap``-able without
  recompiling — as are sweeps over seeds via a stacked injection program;
* all three jitted entry points **donate** the ``SimState`` argument, so
  XLA updates the (large) FIFO buffers in place instead of copying them;
* :func:`simulate` takes a static ``unroll`` factor for its ``lax.scan``,
  and :func:`run_until_drained` a ``check_every`` cadence that evaluates
  the global drain fence every K cycles instead of every cycle (the
  reported drain cycle stays exact; with K > 1 the *state* may run up to
  K - 1 cycles past the fence, which only advances ``SimState.cycle`` —
  a drained network is quiescent);
* the whole per-cycle transition can alternatively run as ONE hand-tiled
  Pallas kernel (``impl="pallas"``, :mod:`repro.kernels.router_step`)
  with a static ``cycles_per_call`` inner loop, so several mesh cycles
  execute per kernel launch — same trace, same bits, amortized dispatch.
  On a TPU Mosaic compiles the kernel; CPU tests run it in interpret
  mode (:mod:`repro.kernels.backend`), with identical results.

The numpy :class:`~repro.core.netsim.MeshSim` remains the oracle: the JAX
path is validated cycle-for-cycle against it in
``tests/test_netsim_jax.py``, including decoded packet-level state in
``tests/test_encoding.py``.  Keep the sub-step ordering here in lockstep
with ``MeshSim.step`` — it is load-bearing for exact parity.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.netsim import (LAT_BINS, NO_MEASURE, NetConfig, NUM_DIRS,
                               P, W, E, N, S)
from repro.core.netsim import OP_CAS, OP_LOAD, OP_STORE  # noqa: F401 (re-export)
from repro.mesh.encoding import (COORD_LIMIT, COORD_MASK, DST_Y_SHIFT,
                                 OP_MASK, OP_SHIFT, pack_dst_op,
                                 swap_for_response, validate_program,
                                 with_src)
from repro.mesh.topology import Topology

__all__ = ["SimConfig", "SimState", "Fifo", "Program", "FWD", "REV",
           "init_state", "load_program", "empty_program_for", "step",
           "simulate", "run_until_drained", "run_until_drained_traced",
           "drained", "JaxMeshSim"]

# packet lanes: the five header fields of netsim._PKT_FIELDS are packed
# into the single `hdr` word (see repro.mesh.encoding for the layout)
FIELDS = ("hdr", "addr", "data", "cmp", "tag")
F = len(FIELDS)
_FI = {k: i for i, k in enumerate(FIELDS)}

# injection-program lanes: `hdr` holds (dst_x, dst_y, op) with the source
# pair zero — the injecting tile ORs itself in at injection time
PROG_FIELDS = ("hdr", "addr", "data", "cmp", "not_before")
_PI = {k: i for i, k in enumerate(PROG_FIELDS)}

# the stacked physical-network axis: index 0 = forward (requests),
# index 1 = reverse (responses/credits)
FWD, REV = 0, 1

I32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Static (shape-determining) configuration; hashable for ``jax.jit``.

    ``router_fifo`` / ``max_out_credits`` here are *capacities*; the
    effective values used by the dynamics are ``SimState.fifo_depth`` /
    ``SimState.max_credits`` (<= capacity), which may be traced/vmapped.
    """
    nx: int
    ny: int
    router_fifo: int = 4
    ep_fifo: int = 4
    max_out_credits: int = 16
    mem_words: int = 64
    resp_latency: int = 1
    # network topology; None is normalized to the plain mesh.  Topology is
    # frozen/hashable, so the config stays a valid jit static — the wrap
    # flags and boundary gating become *compile-time* branches and the
    # mesh trace is byte-identical to the pre-topology code.
    topology: Optional[Topology] = None

    def __post_init__(self):
        if not (0 < self.nx <= COORD_LIMIT and 0 < self.ny <= COORD_LIMIT):
            raise ValueError(
                f"mesh dimensions must be in [1, {COORD_LIMIT}] to fit the "
                f"packed header coordinate fields, got nx={self.nx}, "
                f"ny={self.ny}")
        if self.topology is None:
            object.__setattr__(self, "topology", Topology.mesh())
        self.topology.validate_for(self.nx, self.ny)
        if (self.topology.wrap_x or self.topology.wrap_y) \
                and self.router_fifo < 2:
            raise ValueError(
                "wrapped (ring/torus) topologies need router_fifo >= 2: "
                "the ring bubble flow control reserves one slot for "
                f"entering packets, got router_fifo={self.router_fifo}")

    @classmethod
    def from_netconfig(cls, cfg: NetConfig) -> "SimConfig":
        """Deprecated shim — route conversions through
        :class:`repro.mesh.MeshConfig` (``MeshConfig.from_net(cfg).to_sim()``)."""
        warnings.warn(
            "SimConfig.from_netconfig is deprecated; use "
            "repro.mesh.MeshConfig.from_net(cfg).to_sim()",
            DeprecationWarning, stacklevel=2)
        return _simconfig_from_net(cfg)

    def to_netconfig(self, **kw) -> NetConfig:
        """Deprecated shim — route conversions through
        :class:`repro.mesh.MeshConfig` (``MeshConfig.from_sim(cfg).to_net()``)."""
        warnings.warn(
            "SimConfig.to_netconfig is deprecated; use "
            "repro.mesh.MeshConfig.from_sim(cfg).to_net()",
            DeprecationWarning, stacklevel=2)
        return NetConfig(nx=self.nx, ny=self.ny, router_fifo=self.router_fifo,
                         ep_fifo=self.ep_fifo,
                         max_out_credits=self.max_out_credits,
                         mem_words=self.mem_words,
                         resp_latency=self.resp_latency,
                         topology=self.topology, **kw)


def _simconfig_from_net(cfg: NetConfig) -> "SimConfig":
    return SimConfig(nx=cfg.nx, ny=cfg.ny, router_fifo=cfg.router_fifo,
                     ep_fifo=cfg.ep_fifo, max_out_credits=cfg.max_out_credits,
                     mem_words=cfg.mem_words, resp_latency=cfg.resp_latency,
                     topology=getattr(cfg, "topology", None))


class Fifo(NamedTuple):
    """Struct-of-arrays circular FIFOs.

    The two router networks are stacked: ``buf`` is
    ``(F, 2, ny, nx, ports, cap)`` with ``head``/``count``
    ``(2, ny, nx, ports)``; the endpoint request FIFO keeps its unstacked
    ``(F, ny, nx, 1, cap)`` shape."""
    buf: jax.Array
    head: jax.Array
    count: jax.Array


class Program(NamedTuple):
    """Injection program, kept *outside* the scan carry (it is loop
    invariant; carrying it would copy it every cycle).  ``buf`` lanes are
    ``PROG_FIELDS`` — header-packed, 5 lanes."""
    buf: jax.Array      # (len(PROG_FIELDS), ny, nx, Lp)
    length: jax.Array   # (ny, nx) — entries with op >= 0


class SimState(NamedTuple):
    net: Fifo                  # stacked fwd/rev router FIFOs (see Fifo)
    ep_in: Fifo
    resp_valid: jax.Array      # (L, ny, nx) bool
    resp_buf: jax.Array        # (F, L, ny, nx)
    mem: jax.Array             # (ny, nx, mem_words), or rows: mem_shape
    credits: jax.Array         # (ny, nx)
    rr: jax.Array              # (2, ny, nx, 5) round-robin ptrs per network
    prog_ptr: jax.Array        # (ny, nx)
    reg_valid: jax.Array       # (ny, nx) bool
    reg_buf: jax.Array         # (F, ny, nx)
    completed: jax.Array       # (ny, nx)
    lat_sum: jax.Array         # (ny, nx)
    out_of_credit_cycles: jax.Array  # (ny, nx)
    cycle: jax.Array           # scalar
    fifo_depth: jax.Array      # scalar — effective router FIFO depth
    max_credits: jax.Array     # scalar — effective credit allowance
    # telemetry (cycle-exact twins of the MeshSim accumulators) ---------
    link_util: jax.Array       # (2, ny, nx, 5) — packets out of each port
    fifo_hwm: jax.Array        # (2, ny, nx, 5) — occupancy high-water marks
    ep_hwm: jax.Array          # (ny, nx)
    lat_hist: jax.Array        # (LAT_BINS,) — per-packet RTT histogram
    measure_start: jax.Array   # scalar — window gate on the packet tag
    measure_stop: jax.Array    # scalar


def init_state(cfg: SimConfig,
               fifo_depth: Optional[jax.typing.ArrayLike] = None,
               max_credits: Optional[jax.typing.ArrayLike] = None) -> SimState:
    """Fresh all-idle state (no program loaded).

    ``fifo_depth`` / ``max_credits`` default to the config capacities and
    may be traced values (for ``vmap`` sweeps) as long as they never exceed
    the static capacity.
    """
    ny, nx = cfg.ny, cfg.nx
    L = cfg.resp_latency
    depth = jnp.asarray(cfg.router_fifo if fifo_depth is None else fifo_depth, I32)
    mc = jnp.asarray(cfg.max_out_credits if max_credits is None else max_credits, I32)
    return SimState(
        net=Fifo(buf=jnp.zeros((F, 2, ny, nx, NUM_DIRS, cfg.router_fifo), I32),
                 head=jnp.zeros((2, ny, nx, NUM_DIRS), I32),
                 count=jnp.zeros((2, ny, nx, NUM_DIRS), I32)),
        ep_in=Fifo(buf=jnp.zeros((F, ny, nx, 1, cfg.ep_fifo), I32),
                   head=jnp.zeros((ny, nx, 1), I32),
                   count=jnp.zeros((ny, nx, 1), I32)),
        resp_valid=jnp.zeros((L, ny, nx), bool),
        resp_buf=jnp.zeros((F, L, ny, nx), I32),
        mem=jnp.zeros(mem_shape(cfg), I32),
        credits=jnp.broadcast_to(mc, (ny, nx)).astype(I32),
        rr=jnp.zeros((2, ny, nx, NUM_DIRS), I32),
        prog_ptr=jnp.zeros((ny, nx), I32),
        reg_valid=jnp.zeros((ny, nx), bool),
        reg_buf=jnp.zeros((F, ny, nx), I32),
        completed=jnp.zeros((ny, nx), I32),
        lat_sum=jnp.zeros((ny, nx), I32),
        out_of_credit_cycles=jnp.zeros((ny, nx), I32),
        cycle=jnp.asarray(0, I32),
        fifo_depth=depth,
        max_credits=mc,
        link_util=jnp.zeros((2, ny, nx, NUM_DIRS), I32),
        fifo_hwm=jnp.zeros((2, ny, nx, NUM_DIRS), I32),
        ep_hwm=jnp.zeros((ny, nx), I32),
        lat_hist=jnp.zeros((LAT_BINS,), I32),
        measure_start=jnp.asarray(0, I32),
        measure_stop=jnp.asarray(NO_MEASURE, I32),
    )


def load_program(entries: Dict[str, np.ndarray]) -> Program:
    """Pack an injection program (same schema as ``MeshSim.load_program``:
    fields shaped (ny, nx, L), ``op`` < 0 marks padding) into the
    header-packed 5-lane :class:`Program`.

    Validates the packet domain first: coordinates and opcode must fit
    the packed header field widths, payload lanes must fit int32 — see
    :func:`repro.mesh.encoding.validate_program` for the exact limits
    (the error names the offending field).
    """
    op = np.asarray(entries["op"])
    ny, nx, Lp = op.shape
    validate_program(entries)
    zero = np.zeros(op.shape, np.int64)

    def get(k):
        return np.asarray(entries[k]) if k in entries else zero

    buf = np.stack([
        pack_dst_op(get("dst_x").astype(np.int64), get("dst_y"), op),
        get("addr"), get("data"), get("cmp"), get("not_before"),
    ]).astype(np.int32)
    return Program(buf=jnp.asarray(buf),
                   length=jnp.asarray((op >= 0).sum(-1), I32))


def _empty_program_for(cfg: SimConfig) -> Program:
    return Program(buf=jnp.zeros((len(PROG_FIELDS), cfg.ny, cfg.nx, 1), I32),
                   length=jnp.zeros((cfg.ny, cfg.nx), I32))


def empty_program_for(cfg: SimConfig) -> Program:
    """Deprecated — ``load_program(repro.mesh.empty_program(nx, ny, 1))``
    (or simply don't load anything: a fresh state injects nothing)."""
    warnings.warn(
        "empty_program_for is deprecated; build programs with "
        "repro.mesh.empty_program and pack them with load_program",
        DeprecationWarning, stacklevel=2)
    return _empty_program_for(cfg)


def _iota_last(prefix_shape: Tuple[int, ...], n: int,
               kernel_safe: bool = False) -> jax.Array:
    """``0..n-1`` along a new trailing axis (for one-hot comparisons
    against ``x[..., None]``).  Normally a host ``np.arange`` constant
    (XLA hoists it); inside the Pallas router kernel a full-rank
    ``broadcasted_iota`` op instead — ``pallas_call`` rejects captured
    array constants, and 1-D iotas do not lower on Mosaic."""
    if kernel_safe:
        return lax.broadcasted_iota(I32, tuple(prefix_shape) + (n,),
                                    len(prefix_shape))
    return np.arange(n, dtype=np.int32)


def _expand(x: jax.Array, axis: int = -1,
            kernel_safe: bool = False) -> jax.Array:
    """``jnp.expand_dims(x, axis)``; inside the kernel a bool mask widens
    to int32 for the reshape (exact), which Mosaic cannot do on bool
    vectors."""
    if kernel_safe and x.dtype == jnp.bool_:
        return jnp.expand_dims(x.astype(I32), axis) != 0
    return jnp.expand_dims(x, axis)


def _col(x: jax.Array, i: int, kernel_safe: bool = False) -> jax.Array:
    """``x[..., i]``; inside the kernel a bool column is sliced as int32
    (Mosaic cannot relayout bool vectors)."""
    if kernel_safe and x.dtype == jnp.bool_:
        return x.astype(I32)[..., i] != 0
    return x[..., i]


def _stack_last(xs, kernel_safe: bool = False) -> jax.Array:
    """``jnp.stack(xs, axis=-1)``; inside the kernel a select chain over
    an iota (Mosaic cannot concatenate along an unaligned minor axis)."""
    if not kernel_safe:
        return jnp.stack(xs, axis=-1)
    shape = jnp.broadcast_shapes(*(x.shape for x in xs))
    io = _iota_last(shape, len(xs), True)
    # bool operands select as int32 (Mosaic cannot select i1 vectors)
    is_bool = xs[0].dtype == jnp.bool_
    xs = [jnp.broadcast_to(x, shape).astype(I32)[..., None] for x in xs]
    out = xs[0]
    for k in range(1, len(xs)):
        out = jnp.where(io == k, xs[k], out)
    return out != 0 if is_bool else out


# ----------------------------------------------------------------------
# FIFO primitives (pure)
# ----------------------------------------------------------------------
def _fifo_peek(f: Fifo) -> jax.Array:
    """Head packet of every FIFO: ``buf`` minus its capacity axis.

    A select chain over the (small, static) depth axis rather than a
    gather — XLA CPU fuses the selects into one elementwise pass, while a
    gather lowers to a scalar loop."""
    cap = f.buf.shape[-1]
    out = f.buf[..., 0]
    for d in range(1, cap):
        out = jnp.where(f.head[None] == d, f.buf[..., d], out)
    return out


def _fifo_pop(f: Fifo, mask: jax.Array, depth: jax.Array) -> Fifo:
    m = mask.astype(I32)
    return f._replace(head=(f.head + m) % depth, count=f.count - m)


def _fifo_push(f: Fifo, mask: jax.Array, pkt: jax.Array,
               depth: jax.Array, kernel_safe: bool = False) -> Fifo:
    """Enqueue ``pkt`` (buf shape minus capacity) where ``mask``; caller
    guarantees space.  A one-hot masked select over the (small) depth
    axis — fuses to a single elementwise pass on CPU, where XLA scatters
    are far slower."""
    cap = f.buf.shape[-1]
    tail = (f.head + f.count) % depth
    onehot = (_iota_last(tail.shape, cap, kernel_safe) == tail[..., None]) \
        & _expand(mask, -1, kernel_safe)
    buf = jnp.where(onehot[None], pkt[..., None], f.buf)
    return f._replace(buf=buf, count=f.count + mask.astype(I32))


# ----------------------------------------------------------------------
# router — one fused pass over the stacked (fwd, rev) network axis
# ----------------------------------------------------------------------
def _from_neighbors(a: jax.Array, topo: Topology,
                    kernel_safe: bool = False):
    """What each tile receives from its four neighbours' facing ports:
    ``(w, e, n, s)``, each ``a.shape[:-1]``, where ``w`` is the west
    neighbour's E column, ``e`` the east neighbour's W, ``n`` the north
    neighbour's S and ``s`` the south neighbour's N.  ``a`` ends in
    (ny, nx, ports).  Off the edge of a non-wrapped dimension reads 0
    (False); a wrapped dimension reads the opposite edge (static
    slice + concatenate: ``jnp.roll`` does not lower on Mosaic).  Inside
    the kernel the edge is a concatenated zero block and bools shift as
    int32, since Mosaic lowers neither pads nor bool relayouts."""
    is_bool = kernel_safe and a.dtype == jnp.bool_
    if is_bool:
        a = a.astype(I32)

    def shift(port, axis, from_lower, wrap):
        # ``axis`` indexes the port-less result.  The kernel takes the
        # port column first; the fused path slices the range first, so
        # that XLA folds both slices into one.
        col = a[..., port] if kernel_safe else None

        def part(lo, hi):
            if kernel_safe:
                return lax.slice_in_dim(col, lo, hi, axis=axis)
            return lax.slice_in_dim(a, lo, hi, axis=axis - 1)[..., port]

        n = a.shape[axis - 1]
        rest = part(0, n - 1) if from_lower else part(1, n)
        if not wrap and not kernel_safe:
            widths = [(0, 0)] * rest.ndim
            widths[axis] = (1, 0) if from_lower else (0, 1)
            return jnp.pad(rest, widths)
        edge = part(n - 1, n) if from_lower else part(0, 1)
        if not wrap:
            edge = jnp.zeros_like(edge)
        parts = (edge, rest) if from_lower else (rest, edge)
        return jnp.concatenate(parts, axis=axis)

    out = (shift(E, -1, True, topo.wrap_x),    # from x - 1
           shift(W, -1, False, topo.wrap_x),   # from x + 1
           shift(S, -2, True, topo.wrap_y),    # from y - 1
           shift(N, -2, False, topo.wrap_y))   # from y + 1
    return tuple(o != 0 for o in out) if is_bool else out


def _arbitrate_fused(cfg: SimConfig, net: Fifo, rr: jax.Array, xs, ys,
                     depth: jax.Array, cycle: jax.Array,
                     kernel_safe: bool = False,
                     ) -> Tuple[jax.Array, jax.Array]:
    """Routing + round-robin arbitration for BOTH networks in one traced
    pass (mirrors the first half of ``MeshSim._router_step``, stacked).

    Returns ``(win, moved_pkt)`` where ``win`` (2, ny, nx, out) is the
    winning input port per output (-1 = none) with the port-P deliver
    gate NOT yet applied (computed as if the P output always had space),
    and ``moved_pkt`` (F, 2, ny, nx, out) the winner's packet.  The gate
    is a pure AND on the P output's candidate column, so
    :func:`_finalize` can apply it per network afterwards without
    changing any other column — this is what lets the two networks share
    one arbitration trace even though the forward network's deliver space
    depends on the endpoint service step that *reads* the reverse
    network's results.  (The topology's wrap/bubble/boundary terms below
    never touch the P column either, preserving that property.)
    """
    topo = cfg.topology
    heads = _fifo_peek(net)                     # (F, 2, ny, nx, 5)
    valid = net.count > 0                       # (2, ny, nx, 5)
    # dimension-ordered routing straight off the packed header word — the
    # pluggable decision shared verbatim with the numpy oracle
    h = heads[_FI["hdr"]]
    dx, dy = h & COORD_MASK, (h >> DST_Y_SHIFT) & COORD_MASK
    x, y = xs[None, :, :, None], ys[None, :, :, None]
    want = topo.route(dx, dy, x, y, cfg.nx, cfg.ny, xp=jnp).astype(I32)

    # Destination space per output port (start-of-cycle, conservative):
    # each output sees its neighbour's facing input FIFO.  The P column
    # is provisionally True (the deliver gate is applied in _finalize).
    space = net.count < depth                   # (2, ny, nx, 5)
    w_sp, e_sp, n_sp, s_sp = _from_neighbors(space, topo, kernel_safe)

    # Multi-chip boundary links accept one flit every boundary_period
    # cycles (the narrower off-chip channel): gate the E output of the
    # column west of each boundary and the W output east of it.
    if topo.gated:
        open_now = (cycle % topo.boundary_period) == 0
        cols = topo.boundary_cols(cfg.nx)
        if kernel_safe:
            iox = lax.broadcasted_iota(I32, (1, 1, cfg.nx), 2)
            e_gate = w_gate = jnp.zeros((1, 1, cfg.nx), bool)
            for c0 in cols:
                e_gate = e_gate | (iox == c0 - 1)
                w_gate = w_gate | (iox == c0)
        else:
            e_gate = np.zeros((1, 1, cfg.nx), bool)
            w_gate = np.zeros((1, 1, cfg.nx), bool)
            e_gate[0, 0, [c0 - 1 for c0 in cols]] = True
            w_gate[0, 0, [c0 for c0 in cols]] = True
        e_sp = e_sp & (open_now | ~e_gate)
        w_sp = w_sp & (open_now | ~w_gate)

    out_space = _stack_last([
        jnp.ones(space.shape[:-1], bool),               # P (gated later)
        w_sp, e_sp, n_sp, s_sp,
    ], kernel_safe)

    # Round-robin arbitration, all five output ports of both networks at
    # once: per output port o, the valid requester with minimal
    # (in_port - rr[o]) mod 5 wins.
    if kernel_safe:
        io_out = lax.broadcasted_iota(I32, (1, 1, 1, 1, NUM_DIRS), 4)
        io_in = lax.broadcasted_iota(I32, (NUM_DIRS, 1), 0)
    else:
        io = np.arange(NUM_DIRS, dtype=np.int32)
        io_out, io_in = io[None, None, None, None, :], io[:, None]
    cand = (_expand(valid, -1, kernel_safe)     # (2, ny, nx, in, out)
            & (want[..., :, None] == io_out)
            & _expand(out_space, -2, kernel_safe))

    # Ring bubble flow control (see repro.mesh.topology): a packet
    # ENTERING a wrapped-dimension ring needs TWO free slots in the target
    # FIFO; the CONTINUING input (the opposite port of the same dimension,
    # in = ((out - 1) ^ 1) + 1) needs the usual one.  Compiled out on
    # non-wrapped topologies.
    if topo.wrap_x or topo.wrap_y:
        space2 = net.count < depth - 1          # >= 2 free slots
        ones2 = jnp.ones(space2.shape[:-1], bool)
        w2, e2, n2, s2 = _from_neighbors(space2, topo, kernel_safe)
        if not topo.wrap_x:
            w2 = e2 = ones2
        if not topo.wrap_y:
            n2 = s2 = ones2
        out_space2 = _stack_last([ones2, w2, e2, n2, s2], kernel_safe)
        bubble_out = None                       # which outputs enter rings
        if topo.wrap_x:
            bubble_out = (io_out == E) | (io_out == W)
        if topo.wrap_y:
            b_y = (io_out == N) | (io_out == S)
            bubble_out = b_y if bubble_out is None else (bubble_out | b_y)
        is_cont = io_in == (((io_out - 1) ^ 1) + 1)     # (in, out) broadcast
        need2 = bubble_out & ~is_cont
        cand = cand & (_expand(out_space2, -2, kernel_safe) | ~need2)
    prio = (io_in - rr[..., None, :]) % NUM_DIRS
    prio = jnp.where(cand, prio, NUM_DIRS + 1)
    best = prio.min(-2)                         # (2, ny, nx, out)
    # first input port attaining the minimum — argmin with its lowest-index
    # tie-break, written as a select chain over the static port axis so the
    # identical trace runs inside the Pallas router kernel
    winner = jnp.zeros(best.shape, I32)
    for i in range(NUM_DIRS - 1, -1, -1):
        winner = jnp.where(prio[..., i, :] == best, i, winner)
    win = jnp.where(best <= NUM_DIRS, winner, -1)
    # winning packet per output port: select along the *input* axis
    # (fusible select chain instead of a gather; see _fifo_peek).  The
    # P column is computed from the UNGATED winner — harmless, because
    # every consumer masks it with the gated `has`.
    widx = jnp.clip(win, 0, NUM_DIRS - 1)
    moved_pkt = jnp.broadcast_to(heads[..., :1], heads.shape)
    for i in range(1, NUM_DIRS):
        moved_pkt = jnp.where(widx[None] == i, heads[..., i:i + 1],
                              moved_pkt)        # (F, 2, ny, nx, out)
    return win, moved_pkt


def _finalize(win: jax.Array, rr: jax.Array, deliver_space: jax.Array,
              kernel_safe: bool = False,
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Apply one network's port-P deliver gate to its slice of the fused
    arbitration result; returns (rr', pop_mask (ny,nx,in), has (ny,nx,out))
    — bit-identical to arbitrating that network alone with the gate in
    its candidate mask."""
    if kernel_safe:     # Mosaic lowers no scatter, even at a static index
        io_out = _iota_last(win.shape[:-1], NUM_DIRS, True)
        win = jnp.where((io_out == P) & ~_expand(deliver_space, -1, True),
                        -1, win)
    else:
        win = win.at[..., P].set(jnp.where(deliver_space, win[..., P], -1))
    has = win >= 0
    rr = jnp.where(has, (win + 1) % NUM_DIRS, rr)
    widx = jnp.clip(win, 0, NUM_DIRS - 1)
    io_in = lax.broadcasted_iota(I32, (NUM_DIRS, 1), 0) if kernel_safe \
        else np.arange(NUM_DIRS, dtype=np.int32)[:, None]
    pop = ((io_in == widx[..., None, :])
           & _expand(has, -2, kernel_safe)).any(-1)
    return rr, pop, has


def _neighbor_push_masks(has: jax.Array, moved_pkt: jax.Array,
                         p_mask: jax.Array, p_pkt: jax.Array,
                         topo: Topology, kernel_safe: bool = False,
                         ) -> Tuple[jax.Array, jax.Array]:
    """Turn per-output winners into per-input push masks for the neighbour
    FIFOs, with the local port-P enqueue (endpoint response or program
    injection) folded into the same single write.  Every destination
    (tile, in_port) has exactly one feeder, so this is conflict-free.
    Wrapped dimensions feed the opposite edge (static slice+concat)."""
    w_in, e_in, n_in, s_in = _from_neighbors(has, topo, kernel_safe)
    w_pk, e_pk, n_pk, s_pk = _from_neighbors(moved_pkt, topo, kernel_safe)
    mask_in = _stack_last([p_mask, w_in, e_in, n_in, s_in], kernel_safe)
    pkt_in = _stack_last([p_pkt, w_pk, e_pk, n_pk, s_pk], kernel_safe)
    return mask_in, pkt_in


# ----------------------------------------------------------------------
# the per-cycle transition
# ----------------------------------------------------------------------
def _coords(cfg: SimConfig, kernel_safe: bool = False):
    # host-side numpy constants (NOT jax arrays: a cached jax array created
    # inside one trace would leak into the next); XLA hoists them out of
    # the scan loop.  In the Pallas kernel they become 2-D iota ops
    # instead (captured array constants do not lower).
    if kernel_safe:
        return (lax.broadcasted_iota(I32, (cfg.ny, cfg.nx), 1),
                lax.broadcasted_iota(I32, (cfg.ny, cfg.nx), 0))
    ys, xs = np.mgrid[0:cfg.ny, 0:cfg.nx]
    return xs.astype(np.int32), ys.astype(np.int32)


# Longest program whose next entry the fused step fetches by one-hot
# select rather than by gather (see _step_core).  On a v5e at 16x32 the
# one-hot is the faster up to 2,048 entries and the slower from 3,072
# (benchmarks/entry_fetch_crossover.py).
ENTRY_ONEHOT_MAX_LP = 2048


def _entry_fetch_onehot(Lp: int, kernel_safe: bool) -> bool:
    """Whether a program of ``Lp`` entries per tile is fetched by one-hot
    select: always inside the Pallas kernel (Mosaic lowers no gather),
    elsewhere up to :data:`ENTRY_ONEHOT_MAX_LP` entries."""
    return kernel_safe or Lp <= ENTRY_ONEHOT_MAX_LP


# Largest tile memory (words per tile) the fused step reads and writes by
# one-hot select rather than by gathering one word per tile and writing
# the storing tiles' words (see _step_core, _mem_store).  On a v5e at
# 32x32, a batch of 16, the one-hot is the faster up to 1,024 words and
# the slower from 2,048 (benchmarks/mem_access_crossover.py).
MEM_ONEHOT_MAX_WORDS = 1024
# A larger memory is stored as rows of MEM_ROW words, a whole number of
# (8, MEM_ROW) int32 tiles per tile of the mesh: so laid out, a batch of
# states flattens without a copy of the memory to the one dimension the
# TPU's gather indexes, and to the rows the store kernel copies.
MEM_ROW = 128


def _mem_onehot(mem_words: int, kernel_safe: bool) -> bool:
    """Whether a tile memory of ``mem_words`` words is read and written
    by one-hot select: always inside the Pallas kernel (Mosaic lowers
    neither gather nor scatter), elsewhere up to
    :data:`MEM_ONEHOT_MAX_WORDS` words."""
    return kernel_safe or mem_words <= MEM_ONEHOT_MAX_WORDS


def mem_access_form(cfg: SimConfig, platform: Optional[str] = None) -> str:
    """How the fused step reads and writes ``cfg``'s tile memory on
    ``platform`` (default: JAX's default backend): ``"onehot"`` up to
    :data:`MEM_ONEHOT_MAX_WORDS` words; above, one word gathered per tile
    and written by ``"store_kernel"`` on a TPU (:func:`_mem_store`) or
    by ``"scatter"`` elsewhere."""
    if _mem_onehot(cfg.mem_words, kernel_safe=False):
        return "onehot"
    platform = platform or jax.default_backend()
    return "store_kernel" if platform == "tpu" else "scatter"


def _mem_at(addr: jax.Array):
    """Each tile's word ``addr`` as an index into a memory stored in
    rows, ``(ny, nx, rows, MEM_ROW)``."""
    ys, xs = np.mgrid[0:addr.shape[0], 0:addr.shape[1]].astype(np.int32)
    return ys, xs, addr // MEM_ROW, addr % MEM_ROW


def _mem_scatter(mem: jax.Array, addr: jax.Array, newval: jax.Array,
                 write: jax.Array) -> jax.Array:
    """Every tile's word ``addr`` set to ``newval`` by one XLA scatter;
    where ``write`` is false ``newval`` is the word as it was."""
    del write
    return mem.at[_mem_at(addr)].set(
        newval, indices_are_sorted=True, unique_indices=True,
        mode="promise_in_bounds")


def _mem_store_kernel(mem: jax.Array, addr: jax.Array, newval: jax.Array,
                      write: jax.Array) -> jax.Array:
    """The storing tiles' words written in place by the compiled
    ``tile_mem_store`` kernel; the other tiles are not touched."""
    from repro.kernels.tile_mem_store import tile_mem_store
    return tile_mem_store(mem, addr, newval, write, interpret=False)


def _mem_store(mem: jax.Array, addr: jax.Array, newval: jax.Array,
               write: jax.Array) -> jax.Array:
    """The step's write of a memory stored in rows: the in-place kernel
    where the program is lowered for a TPU, the XLA scatter elsewhere
    (:func:`mem_access_form`).  The two leave the same memory."""
    return lax.platform_dependent(mem, addr, newval, write,
                                  tpu=_mem_store_kernel,
                                  default=_mem_scatter)


def mem_shape(cfg: SimConfig) -> Tuple[int, ...]:
    """``SimState.mem``'s shape: ``(ny, nx, mem_words)`` up to
    :data:`MEM_ONEHOT_MAX_WORDS` words, else ``(ny, nx, rows, MEM_ROW)``
    with the words in order and unaddressed padding at the end."""
    if _mem_onehot(cfg.mem_words, kernel_safe=False):
        return (cfg.ny, cfg.nx, cfg.mem_words)
    rows = -(-cfg.mem_words // (8 * MEM_ROW)) * 8
    return (cfg.ny, cfg.nx, rows, MEM_ROW)


def mem_to_state(cfg: SimConfig, words) -> jax.Array:
    """A ``(ny, nx, mem_words)`` memory image in ``SimState.mem``'s shape."""
    words = jnp.asarray(words, I32)
    shape = mem_shape(cfg)
    pad = int(np.prod(shape[2:])) - cfg.mem_words
    return jnp.pad(words, ((0, 0), (0, 0), (0, pad))).reshape(shape)


def mem_from_state(cfg: SimConfig, mem) -> np.ndarray:
    """``SimState.mem`` as the ``(ny, nx, mem_words)`` memory image."""
    return np.asarray(mem, np.int64).reshape(
        cfg.ny, cfg.nx, -1)[..., :cfg.mem_words]


def memory_digest(cfg: SimConfig, mem: jax.Array) -> jax.Array:
    """Two uint32 words that sum up one state's ``SimState.mem``, mod
    2**32: the sum of its words, and the sum of each word times one more
    than its place in the ``(ny, nx, mem_words)`` image.  A store that
    is dropped, or written to another word, moves the second."""
    u32 = jnp.uint32
    words = lax.bitcast_convert_type(mem, u32)
    # place = tile * mem_words + word + 1, summed per tile first so that
    # no array of places as large as the memory is made; padding words
    # past mem_words hold 0, whatever place they get
    in_tile = tuple(range(2, mem.ndim))
    iota = functools.partial(lax.broadcasted_iota, u32, mem.shape[2:])
    word = iota(0) if mem.ndim == 3 else iota(0) * MEM_ROW + iota(1)
    tile = lax.broadcasted_iota(u32, mem.shape[:2], 0) * cfg.nx \
        + lax.broadcasted_iota(u32, mem.shape[:2], 1)
    total = words.sum(in_tile, dtype=u32)
    placed = total * (tile * cfg.mem_words + 1) \
        + (words * word).sum(in_tile, dtype=u32)
    return jnp.stack([total.sum(dtype=u32), placed.sum(dtype=u32)])


def _step_core(cfg: SimConfig, prog: Program, st: SimState, *,
               kernel_safe: bool = False) -> Tuple[SimState, jax.Array]:
    """One simulator cycle; returns (state', completions_this_cycle).

    The sub-step order matches ``MeshSim.step`` exactly — do not reorder.
    Both networks' FIFO *counts* advance at their original points in the
    cycle (the endpoint service step reads the reverse network's post-push
    port-P count), but the two (large) buffer writes are deferred and
    performed as ONE stacked write at the end — legal because nothing in
    between reads the router buffers, only the counts.

    The endpoint's memory read and write are one-hot selects over
    ``mem_words`` where :func:`_mem_onehot` says so (always in the
    kernel; on the fused path up to :data:`MEM_ONEHOT_MAX_WORDS` words),
    else, from a memory stored in rows (:func:`mem_shape`), one word
    gathered per tile and the storing tiles' words written in place by
    the ``tile_mem_store`` kernel on a TPU, or one word scattered per
    tile elsewhere (:func:`_mem_store`, :func:`mem_access_form`).  The
    program-entry fetch is one where :func:`_entry_fetch_onehot` says so
    (always in the kernel; on the fused path up to
    :data:`ENTRY_ONEHOT_MAX_LP` entries).  On a TPU an element-wise
    gather along the minor axis costs about the same whatever the
    length, while a one-hot streams the whole array every cycle.

    ``kernel_safe=True`` is the variant traced inside the Pallas router
    kernel (:mod:`repro.kernels.router_step`): the remaining
    traced-index scatter/gather ops (the latency-histogram ``.at[].add``,
    the program-entry fetch of long programs, the access to large tile
    memories and the ``resp_latency > 1`` slot rotation) are swapped for
    one-hot select/sum forms, and bool masks are reshaped and stacked as
    int32 (:func:`_expand`, :func:`_stack_last`) — Mosaic lowers neither
    scatters nor bool relayouts.  All of it is exact int32 arithmetic,
    so the two variants are bit-identical.
    """
    ny, nx = cfg.ny, cfg.nx
    xs, ys = _coords(cfg, kernel_safe)
    c = st.cycle

    with jax.named_scope("step/stats"):
        # ---- registered response port becomes visible (stats record) ----
        rv = st.reg_valid
        completed = st.completed + rv.astype(I32)
        tag = st.reg_buf[_FI["tag"]]
        lat = c - tag
        lat_sum = st.lat_sum + jnp.where(rv, lat, 0)
        done_now = rv.sum().astype(I32)
        # latency histogram, gated to the measurement window by the
        # packet's injection cycle (its tag); scatter-add of 0 elsewhere
        # is a no-op
        in_win = rv & (tag >= st.measure_start) & (tag < st.measure_stop)
        bin_idx = jnp.clip(lat, 0, LAT_BINS - 1)
        if kernel_safe:
            bin_oh = (_iota_last(bin_idx.shape, LAT_BINS, True)
                      == bin_idx[..., None]) & _expand(in_win, -1, True)
            lat_hist = st.lat_hist + bin_oh.astype(I32).sum((0, 1))
        else:
            lat_hist = st.lat_hist.at[bin_idx].add(in_win.astype(I32))

    with jax.named_scope("step/arbitrate"):
        # ---- both networks: ONE fused routing + arbitration pass ----
        win2, moved2 = _arbitrate_fused(cfg, st.net, st.rr, xs, ys,
                                        st.fifo_depth, c, kernel_safe)

    with jax.named_scope("step/endpoint"):
        # ---- reverse network: P deliveries are ALWAYS absorbed ----
        rr_rev, rpop, rhas = _finalize(win2[REV], st.rr[REV],
                                       jnp.ones((ny, nx), bool),
                                       kernel_safe)
        rmoved = moved2[:, REV]
        rev_head = (st.net.head[REV] + rpop.astype(I32)) % st.fifo_depth
        rev_count = st.net.count[REV] - rpop.astype(I32)
        absorbed, rpkt = _col(rhas, P, kernel_safe), rmoved[..., P]
        credits = st.credits + absorbed.astype(I32)
        reg_valid = absorbed
        reg_buf = jnp.where(absorbed[None], rpkt, 0)

        # ---- endpoint: inject pending responses into reverse P FIFO ----
        # (folded into the same stacked buffer write as the neighbour
        # enqueues; the neighbour pushes never touch port P, so tails are
        # independent)
        L = cfg.resp_latency
        if L == 1:                    # static fast path: slot is always 0
            slot = jnp.asarray(0, I32)
            slot_oh = None
            inj = st.resp_valid[0]                          # (ny, nx)
            inj_pkt = st.resp_buf[:, 0]                     # (F, ny, nx)
        else:
            slot = (c % L).astype(I32)
            if kernel_safe:
                # one-hot over the (static, small) slot axis instead of a
                # traced-index take: exact select, identical bits
                slot_oh = lax.broadcasted_iota(I32, (L, 1, 1), 0) == slot
                inj = (st.resp_valid & slot_oh).any(0)
                inj_pkt = jnp.where(slot_oh[None], st.resp_buf, 0).sum(1)
            else:
                slot_oh = None
                inj = jnp.take(st.resp_valid, slot, axis=0)
                inj_pkt = jnp.take(st.resp_buf, slot, axis=1)
        rmask_in, rpkt_in = _neighbor_push_masks(rhas, rmoved, inj,
                                                 inj_pkt, cfg.topology,
                                                 kernel_safe)
        rev_tail = (rev_head + rev_count) % st.fifo_depth
        rev_count = rev_count + rmask_in.astype(I32)
        if L == 1:
            resp_valid = jnp.zeros_like(st.resp_valid)
        elif kernel_safe:
            resp_valid = st.resp_valid & ~slot_oh
        else:
            resp_valid = st.resp_valid.at[slot].set(False)
        resp_buf = st.resp_buf

        # ---- endpoint: service one request/cycle (line rate) ------
        resp_inflight = resp_valid.sum(0).astype(I32)
        rev_space = (rev_count[..., P] + resp_inflight) < st.fifo_depth
        can = (st.ep_in.count[..., 0] > 0) & rev_space
        req = _fifo_peek(st.ep_in)[..., 0]                  # (F, ny, nx)
        req_hdr = req[_FI["hdr"]]
        req_op = (req_hdr >> OP_SHIFT) & OP_MASK
        addr = jnp.clip(req[_FI["addr"]], 0, cfg.mem_words - 1)
        mem_onehot = _mem_onehot(cfg.mem_words, kernel_safe)
        # memory stored in rows: one more axis after the tile's
        rows = st.mem.ndim - 3
        with jax.named_scope("mem"):                 # step/endpoint/mem
            if mem_onehot:
                if rows:      # inside the kernel: a word's place in rows
                    word = lax.broadcasted_iota(I32, st.mem.shape, 2) \
                        * MEM_ROW + lax.broadcasted_iota(I32, st.mem.shape, 3)
                    addr_oh = word == addr[..., None, None]
                else:
                    addr_oh = _iota_last(addr.shape, cfg.mem_words,
                                         kernel_safe) == addr[..., None]
                # one-hot read reusing the write mask (exact: int32, one
                # hot bit)
                cur = jnp.where(addr_oh, st.mem, 0).sum(
                    tuple(range(2, st.mem.ndim)))
            else:
                cur = st.mem.at[_mem_at(addr)].get(mode="promise_in_bounds")
        is_store = can & (req_op == OP_STORE)
        is_load = can & (req_op == OP_LOAD)
        is_cas = can & (req_op == OP_CAS)
        cas_hit = is_cas & (cur == req[_FI["cmp"]])
        # the current word wherever nothing is stored
        write = is_store | cas_hit
        newval = jnp.where(write, req[_FI["data"]], cur)
        with jax.named_scope("mem"):
            if mem_onehot:
                can_w = _expand(can, -1, kernel_safe)
                if rows:
                    can_w = _expand(can_w, -1, kernel_safe)
                mem = jnp.where(addr_oh & can_w,
                                newval[(...,) + (None,) * (1 + rows)],
                                st.mem)
            else:
                mem = _mem_store(st.mem, addr, newval, write)
        ep_in = _fifo_pop(st.ep_in, _expand(can, -1, kernel_safe),
                          jnp.asarray(cfg.ep_fifo, I32))
        rdata = jnp.where(is_load | is_cas, cur, 0)
        # build the response packet: src<->dst swapped so it routes home
        resp = jnp.stack([
            swap_for_response(req_hdr, xs, ys),
            req[_FI["addr"]], rdata, req[_FI["cmp"]], req[_FI["tag"]],
        ])
        if L == 1:                    # resp_valid[0] was just cleared above
            resp_valid = can[None]
            resp_buf = jnp.where(can[None, None], resp[:, None], resp_buf)
        elif kernel_safe:
            # refill the just-cleared slot (so `where(can, True, False)`
            # is simply `can`) and overwrite its packet lanes where `can`
            resp_valid = jnp.where(slot_oh, can[None], resp_valid)
            resp_buf = jnp.where(slot_oh[None] & can[None, None],
                                 resp[:, None], resp_buf)
        else:
            wslot = slot          # c % L: inject and refill the same slot
            resp_valid = resp_valid.at[wslot].set(
                jnp.where(can, True, jnp.take(resp_valid, wslot, axis=0)))
            resp_buf = resp_buf.at[:, wslot].set(
                jnp.where(can[None], resp,
                          jnp.take(resp_buf, wslot, axis=1)))

    with jax.named_scope("step/inject"):
        # ---- forward network: P deliveries go to endpoint FIFO ----
        rr_fwd, fpop, fhas = _finalize(win2[FWD], st.rr[FWD],
                                       ep_in.count[..., 0] < cfg.ep_fifo,
                                       kernel_safe)
        fmoved = moved2[:, FWD]
        fwd_head = (st.net.head[FWD] + fpop.astype(I32)) % st.fifo_depth
        fwd_count = st.net.count[FWD] - fpop.astype(I32)
        got, fpkt = _col(fhas, P, kernel_safe), fmoved[..., P]
        ep_in = _fifo_push(ep_in, _expand(got, -1, kernel_safe),
                           fpkt[..., None], jnp.asarray(cfg.ep_fifo, I32),
                           kernel_safe)

        # ---- master injection from the per-tile program -------------
        # The injection enqueue targets port P of the post-pop forward
        # FIFOs (neighbour pushes never touch port P), so it folds into
        # the same stacked buffer write as the neighbour enqueues.
        pending = st.prog_ptr < prog.length
        out_of_credit = st.out_of_credit_cycles + \
            (pending & (credits <= 0)).astype(I32)
        can_inj = pending & (credits > 0)
        Lp = prog.buf.shape[-1]
        pidx = jnp.clip(st.prog_ptr, 0, max(Lp - 1, 0))
        with jax.named_scope("fetch"):               # step/inject/fetch
            if _entry_fetch_onehot(Lp, kernel_safe):
                lp_oh = _iota_last(pidx.shape, Lp, kernel_safe) \
                    == pidx[..., None]
                # (|PROG|, ny, nx); exact: int32, one hot bit
                entry = jnp.where(lp_oh[None], prog.buf, 0).sum(-1)
            else:
                entry = jnp.take_along_axis(
                    prog.buf, jnp.broadcast_to(pidx[None, ..., None],
                                               (len(PROG_FIELDS), ny, nx, 1)),
                    axis=-1)[..., 0]                    # (|PROG|, ny, nx)
        can_inj = can_inj & (entry[_PI["not_before"]] <= c)
        can_inj = can_inj & (fwd_count[..., P] < st.fifo_depth)
        pkt = jnp.stack([
            with_src(entry[_PI["hdr"]], xs, ys),
            entry[_PI["addr"]], entry[_PI["data"]], entry[_PI["cmp"]],
            jnp.full((ny, nx), c, I32),
        ])                                                  # (F, ny, nx)
        fmask_in, fpkt_in = _neighbor_push_masks(fhas, fmoved, can_inj,
                                                 pkt, cfg.topology,
                                                 kernel_safe)
        fwd_tail = (fwd_head + fwd_count) % st.fifo_depth
        fwd_count = fwd_count + fmask_in.astype(I32)
        credits = credits - can_inj.astype(I32)
        prog_ptr = st.prog_ptr + can_inj.astype(I32)

    with jax.named_scope("step/commit"):
        # ---- deferred stacked buffer write: both networks at once ----
        cap = st.net.buf.shape[-1]
        mask2 = jnp.stack([fmask_in, rmask_in])             # (2, ny, nx, 5)
        pkt2 = jnp.stack([fpkt_in, rpkt_in], axis=1)     # (F, 2, ny, nx, 5)
        tail2 = jnp.stack([fwd_tail, rev_tail])
        onehot = (_iota_last(tail2.shape, cap, kernel_safe)
                  == tail2[..., None]) & _expand(mask2, -1, kernel_safe)
        net = Fifo(buf=jnp.where(onehot[None], pkt2[..., None],
                                 st.net.buf),
                   head=jnp.stack([fwd_head, rev_head]),
                   count=jnp.stack([fwd_count, rev_count]))

    with jax.named_scope("step/telemetry"):
        # ---- telemetry: link counts + occupancy high-water marks ------
        link_util = st.link_util + jnp.stack([fhas, rhas]).astype(I32)
        fifo_hwm = jnp.maximum(st.fifo_hwm, net.count)
        ep_hwm = jnp.maximum(st.ep_hwm, ep_in.count[..., 0])

    st = SimState(net=net, ep_in=ep_in,
                  resp_valid=resp_valid, resp_buf=resp_buf, mem=mem,
                  credits=credits, rr=jnp.stack([rr_fwd, rr_rev]),
                  prog_ptr=prog_ptr,
                  reg_valid=reg_valid, reg_buf=reg_buf,
                  completed=completed, lat_sum=lat_sum,
                  out_of_credit_cycles=out_of_credit,
                  cycle=c + 1, fifo_depth=st.fifo_depth,
                  max_credits=st.max_credits,
                  link_util=link_util, fifo_hwm=fifo_hwm,
                  ep_hwm=ep_hwm, lat_hist=lat_hist,
                  measure_start=st.measure_start,
                  measure_stop=st.measure_stop)
    return st, done_now


def _check_impl(impl: str, cycles_per_call: int = 1) -> None:
    if impl not in ("fused", "pallas"):
        raise ValueError(
            f"unknown step impl {impl!r}: expected 'fused' or 'pallas'")
    if cycles_per_call < 1:
        raise ValueError(
            f"cycles_per_call must be >= 1, got {cycles_per_call}")


def step(cfg: SimConfig, prog: Program, st: SimState, impl: str = "fused",
         ) -> Tuple[SimState, jax.Array]:
    """One simulator cycle; returns (state', completions_this_cycle).

    ``impl`` selects how the transition executes — never what it computes:

    * ``"fused"`` — the stacked single-trace XLA step (:func:`_step_core`);
    * ``"pallas"`` — the same transition as one Pallas kernel launch
      (:mod:`repro.kernels.router_step`; compiled on TPU, interpret mode
      in CPU tests).  Bit-identical to ``"fused"`` by construction and by
      test (``tests/test_router_kernel.py``).
    """
    _check_impl(impl)
    if impl == "pallas":
        from repro.kernels.router_step import router_step_call
        st2, done, _drained_flags = router_step_call(cfg, prog, st, 1)
        return st2, done[0]
    return _step_core(cfg, prog, st)


def drained(st: SimState, prog: Program) -> jax.Array:
    """Global-fence condition: programs issued, credits home, nothing in
    the registered response port (same as ``MeshSim.run_until_drained``)."""
    return ((st.prog_ptr >= prog.length).all()
            & (st.credits == st.max_credits).all()
            & ~st.reg_valid.any())


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5, 6),
                   donate_argnums=(2,))
def simulate(cfg: SimConfig, prog: Program, state: SimState, cycles: int,
             unroll: int = 1, impl: str = "fused", cycles_per_call: int = 1,
             ) -> Tuple[SimState, jax.Array]:
    """Run ``cycles`` cycles; returns
    (final_state, completions_per_cycle (cycles,)).

    ``unroll`` is passed to ``lax.scan``: N copies of the cycle step per
    loop iteration trade compile time (more HLO) for lower loop overhead.
    ``impl="pallas"`` runs the cycle transition as the Pallas router
    kernel, ``cycles_per_call`` mesh cycles per kernel launch (a static
    inner ``fori_loop``; dispatch cost is amortized the way ``ssd_scan``
    chunks its recurrence).  All four knobs affect speed only — the
    per-cycle completion trace and final state are bit-identical across
    every (impl, unroll, cycles_per_call) combination.
    ``state`` is donated — do not reuse the argument after the call.
    """
    _check_impl(impl, cycles_per_call)
    if impl == "pallas":
        from repro.kernels.router_step import router_step_call
        C = min(cycles_per_call, cycles) if cycles else 1
        n_full, rem = divmod(cycles, C)

        def body(st, _):
            st2, done, _dr = router_step_call(cfg, prog, st, C)
            return st2, done

        state, dones = lax.scan(body, state, None, length=n_full)
        per_cycle = dones.reshape((-1,))
        if rem:
            state, done_r, _dr = router_step_call(cfg, prog, state, rem)
            per_cycle = jnp.concatenate([per_cycle, done_r])
        return state, per_cycle

    def body(st, _):
        return _step_core(cfg, prog, st)
    return lax.scan(body, state, None, length=cycles, unroll=unroll)


def _drain_loop(cfg: SimConfig, prog: Program, state: SimState,
                max_cycles: int, check_every: int, trace: bool,
                impl: str = "fused", cycles_per_call: int = 1):
    """Shared driver for the two drain entry points: run blocks of
    ``check_every`` cycles, checking the global fence once per block (and
    recording the *exact* fence cycle from inside the block)."""
    K = check_every
    blocks = -(-max_cycles // K)
    c0 = state.cycle
    with jax.named_scope("drain/fence"):
        d0 = jnp.where(drained(state, prog), c0, -1)
    trace0 = jnp.zeros((blocks * K if trace else 1,), I32)

    def cond(carry):
        _st, _tr, i, dcyc = carry
        return (dcyc < 0) & (i < blocks)

    if impl == "pallas":
        from repro.kernels.router_step import router_step_call
        # cover the K-cycle block with kernel launches; a check_every not
        # divisible by cycles_per_call gets a short remainder launch (its
        # own static compilation, shared across blocks)
        C = min(cycles_per_call, K)
        launches = [C] * (K // C) + ([K % C] if K % C else [])

        def body(carry):
            st, tr, i, dcyc = carry
            c_start = st.cycle
            dones, drains = [], []
            for c in launches:
                st, d, dr = router_step_call(cfg, prog, st, c)
                dones.append(d)
                drains.append(dr)
            done_vec = jnp.concatenate(dones)        # (K,)
            drain_vec = jnp.concatenate(drains) > 0  # (K,) post-cycle fence
            if trace:
                with jax.named_scope("drain/trace"):
                    tr = lax.dynamic_update_slice(tr, done_vec, (i * K,))
            # exact fence cycle: first in-block cycle whose post-step
            # fence held (same recording point as the fused inner scan)
            with jax.named_scope("drain/fence"):
                first = jnp.argmax(drain_vec).astype(I32)
                dcyc = jnp.where((dcyc < 0) & drain_vec.any(),
                                 c_start + first + 1, dcyc)
            return st, tr, i + 1, dcyc
    else:
        def body(carry):
            st, tr, i, dcyc = carry

            def inner(c2, j):
                st2, tr2, d2 = c2
                st3, done = _step_core(cfg, prog, st2)
                if trace:
                    with jax.named_scope("drain/trace"):
                        tr2 = tr2.at[i * K + j].set(done)
                with jax.named_scope("drain/fence"):
                    d2 = jnp.where((d2 < 0) & drained(st3, prog),
                                   st3.cycle, d2)
                return (st3, tr2, d2), None

            (st, tr, dcyc), _ = lax.scan(inner, (st, tr, dcyc),
                                         jnp.arange(K, dtype=I32))
            return st, tr, i + 1, dcyc

    final, tr, nblocks, dcyc = lax.while_loop(
        cond, body, (state, trace0, jnp.asarray(0, I32), d0))
    steps = jnp.where(dcyc >= 0, dcyc - c0, nblocks * K)
    return final, steps, dcyc, tr


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5, 6),
                   donate_argnums=(2,))
def run_until_drained(cfg: SimConfig, prog: Program, state: SimState,
                      max_cycles: int = 100_000, check_every: int = 1,
                      impl: str = "fused", cycles_per_call: int = 1,
                      ) -> Tuple[SimState, jax.Array]:
    """Step until the global fence closes (or after ``max_cycles`` further
    steps); returns (final_state, drain_cycle).

    ``check_every=K`` evaluates the fence once per K cycles: fewer
    reductions and a K-step ``scan`` body per ``while_loop`` iteration.
    The returned drain cycle is exact for any K; with K > 1 the *state*
    may have stepped up to K - 1 cycles past the fence (only
    ``SimState.cycle`` advances — a drained network is quiescent).
    ``impl``/``cycles_per_call`` select the Pallas router kernel as in
    :func:`simulate` (exact drain cycle for any combination).
    ``state`` is donated — do not reuse the argument after the call.
    """
    _check_impl(impl, cycles_per_call)
    final, _steps, dcyc, _ = _drain_loop(cfg, prog, state, max_cycles,
                                         check_every, False, impl,
                                         cycles_per_call)
    return final, jnp.where(dcyc >= 0, dcyc, final.cycle)


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5, 6),
                   donate_argnums=(2,))
def run_until_drained_traced(cfg: SimConfig, prog: Program, state: SimState,
                             max_cycles: int = 100_000, check_every: int = 1,
                             impl: str = "fused", cycles_per_call: int = 1,
                             ) -> Tuple[SimState, jax.Array, jax.Array]:
    """Like :func:`run_until_drained` but also records the per-cycle
    completion trace into a preallocated buffer; returns
    (final_state, steps_taken, trace) — ``trace[:steps_taken]`` is valid."""
    _check_impl(impl, cycles_per_call)
    final, steps, _dcyc, tr = _drain_loop(cfg, prog, state, max_cycles,
                                          check_every, True, impl,
                                          cycles_per_call)
    return final, steps, tr


# ----------------------------------------------------------------------
# convenience wrapper mirroring the MeshSim driving API
# ----------------------------------------------------------------------
class JaxMeshSim:
    """Thin stateful wrapper over the functional API, drop-in enough for
    the oracle's driving pattern::

        sim = JaxMeshSim(NetConfig(nx=4, ny=4))
        sim.load_program(prog)
        sim.run(100)            # or sim.run_until_drained()
        sim.mem, sim.completed, sim.completed_per_cycle, ...

    Each ``run*`` call dispatches one jitted XLA program; repeated calls
    with the same static config reuse the compilation cache.

    ``unroll`` / ``check_every`` / ``impl`` / ``cycles_per_call`` are the
    jit tuning knobs of :func:`simulate` / :func:`run_until_drained` (see
    their docstrings); they affect speed only, never results.
    """

    def __init__(self, cfg, fifo_depth=None, max_credits=None, *,
                 unroll: int = 1, check_every: int = 1,
                 impl: str = "fused", cycles_per_call: int = 1):
        if not isinstance(cfg, SimConfig):
            # NetConfig / repro.mesh.MeshConfig share the field names
            cfg = _simconfig_from_net(cfg)
        _check_impl(impl, cycles_per_call)
        self.cfg = cfg
        self.unroll = int(unroll)
        self.check_every = int(check_every)
        self.impl = impl
        self.cycles_per_call = int(cycles_per_call)
        self.state = init_state(cfg, fifo_depth=fifo_depth,
                                max_credits=max_credits)
        self.program = _empty_program_for(cfg)
        self.completed_per_cycle: list = []

    def load_program(self, entries: Dict[str, np.ndarray]) -> None:
        self.program = load_program(entries)
        self.state = self.state._replace(
            prog_ptr=jnp.zeros((self.cfg.ny, self.cfg.nx), I32))

    def run(self, cycles: int) -> None:
        self.state, per_cycle = simulate(self.cfg, self.program, self.state,
                                         cycles, self.unroll, self.impl,
                                         self.cycles_per_call)
        self.completed_per_cycle.extend(np.asarray(per_cycle).tolist())

    def run_until_drained(self, max_cycles: int = 100_000) -> int:
        cycle0 = int(self.state.cycle)
        self.state, steps, trace = run_until_drained_traced(
            self.cfg, self.program, self.state, max_cycles, self.check_every,
            self.impl, self.cycles_per_call)
        steps = int(steps)
        self.completed_per_cycle.extend(np.asarray(trace[:steps]).tolist())
        if steps >= max_cycles and \
                not bool(drained(self.state, self.program)):
            raise RuntimeError(f"network did not drain in {max_cycles} cycles")
        # exact fence cycle even when check_every > 1 overshoots the state
        return cycle0 + steps

    # oracle-shaped accessors -----------------------------------------
    @property
    def mem(self) -> np.ndarray:
        return mem_from_state(self.cfg, self.state.mem)

    @property
    def completed(self) -> np.ndarray:
        return np.asarray(self.state.completed, np.int64)

    @property
    def lat_sum(self) -> np.ndarray:
        return np.asarray(self.state.lat_sum, np.int64)

    @property
    def credits(self) -> np.ndarray:
        return np.asarray(self.state.credits, np.int64)

    @property
    def out_of_credit_cycles(self) -> np.ndarray:
        return np.asarray(self.state.out_of_credit_cycles, np.int64)

    # telemetry ---------------------------------------------------------
    @property
    def link_util_fwd(self) -> np.ndarray:
        return np.asarray(self.state.link_util[FWD], np.int64)

    @property
    def link_util_rev(self) -> np.ndarray:
        return np.asarray(self.state.link_util[REV], np.int64)

    @property
    def fifo_hwm_fwd(self) -> np.ndarray:
        return np.asarray(self.state.fifo_hwm[FWD], np.int64)

    @property
    def fifo_hwm_rev(self) -> np.ndarray:
        return np.asarray(self.state.fifo_hwm[REV], np.int64)

    @property
    def ep_hwm(self) -> np.ndarray:
        return np.asarray(self.state.ep_hwm, np.int64)

    @property
    def lat_hist(self) -> np.ndarray:
        return np.asarray(self.state.lat_hist, np.int64)

    def set_measure_window(self, start: int, stop: int) -> None:
        """Restrict the latency histogram to packets *injected* in cycle
        range [start, stop) — same contract as ``MeshSim.set_measure_window``."""
        self.state = self.state._replace(
            measure_start=jnp.asarray(start, I32),
            measure_stop=jnp.asarray(stop, I32))

    @property
    def cycle(self) -> int:
        return int(self.state.cycle)

    def mean_latency(self) -> float:
        done = int(self.completed.sum())
        return float(self.lat_sum.sum()) / max(done, 1)

    def throughput(self, warmup: int = 0) -> float:
        per = self.completed_per_cycle[warmup:]
        return float(np.sum(per)) / max(len(per), 1)
