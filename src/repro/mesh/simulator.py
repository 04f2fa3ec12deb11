"""The backend-agnostic mesh simulator facade.

One front door for every way of driving the mesh::

    from repro.mesh import MeshConfig, Simulator, make_traffic

    sim = Simulator(MeshConfig(nx=8, ny=8), backend="jax")
    sim.attach(make_traffic("uniform", 8, 8, 64, rate=0.5))   # a program
    sim.run_until_drained()
    t = sim.telemetry()          # Telemetry — bit-identical across backends

or, attaching a *reactive user design* (the paper's integration story)::

    from repro.mesh import DmaEndpoint, Simulator

    sim = Simulator(cfg)                      # numpy oracle backend
    sim.attach(DmaEndpoint(dst_x=3, dst_y=2, data=range(16)), at=(0, 0))
    sim.run_until_drained()

Backends:

* ``backend="numpy"`` — :class:`repro.core.netsim.MeshSim`, the oracle.
  Reactive endpoints run *natively*: each cycle the facade delivers any
  registered response to its endpoint, then the router step asks every
  ready endpoint for an offer.
* ``backend="jax"`` — :class:`repro.netsim_jax.JaxMeshSim` (imported
  lazily so the oracle works without the JAX stack warmed up).  Injection
  programs run directly under ``jit``.  Reactive endpoints run through
  the **trace-to-program bridge**: the scenario executes once on an
  internal oracle (recording the exact injection cycle of every packet),
  and the resulting ``not_before``-pinned program replays bit-identically
  on the JAX path — so endpoint-driven scenarios still compile, and their
  exported programs (:meth:`injection_trace_program`) ``vmap`` into
  sweeps.

Everything not defined here (``mem``, ``credits``, ``lat_hist``,
``throughput()``, ...) transparently delegates to the backend object, so
the facade satisfies the same oracle-shaped contract the differential
suites check.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.netsim import MeshSim, _PKT_FIELDS

from .config import MeshConfig
from .encoding import validate_program
from .endpoint import Endpoint, Request, Response, trace_to_program
from .telemetry import Telemetry

__all__ = ["Simulator", "BACKENDS"]

BACKENDS = ("numpy", "jax")


class Simulator:
    """Backend-agnostic facade over the two cycle-level simulators."""

    def __init__(self, cfg, *, backend: str = "numpy", seed: int = 0,
                 fifo_depth: Optional[int] = None,
                 max_credits: Optional[int] = None,
                 unroll: int = 1, check_every: int = 1,
                 impl: str = "fused", cycles_per_call: int = 1):
        """``cfg`` may be a MeshConfig, NetConfig or SimConfig.

        ``fifo_depth`` / ``max_credits`` set the *effective* router-FIFO
        depth and credit allowance below the config's capacities — on the
        JAX backend they stay dynamic state (so sweeps vmap without
        recompiling); the numpy oracle folds them into its config, which
        is dynamics-identical.

        ``unroll`` / ``check_every`` / ``impl`` / ``cycles_per_call`` are
        JAX-backend jit tuning knobs (scan-unroll factor of ``run``;
        drain-fence check cadence of ``run_until_drained``; the
        fused-XLA vs Pallas-kernel cycle step and the kernel's
        cycles-per-launch — see :func:`repro.netsim_jax.simulate` /
        :func:`repro.netsim_jax.run_until_drained`).  They affect speed
        only, never results; the numpy oracle ignores them.
        """
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r}; known: {BACKENDS}")
        if unroll < 1 or check_every < 1:
            raise ValueError(
                f"unroll and check_every must be >= 1, got unroll={unroll}, "
                f"check_every={check_every}")
        if impl not in ("fused", "pallas"):
            raise ValueError(
                f"unknown step impl {impl!r}: expected 'fused' or 'pallas'")
        if cycles_per_call < 1:
            raise ValueError(
                f"cycles_per_call must be >= 1, got {cycles_per_call}")
        self.cfg = MeshConfig.coerce(cfg)
        self.backend = backend
        self._seed = seed
        self._fifo_depth = fifo_depth
        self._max_credits = max_credits
        self._unroll = int(unroll)
        self._check_every = int(check_every)
        self._impl = impl
        self._cycles_per_call = int(cycles_per_call)
        self._endpoints: Dict[Tuple[int, int], Endpoint] = {}  # (y, x) -> ep
        self._trace: List[Tuple[int, int, int, Request]] = []
        self._program: Optional[Dict[str, np.ndarray]] = None
        self._mem0: Optional[np.ndarray] = None
        self._window: Optional[Tuple[int, int]] = None
        self._oracle: Optional["Simulator"] = None   # jax+endpoints bridge
        self._sim = self._make_backend()

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    def _effective_cfg(self) -> MeshConfig:
        cfg = self.cfg
        if self._fifo_depth is not None:
            cfg = cfg.replace(router_fifo=int(self._fifo_depth))
        if self._max_credits is not None:
            cfg = cfg.replace(max_out_credits=int(self._max_credits))
        return cfg

    def _make_backend(self):
        if self.backend == "numpy":
            # effective values fold into the oracle's config (identical
            # dynamics; the capacity/effective split is a JAX vmap affordance)
            return MeshSim(self._effective_cfg().to_net(), seed=self._seed)
        from repro.netsim_jax.sim import JaxMeshSim
        return JaxMeshSim(self.cfg.to_sim(), fifo_depth=self._fifo_depth,
                          max_credits=self._max_credits,
                          unroll=self._unroll,
                          check_every=self._check_every,
                          impl=self._impl,
                          cycles_per_call=self._cycles_per_call)

    def _bridge(self) -> "Simulator":
        """The internal oracle that natively executes reactive endpoints
        for the JAX backend (created on first endpoint attach)."""
        if self._oracle is None:
            self._oracle = Simulator(self._effective_cfg(),
                                     backend="numpy", seed=self._seed)
            if self._mem0 is not None:
                self._oracle.set_mem(self._mem0)
            if self._window is not None:
                self._oracle.set_measure_window(*self._window)
            if self._program is not None:
                self._oracle.attach(self._program)
        return self._oracle

    # ------------------------------------------------------------------
    # attachment
    # ------------------------------------------------------------------
    def attach(self, item, at: Optional[Tuple[int, int]] = None) -> "Simulator":
        """Attach a master to the mesh and return ``self`` (chainable).

        * a dict injection program (the ``make_traffic`` schema) loads on
          every tile at once;
        * an :class:`Endpoint` attaches to the single tile ``at=(x, y)``.
        """
        if isinstance(item, dict):
            if at is not None:
                raise ValueError(
                    "a program drives every tile; 'at' only applies to "
                    "endpoint attachment")
            self._attach_program(item)
            return self
        if not isinstance(item, Endpoint):
            raise TypeError(
                f"cannot attach {type(item).__name__}: expected an injection"
                " program dict or an object with offer/deliver/done")
        if at is None:
            raise ValueError(
                "attaching an endpoint needs its tile: attach(ep, at=(x, y))")
        x, y = at
        if not (0 <= x < self.cfg.nx and 0 <= y < self.cfg.ny):
            raise ValueError(
                f"endpoint tile (x={x}, y={y}) is outside the "
                f"{self.cfg.nx}x{self.cfg.ny} mesh")
        if (y, x) in self._endpoints:
            raise ValueError(
                f"tile (x={x}, y={y}) already has an endpoint attached; "
                "a tile has one master")
        if self.backend == "jax" and self._cycles_run() > 0:
            raise ValueError(
                "cannot attach an endpoint to a jax-backend Simulator that "
                "has already run: the trace-to-program bridge replays the "
                "scenario from cycle 0, which would drop the pre-attach "
                "history; attach endpoints before running (the numpy "
                "backend supports mid-run attachment natively)")
        if self._program is not None and \
                (np.asarray(self._program["op"])[y, x] >= 0).any():
            raise ValueError(
                f"tile (x={x}, y={y}) already has injection-program "
                "entries; a tile has one master")
        self._endpoints[(y, x)] = item
        if self.backend == "numpy":
            self._sim._injectors[(y, x)] = self._traced_offer(y, x, item)
        else:
            self._bridge().attach(item, at=at)
        return self

    def _attach_program(self, entries: Dict[str, np.ndarray]) -> None:
        # one packet-domain contract for BOTH backends: coordinates and
        # opcode must fit the packed header widths (and the mesh), payload
        # lanes must fit int32 — the error names the offending field
        validate_program(entries, nx=self.cfg.nx, ny=self.cfg.ny,
                         topology=self.cfg.topology)
        op = np.asarray(entries["op"])
        for (y, x) in self._endpoints:
            if (op[y, x] >= 0).any():
                raise ValueError(
                    f"tile (x={x}, y={y}) is driven by an endpoint but the "
                    "program has entries there; a tile has one master")
        self._program = {k: np.asarray(v).copy() for k, v in entries.items()}
        if self.backend == "jax" and self._endpoints:
            # bridge mode replays from cycle 0, so a program arriving after
            # cycles have run would be scheduled earlier than it was seen
            if self._cycles_run() > 0:
                raise ValueError(
                    "cannot attach a program to an endpoint-driven "
                    "jax-backend Simulator that has already run: the "
                    "trace-to-program bridge replays from cycle 0; attach "
                    "everything before running")
            self._bridge().attach(self._program)
        else:
            self._sim.load_program(
                {k: v.copy() for k, v in self._program.items()})

    # program-compatibility alias (load_program(prog) == attach(prog))
    def load_program(self, entries: Dict[str, np.ndarray]) -> None:
        self.attach(entries)

    def _traced_offer(self, y: int, x: int, ep: Endpoint):
        def offer(cycle: int, credits: int) -> Optional[Request]:
            req = ep.offer(cycle, credits)
            if req is not None:
                self._trace.append((y, x, cycle, req))
            return req
        return offer

    # ------------------------------------------------------------------
    # state seeding
    # ------------------------------------------------------------------
    def set_mem(self, mem: np.ndarray) -> None:
        """Initialize every tile's local memory, shape (ny, nx, mem_words)
        — e.g. to seed the pointer chains a memory-controller endpoint
        chases."""
        cfg = self.cfg
        mem = np.asarray(mem)
        if mem.shape != (cfg.ny, cfg.nx, cfg.mem_words):
            raise ValueError(
                f"memory image must be shaped (ny={cfg.ny}, nx={cfg.nx}, "
                f"mem_words={cfg.mem_words}), got {mem.shape}")
        self._mem0 = mem.astype(np.int64)
        if self.backend == "numpy":
            self._sim.mem[:] = self._mem0
        else:
            from repro.netsim_jax.sim import mem_to_state
            self._sim.state = self._sim.state._replace(
                mem=mem_to_state(self._sim.cfg, self._mem0))
            if self._oracle is not None:
                self._oracle.set_mem(self._mem0)

    def set_measure_window(self, start: int, stop: int) -> None:
        """Restrict the latency histogram to packets *injected* in cycle
        range [start, stop) — same contract on every backend."""
        self._window = (int(start), int(stop))
        self._sim.set_measure_window(start, stop)
        if self._oracle is not None:
            self._oracle.set_measure_window(start, stop)

    def _cycles_run(self) -> int:
        """Total scenario cycles executed so far (the bridge oracle's view
        when endpoints run on the jax backend)."""
        src = self._oracle if self._oracle is not None else self
        return int(src._sim.cycle)

    # ------------------------------------------------------------------
    # running
    # ------------------------------------------------------------------
    def step(self) -> None:
        """Advance one cycle (numpy backend only — the jit path dispatches
        whole runs).  With endpoints attached this is the reactive step:
        responses are delivered before the router cycle, exactly as in
        :meth:`run`, so manual stepping never starves a request/reply
        endpoint."""
        if self.backend != "numpy":
            raise NotImplementedError(
                "cycle-by-cycle stepping is a numpy-backend feature; the "
                "jax backend dispatches whole runs — use run(cycles)")
        if self._endpoints:
            self._step_reactive()
        else:
            self._sim.step()

    def run(self, cycles: int) -> None:
        """Advance ``cycles`` cycles."""
        if not self._endpoints:
            self._sim.run(cycles)
            return
        if self.backend == "numpy":
            for _ in range(cycles):
                self._step_reactive()
            return
        self._bridge().run(cycles)
        self._replay(drained=False)

    def run_until_drained(self, max_cycles: int = 100_000) -> int:
        """Run until the global fence closes — programs fully issued,
        every endpoint ``done()``, all credits home and the registered
        response port idle; returns the drain cycle."""
        if not self._endpoints:
            return self._sim.run_until_drained(max_cycles)
        if self.backend == "numpy":
            for _ in range(max_cycles):
                if self._reactive_drained():
                    return int(self._sim.cycle)
                self._step_reactive()
            raise RuntimeError(
                f"network did not drain in {max_cycles} cycles")
        n = self._bridge().run_until_drained(max_cycles)
        self._replay(drained=True, max_cycles=max_cycles)
        return n

    def _step_reactive(self) -> None:
        """One oracle cycle with the reverse link serviced: deliver any
        registered response to its endpoint (the sink rule — the endpoint
        cannot refuse), then step; offers happen inside the step at the
        injection stage, exactly where program injection lives."""
        sim = self._sim
        rv = sim.reg_valid
        if rv.any():
            c = int(sim.cycle)
            for (y, x), ep in self._endpoints.items():
                if rv[y, x]:
                    p = sim.reg_pkt
                    ep.deliver(Response(
                        op=int(p["op"][y, x]), addr=int(p["addr"][y, x]),
                        data=int(p["data"][y, x]),
                        src_x=int(p["src_x"][y, x]),
                        src_y=int(p["src_y"][y, x]),
                        tag=int(p["tag"][y, x]), cycle=c))
        sim.step()

    def _reactive_drained(self) -> bool:
        sim = self._sim
        return (all(ep.done() for ep in self._endpoints.values())
                and bool((sim.prog_ptr >= sim.prog_len).all())
                and bool((sim.credits == sim.cfg.max_out_credits).all())
                and not bool(sim.reg_valid.any()))

    # ------------------------------------------------------------------
    # the trace -> program bridge (jax backend with endpoints)
    # ------------------------------------------------------------------
    def injection_trace_program(self) -> Dict[str, np.ndarray]:
        """The injection program equivalent to everything injected so far
        (endpoint offers pinned to their recorded cycles, merged with any
        attached base program).  Replayable on either backend — and
        stackable for ``vmap`` sweeps."""
        oracle = self._oracle if self._oracle is not None else self
        return trace_to_program(oracle._trace, self.cfg.nx, self.cfg.ny,
                                base=self._program)

    def _replay(self, drained: bool, max_cycles: int = 100_000) -> None:
        """Re-run the oracle-traced scenario on a fresh JAX state."""
        prog = self.injection_trace_program()
        self._sim = self._make_backend()
        if self._mem0 is not None:
            from repro.netsim_jax.sim import mem_to_state
            self._sim.state = self._sim.state._replace(
                mem=mem_to_state(self._sim.cfg, self._mem0))
        if self._window is not None:
            self._sim.set_measure_window(*self._window)
        self._sim.load_program(prog)
        if drained:
            self._sim.run_until_drained(max_cycles)
        else:
            self._sim.run(int(self._oracle.cycle))

    # ------------------------------------------------------------------
    # results
    # ------------------------------------------------------------------
    def telemetry(self) -> Telemetry:
        """The unified, backend-bit-identical telemetry record."""
        return Telemetry.of(self._sim)

    @property
    def endpoints(self) -> Dict[Tuple[int, int], Endpoint]:
        """Attached endpoints, keyed (x, y)."""
        return {(x, y): ep for (y, x), ep in self._endpoints.items()}

    def __getattr__(self, name):
        # oracle-shaped passthrough (mem, credits, lat_hist, throughput,
        # mean_latency, cycle, ...) — keeps the facade drop-in for the
        # differential suites' state assertions
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self._sim, name)

    def __repr__(self) -> str:
        return (f"Simulator({self.cfg.nx}x{self.cfg.ny}, "
                f"backend={self.backend!r}, "
                f"endpoints={len(self._endpoints)}, "
                f"program={'yes' if self._program is not None else 'no'})")


# re-exported for facade users who want the raw packet fields
PKT_FIELDS = _PKT_FIELDS
