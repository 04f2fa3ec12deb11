"""The plain reference: a cycle-level model of the BaseJump mesh in numpy.

A copy of the repo's numpy oracle (``repro.core.netsim.MeshSim``) cut to
what the benchmark's configurations use: the plain mesh, program-driven
tiles, the standard endpoint.  It imports nothing of the program.  The
semantics it fixes, which the device path must match bit for bit:

* 5-port routers (P/W/E/N/S) with input FIFOs and no output FIFOs; every
  FIFO crossing costs one cycle;
* round-robin arbitration per output port, head-of-line blocking;
* XY dimension-ordered routing;
* a forward (request) and a reverse (response) network; the reverse
  network is a sink;
* standard endpoints with ``max_out_credits`` credits, an input FIFO of
  ``ep_fifo`` entries, line-rate service of remote load/store/CAS, and a
  registered response port, so the unloaded 1-hop round trip is 7 cycles.

``registered_response=False`` is the control: it drops the registered
response port, so a response counts in the cycle the reverse network
delivers it and the 1-hop round trip is 6 cycles.  It breaks one
guarantee the configurations state and must fail the comparison.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

P, W, E, N, S = 0, 1, 2, 3, 4
NUM_DIRS = 5
LAT_BINS = 512
NO_MEASURE = 2**31 - 1
OP_LOAD, OP_STORE, OP_CAS = 0, 1, 2
TELEMETRY_FIELDS = ("completed", "lat_sum", "completed_per_cycle",
                    "link_util_fwd", "link_util_rev", "fifo_hwm_fwd",
                    "fifo_hwm_rev", "ep_hwm", "lat_hist")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    nx: int
    ny: int
    router_fifo: int = 4
    ep_fifo: int = 4
    max_out_credits: int = 16
    mem_words: int = 64
    registered_response: bool = True


# packet fields, the last axis of every packet array
DX, DY, SX, SY, ADDR, DATA, CMP, OP, TAG = range(9)
NUM_FIELDS = 9
_PROG = ("dst_x", "dst_y", "addr", "data", "cmp", "op", "not_before")


class _Fifos:
    """Circular FIFOs: packets (ny, nx, ports, depth, fields)."""

    def __init__(self, ny, nx, ports, depth):
        self.depth = depth
        self.buf = np.zeros((ny, nx, ports, depth, NUM_FIELDS), np.int64)
        self.head = np.zeros((ny, nx, ports), np.int64)
        self.count = np.zeros((ny, nx, ports), np.int64)
        self._idx = np.ogrid[0:ny, 0:nx, 0:ports]

    def peek(self):
        """Head packet of every FIFO, (ny, nx, ports, fields)."""
        iy, ix, ip = self._idx
        return self.buf[iy, ix, ip, self.head % self.depth]

    def pop_mask(self, mask):
        m = mask.astype(np.int64)
        self.head = (self.head + m) % self.depth
        self.count = self.count - m

    def push_mask(self, mask, pkt):
        """Enqueue ``pkt`` (broadcast to (ny, nx, ports, fields)) where
        ``mask`` (ny, nx, ports); the caller has checked for space."""
        iy, ix, ip = np.nonzero(mask)
        if iy.size:
            tail = (self.head[iy, ix, ip] + self.count[iy, ix, ip]) % self.depth
            pkt = np.broadcast_to(pkt, mask.shape + (NUM_FIELDS,))
            self.buf[iy, ix, ip, tail] = pkt[iy, ix, ip]
            self.count[iy, ix, ip] += 1

    def space(self):
        return self.count < self.depth


class MeshSim:
    """Forward and reverse networks, endpoints and tile memories."""

    def __init__(self, cfg: MeshConfig):
        self.cfg = cfg
        ny, nx = cfg.ny, cfg.nx
        self.cycle = 0
        self.fwd = _Fifos(ny, nx, NUM_DIRS, cfg.router_fifo)
        self.rev = _Fifos(ny, nx, NUM_DIRS, cfg.router_fifo)
        self.ep_in = _Fifos(ny, nx, 1, cfg.ep_fifo)
        self.resp_valid = np.zeros((ny, nx), bool)
        self.resp_pkt = np.zeros((ny, nx, NUM_FIELDS), np.int64)
        self.mem = np.zeros((ny, nx, cfg.mem_words), np.int64)
        self.credits = np.full((ny, nx), cfg.max_out_credits, np.int64)
        self.rr = np.zeros((ny, nx, NUM_DIRS), np.int64)
        self.rr_rev = np.zeros((ny, nx, NUM_DIRS), np.int64)
        self.prog = np.zeros((ny, nx, 1, len(_PROG)), np.int64)
        self.prog_len = np.zeros((ny, nx), np.int64)
        self.prog_ptr = np.zeros((ny, nx), np.int64)
        self.reg_valid = np.zeros((ny, nx), bool)
        self.reg_pkt = np.zeros((ny, nx, NUM_FIELDS), np.int64)
        self.completed = np.zeros((ny, nx), np.int64)
        self.lat_sum = np.zeros((ny, nx), np.int64)
        self.completed_per_cycle = []
        self.link_util_fwd = np.zeros((ny, nx, NUM_DIRS), np.int64)
        self.link_util_rev = np.zeros((ny, nx, NUM_DIRS), np.int64)
        self.fifo_hwm_fwd = np.zeros((ny, nx, NUM_DIRS), np.int64)
        self.fifo_hwm_rev = np.zeros((ny, nx, NUM_DIRS), np.int64)
        self.ep_hwm = np.zeros((ny, nx), np.int64)
        self.lat_hist = np.zeros(LAT_BINS, np.int64)
        self.measure_start = 0
        self.measure_stop = NO_MEASURE
        self._ys, self._xs = np.mgrid[0:ny, 0:nx]
        self._iy, self._ix = np.ogrid[0:ny, 0:nx]

    def load_program(self, entries: Dict[str, np.ndarray]) -> None:
        """``entries`` fields shaped (ny, nx, L); ``op`` < 0 is padding."""
        self.prog = np.stack([np.asarray(entries[k], np.int64)
                              for k in _PROG], -1)
        self.prog_len = (self.prog[..., 5] >= 0).sum(-1).astype(np.int64)
        self.prog_ptr = np.zeros_like(self.prog_len)

    def set_measure_window(self, start: int, stop: int) -> None:
        """Histogram only packets injected in cycles [start, stop)."""
        self.measure_start, self.measure_stop = int(start), int(stop)

    def _router_step(self, net, rr, deliver_space, link_util):
        """One cycle of every router of one network; returns the packets
        delivered out of the P port and which tiles delivered one."""
        ny, nx = self.cfg.ny, self.cfg.nx
        heads = net.peek()
        valid = net.count > 0
        x, y = self._xs[..., None], self._ys[..., None]
        dx, dy = heads[..., DX], heads[..., DY]
        want = np.where(dx > x, E, np.where(dx < x, W, np.where(
            dy > y, S, np.where(dy < y, N, P))))
        space = net.space()
        out_space = np.zeros((ny, nx, NUM_DIRS), bool)
        out_space[..., P] = deliver_space
        out_space[:, :-1, E] = space[:, 1:, W]
        out_space[:, 1:, W] = space[:, :-1, E]
        out_space[:-1, :, S] = space[1:, :, N]
        out_space[1:, :, N] = space[:-1, :, S]

        # round robin: each output port takes the valid requester with the
        # least (in_port - rr[o]) mod 5, then moves its pointer past it;
        # axes (ny, nx, in_port, out_port)
        ports = np.arange(NUM_DIRS)
        cand = (valid[..., None] & (want[..., None] == ports)
                & out_space[..., None, :])
        prio = (ports[:, None] - rr[..., None, :]) % NUM_DIRS
        prio = np.where(cand, prio, NUM_DIRS + 1)
        winners = np.where(prio.min(2) <= NUM_DIRS, prio.argmin(2), -1)
        rr[...] = np.where(winners >= 0, (winners + 1) % NUM_DIRS, rr)
        has = winners >= 0
        link_util += has
        # a head packet wants one output, so it wins at most one
        net.pop_mask((winners[..., None, :] == ports[:, None]).any(-1))
        out = np.take_along_axis(
            heads, np.clip(winners, 0, NUM_DIRS - 1)[..., None], axis=2)

        # each input FIFO of a neighbour has exactly one feeder
        inmask = np.zeros((ny, nx, NUM_DIRS), bool)
        inpkt = np.zeros((ny, nx, NUM_DIRS, NUM_FIELDS), np.int64)
        for o, i, dst, src in ((E, W, np.s_[:, 1:], np.s_[:, :-1]),
                               (W, E, np.s_[:, :-1], np.s_[:, 1:]),
                               (S, N, np.s_[1:, :], np.s_[:-1, :]),
                               (N, S, np.s_[:-1, :], np.s_[1:, :])):
            inmask[dst + (i,)] = has[src + (o,)]
            inpkt[dst + (i,)] = out[src + (o,)]
        net.push_mask(inmask, inpkt)
        return has[..., P], out[..., P, :]

    def _record(self, valid, pkt, c):
        """Count the responses ``valid`` that the cores see in cycle ``c``."""
        self.completed += valid
        tag = pkt[..., TAG]
        lat = c - tag
        self.lat_sum += np.where(valid, lat, 0)
        in_win = valid & (tag >= self.measure_start) & (tag < self.measure_stop)
        if in_win.any():
            np.add.at(self.lat_hist, np.clip(lat[in_win], 0, LAT_BINS - 1), 1)
        return int(valid.sum())

    def step(self) -> None:
        cfg = self.cfg
        ny, nx = cfg.ny, cfg.nx
        c = self.cycle
        ports = np.arange(NUM_DIRS)

        # the registered response port becomes visible
        done = 0
        if cfg.registered_response and self.reg_valid.any():
            done = self._record(self.reg_valid, self.reg_pkt, c)
        self.reg_valid = np.zeros((ny, nx), bool)

        # reverse network; P deliveries are always absorbed
        absorbed, rpkt = self._router_step(
            self.rev, self.rr_rev, np.ones((ny, nx), bool), self.link_util_rev)
        self.credits += absorbed.astype(np.int64)
        if cfg.registered_response:
            self.reg_valid = absorbed
            self.reg_pkt = np.where(absorbed[..., None], rpkt, 0)
        elif absorbed.any():
            done = self._record(absorbed, rpkt, c)
        self.completed_per_cycle.append(done)

        # the endpoint injects last cycle's response into the reverse P FIFO
        if self.resp_valid.any():
            self.rev.push_mask(self.resp_valid[..., None] & (ports == P),
                               self.resp_pkt[:, :, None, :])
            self.resp_valid = np.zeros((ny, nx), bool)

        # the endpoint serves one request per cycle, only when the reverse
        # channel can take its response
        can = (self.ep_in.count[..., 0] > 0) & \
            (self.rev.count[..., P] < self.rev.depth)
        if can.any():
            req = self.ep_in.peek()[:, :, 0, :]
            addr = np.clip(req[..., ADDR], 0, cfg.mem_words - 1)
            cur = self.mem[self._ys, self._xs, addr]
            op = req[..., OP]
            is_store = can & (op == OP_STORE)
            is_load = can & (op == OP_LOAD)
            is_cas = can & (op == OP_CAS)
            cas_hit = is_cas & (cur == req[..., CMP])
            newval = np.where(is_store | cas_hit, req[..., DATA], cur)
            self.mem[self._ys, self._xs, addr] = np.where(can, newval, cur)
            self.ep_in.pop_mask(can[..., None])
            # the response swaps source and destination so it routes home;
            # loads and CAS return the old value, stores a credit
            resp = req.copy()
            resp[..., DX], resp[..., DY] = req[..., SX], req[..., SY]
            resp[..., SX], resp[..., SY] = self._xs, self._ys
            resp[..., DATA] = np.where(is_load | is_cas, cur, 0)
            self.resp_pkt = np.where(can[..., None], resp, self.resp_pkt)
            self.resp_valid = can

        # forward network; P deliveries go to the endpoint FIFO
        got, fpkt = self._router_step(self.fwd, self.rr,
                                      self.ep_in.space()[..., 0],
                                      self.link_util_fwd)
        if got.any():
            self.ep_in.push_mask(got[..., None], fpkt[:, :, None, :])

        # injection from the per-tile program
        can_inj = (self.prog_ptr < self.prog_len) & (self.credits > 0)
        if can_inj.any():
            pidx = np.clip(self.prog_ptr, 0, self.prog.shape[2] - 1)
            entry = self.prog[self._iy, self._ix, pidx]
            can_inj &= entry[..., 6] <= c
            can_inj &= self.fwd.space()[..., P]
            if can_inj.any():
                pkt = np.stack([entry[..., 0], entry[..., 1], self._xs,
                                self._ys, entry[..., 2], entry[..., 3],
                                entry[..., 4], entry[..., 5],
                                np.full((ny, nx), c)], -1).astype(np.int64)
                self.fwd.push_mask(can_inj[..., None] & (ports == P),
                                   pkt[:, :, None, :])
                self.credits -= can_inj.astype(np.int64)
                self.prog_ptr += can_inj.astype(np.int64)

        # FIFO occupancy high-water marks at the cycle edge
        np.maximum(self.fifo_hwm_fwd, self.fwd.count, out=self.fifo_hwm_fwd)
        np.maximum(self.fifo_hwm_rev, self.rev.count, out=self.fifo_hwm_rev)
        np.maximum(self.ep_hwm, self.ep_in.count[..., 0], out=self.ep_hwm)
        self.cycle += 1

    def run(self, cycles: int) -> None:
        for _ in range(cycles):
            self.step()

    def run_until_drained(self, max_cycles: int) -> int:
        """Run until every program has issued and every credit is home
        (the global fence); returns that cycle."""
        for _ in range(max_cycles):
            if (self.prog_ptr >= self.prog_len).all() and \
               (self.credits == self.cfg.max_out_credits).all() and \
               not self.reg_valid.any():
                return self.cycle
            self.step()
        raise RuntimeError(f"network did not drain in {max_cycles} cycles")

    def telemetry(self) -> Dict[str, np.ndarray]:
        out = {f: np.asarray(getattr(self, f), np.int64)
               for f in TELEMETRY_FIELDS}
        out["cycles"] = np.asarray(self.cycle, np.int64)
        return out


def drain(cfg: MeshConfig, entries, max_cycles: int):
    """The drain cycle and the whole telemetry of one drained program."""
    sim = MeshSim(cfg)
    sim.load_program(entries)
    cycle = sim.run_until_drained(max_cycles)
    return cycle, sim.telemetry()


def phased(cfg: MeshConfig, entries, warmup: int, measure: int, drain: int):
    """Warmup, measurement window, drain: the window's raw counts.

    Returns exact integers: ``d_inj``/``d_comp`` (packets injected and
    completed during the window), ``d_util`` (2, ny, nx, 5) link
    crossings of the forward and reverse networks during the window, and
    ``hist``, the latency histogram of the packets injected in the
    window, delivered by the end of the drain."""
    sim = MeshSim(cfg)
    sim.load_program(entries)
    sim.set_measure_window(warmup, warmup + measure)

    def snap():
        return (int(sim.prog_ptr.sum()), int(sim.completed.sum()),
                np.stack([sim.link_util_fwd, sim.link_util_rev]).copy())
    sim.run(warmup)
    inj0, comp0, util0 = snap()
    sim.run(measure)
    inj1, comp1, util1 = snap()
    sim.run(drain)
    return {"d_inj": inj1 - inj0, "d_comp": comp1 - comp0,
            "d_util": util1 - util0, "hist": sim.lat_hist.copy()}


def replay(case: dict, registered_response: bool = True):
    """Rebuild one checked item's program with the benchmark's own
    generator and run it here.  ``case`` names the mesh, the traffic and
    either ``max_cycles`` (a drain) or the three phases."""
    from bench import patterns
    cfg = MeshConfig(case["nx"], case["ny"], case["router_fifo"],
                     case["ep_fifo"], case["max_out_credits"],
                     case["mem_words"], registered_response)
    entries = patterns.make_traffic(
        case["pattern"], case["nx"], case["ny"], case["length"],
        rate=case["rate"], seed=case["seed"], mem_words=case["mem_words"])
    if "max_cycles" in case:
        return drain(cfg, entries, case["max_cycles"])
    return phased(cfg, entries, case["warmup"], case["measure"],
                  case["drain"])


def replay_all(cases, registered_response: bool = True, workers: int = 1):
    """``replay`` every case, ``workers`` processes at a time."""
    if workers <= 1 or len(cases) <= 1:
        return [replay(c, registered_response) for c in cases]
    import concurrent.futures
    import multiprocessing
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(
            max_workers=min(workers, len(cases)), mp_context=ctx) as pool:
        return list(pool.map(replay, cases,
                             [registered_response] * len(cases)))
