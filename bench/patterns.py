"""Synthetic NoC traffic patterns: the benchmark's own copy.

A copy of the pattern generators of ``repro.mesh.traffic`` on the plain
mesh, kept here so that a change to the program cannot move the
yardstick: the drain cell builds its jobs with it, and the reference
rebuilds every program it replays with it.  ``bench/tests`` checks that
it gives the same programs as the program's generator.

Every generator returns an injection program: a dict of ``(ny, nx,
length)`` int64 arrays (``dst_x, dst_y, addr, data, cmp, op,
not_before``).  The injection rate r (packets/cycle/tile) is enforced
with ``not_before``: entry ``i`` may not inject before cycle
``floor(i / r)``.
"""
from __future__ import annotations

import math

import numpy as np

OP_STORE = 1
PROG_KEYS = ("dst_x", "dst_y", "addr", "data", "cmp", "op", "not_before")


def _base(nx, ny, length, rate, op, mem_words, seed):
    if not 0.0 < rate <= 1.0:
        raise ValueError(f"injection rate must be in (0, 1], got {rate}")
    prog = {k: np.zeros((ny, nx, length), np.int64) for k in PROG_KEYS}
    i = np.arange(length)
    prog["op"][:] = op
    prog["addr"][:] = i % mem_words
    prog["data"][:] = np.arange(ny * nx * length).reshape(ny, nx, length)
    prog["not_before"][:] = np.floor(i / rate).astype(np.int64)
    return prog, np.random.default_rng(seed)


def uniform(nx, ny, length, rate, op, mem_words, seed):
    """Every packet targets a uniformly random other tile."""
    prog, rng = _base(nx, ny, length, rate, op, mem_words, seed)
    n = ny * nx
    src = np.arange(n).reshape(ny, nx, 1)
    dst = (src + rng.integers(1, n, (ny, nx, length))) % n
    prog["dst_y"], prog["dst_x"] = np.divmod(dst, nx)
    return prog


def transpose(nx, ny, length, rate, op, mem_words, seed):
    """(x, y) -> (y, x); square meshes only."""
    if nx != ny:
        raise ValueError(f"transpose needs a square mesh, got {nx}x{ny}")
    prog, _ = _base(nx, ny, length, rate, op, mem_words, seed)
    ys, xs = np.mgrid[0:ny, 0:nx]
    prog["dst_x"][:] = ys[..., None]
    prog["dst_y"][:] = xs[..., None]
    return prog


def bit_complement(nx, ny, length, rate, op, mem_words, seed):
    """(x, y) -> (nx-1-x, ny-1-y)."""
    prog, _ = _base(nx, ny, length, rate, op, mem_words, seed)
    ys, xs = np.mgrid[0:ny, 0:nx]
    prog["dst_x"][:] = (nx - 1 - xs)[..., None]
    prog["dst_y"][:] = (ny - 1 - ys)[..., None]
    return prog


def tornado(nx, ny, length, rate, op, mem_words, seed):
    """Each dimension shifts by ceil(k/2) - 1 (the mesh tornado)."""
    prog, _ = _base(nx, ny, length, rate, op, mem_words, seed)
    ys, xs = np.mgrid[0:ny, 0:nx]
    prog["dst_x"][:] = ((xs + max(math.ceil(nx / 2) - 1, 0)) % nx)[..., None]
    prog["dst_y"][:] = ((ys + max(math.ceil(ny / 2) - 1, 0)) % ny)[..., None]
    return prog


def hotspot(nx, ny, length, rate, op, mem_words, seed):
    """Half of the packets go to the centre tile, the rest uniformly."""
    prog, rng = _base(nx, ny, length, rate, op, mem_words, seed)
    uni = uniform(nx, ny, length, rate, op, mem_words, seed + 1)
    hot = rng.random((ny, nx, length)) < 0.5
    prog["dst_x"] = np.where(hot, nx // 2, uni["dst_x"])
    prog["dst_y"] = np.where(hot, ny // 2, uni["dst_y"])
    return prog


def neighbor(nx, ny, length, rate, op, mem_words, seed):
    """Each tile streams to its east neighbour, wrapping at the edge."""
    prog, _ = _base(nx, ny, length, rate, op, mem_words, seed)
    ys, xs = np.mgrid[0:ny, 0:nx]
    prog["dst_x"][:] = ((xs + 1) % nx)[..., None]
    prog["dst_y"][:] = ys[..., None]
    return prog


PATTERNS = {"uniform": uniform, "transpose": transpose,
            "bit_complement": bit_complement, "tornado": tornado,
            "hotspot": hotspot, "neighbor": neighbor}


def make_traffic(pattern: str, nx: int, ny: int, length: int, *,
                 rate: float, seed: int, op: int = OP_STORE,
                 mem_words: int = 64):
    """The injection program of one pattern on an ``nx`` x ``ny`` mesh."""
    if pattern not in PATTERNS:
        raise ValueError(f"unknown pattern {pattern!r}; known: "
                         f"{sorted(PATTERNS)}")
    return PATTERNS[pattern](nx, ny, length, rate, op, mem_words, seed)


def program_length(load: float, horizon: int) -> int:
    """Entries per tile that ``load`` cannot exhaust in ``horizon`` cycles
    (the sizing both ``SimRequest`` and ``SweepSpec`` apply)."""
    return int(np.ceil(load * horizon)) + 1
