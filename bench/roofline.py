"""The least device time a simulated mesh cycle can take.

The state of a BaseJump mesh model, counted from the configuration's
sizes in 32-bit words (a packet is 5 words: packed header, address,
data, compare value, tag); booleans count one byte.  A cycle has to read
and write all of it once, so the least time per cycle is twice the
state bytes over the peak HBM bandwidth.  The count is fixed by the
model, not by any one implementation's layout.
"""
from __future__ import annotations

PACKET_WORDS = 5
PORTS = 5
LAT_BINS = 512
WORD = 4


def state_bytes(cfg: dict) -> int:
    """Bytes of the whole mesh state of one simulation."""
    tiles = cfg["nx"] * cfg["ny"]
    depth, ep_fifo = cfg["router_fifo"], cfg["ep_fifo"]
    words = (
        # two router networks: input FIFOs, their heads and counts
        2 * tiles * PORTS * depth * PACKET_WORDS
        + 2 * 2 * tiles * PORTS
        # endpoint request FIFO, its head and count
        + tiles * ep_fifo * PACKET_WORDS + 2 * tiles
        # the response delay slot and the registered response port
        + 2 * tiles * PACKET_WORDS
        # tile memory
        + tiles * cfg["mem_words"]
        # credits, program pointer, completions, latency sum, cycles
        # out of credit
        + 5 * tiles
        # round-robin pointers of both networks
        + 2 * tiles * PORTS
        # cycle, effective depth and credits, measurement window
        + 5
        # link utilization and FIFO high-water marks of both networks,
        # endpoint high-water mark, latency histogram
        + 2 * 2 * tiles * PORTS + tiles + LAT_BINS)
    valid_flags = 2 * tiles      # response slot and registered port
    return WORD * words + valid_flags


def least_seconds_per_cycle(cfg: dict, hbm_bytes_per_s: float) -> float:
    """Read and write the whole state once, at the peak bandwidth."""
    return 2 * state_bytes(cfg) / hbm_bytes_per_s
