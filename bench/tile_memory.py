"""The tile memory a replay leaves behind, summed up as the program does.

The sweep's program returns, for every point, two uint32 words that sum
up the tile memory its three phases leave: the sum of the words, and
the sum of each word times one more than its place in the ``(ny, nx,
mem_words)`` image, both mod 2**32.  A store that is dropped, or written
to another word, moves the second.  Here the plain reference runs the
same phases on the same program and its memory is summed up alike.
"""
from __future__ import annotations

import numpy as np

from bench import patterns, reference

MOD = 2**32


def digest(mem) -> list:
    """``[sum, weighted sum]`` of a ``(ny, nx, mem_words)`` memory."""
    words = (np.asarray(mem, np.int64) % MOD).astype(np.uint64).ravel()
    place = np.arange(1, words.size + 1, dtype=np.uint64)
    return [int(words.sum() % MOD), int((words * place % MOD).sum() % MOD)]


def final_digest(case: dict) -> list:
    """The digest of the memory the reference leaves after ``case``'s
    warmup, measurement window and drain."""
    cfg = reference.MeshConfig(case["nx"], case["ny"], case["router_fifo"],
                               case["ep_fifo"], case["max_out_credits"],
                               case["mem_words"])
    sim = reference.MeshSim(cfg)
    sim.load_program(patterns.make_traffic(
        case["pattern"], case["nx"], case["ny"], case["length"],
        rate=case["rate"], seed=case["seed"], mem_words=case["mem_words"]))
    sim.run(case["warmup"] + case["measure"] + case["drain"])
    return digest(sim.mem)


def final_digests(cases, workers: int = 1) -> list:
    """``final_digest`` of every case, ``workers`` processes at a time."""
    if workers <= 1 or len(cases) <= 1:
        return [final_digest(c) for c in cases]
    import concurrent.futures
    import multiprocessing
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(
            max_workers=min(workers, len(cases)), mp_context=ctx) as pool:
        return list(pool.map(final_digest, cases))
