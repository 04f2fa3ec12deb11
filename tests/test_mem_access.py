"""The fused step's tile-memory access at deployment sizes.

Tile memories of up to ``MEM_ONEHOT_MAX_WORDS`` words are read and
written by one-hot select; larger ones are stored in rows of
``MEM_ROW`` words and read by one word gathered per tile.  Their write
is, on a TPU, the ``tile_mem_store`` Pallas kernel, which writes the
storing tiles' words in place, and elsewhere one word scattered per
tile, the current word written back where nothing is stored (the
Pallas router kernel keeps the one-hot, over the rows).  Every form
must agree with the numpy oracle bit for bit, the memory included,
under store, load and compare-and-swap traffic on a preset memory
image, alone and under the vmapped phased measurement a sweep runs;
the store kernel runs here in the TPU interpreter.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.tile_mem_store import tile_mem_store
from repro.mesh import MeshConfig, Simulator, make_traffic
from repro.netsim_jax import sim
from repro.netsim_jax.measure import SweepKey, batch_stats_fn, phased_stats
from repro.netsim_jax.sim import (MEM_ONEHOT_MAX_WORDS, MEM_ROW, OP_CAS,
                                  OP_LOAD, OP_STORE, SimConfig, _mem_onehot,
                                  init_state, load_program, mem_access_form,
                                  mem_from_state, mem_shape, mem_to_state,
                                  memory_digest, simulate)
from repro.netsim_jax.testing import assert_state_equal

LARGE = 4 * MEM_ONEHOT_MAX_WORDS
NX = NY = 4


def _mixed_traffic(mem_words: int, seed: int, length: int = 40,
                   rate: float = 0.5):
    """Stores, loads and compare-and-swaps to a few words spread over
    the whole memory, last word included, so that loads and CAS read
    back what earlier stores wrote."""
    rng = np.random.default_rng(seed)
    e = make_traffic("uniform", NX, NY, length, rate=rate, seed=seed,
                     mem_words=mem_words)
    shape = e["op"].shape
    e["op"] = rng.choice([OP_STORE, OP_LOAD, OP_CAS], shape)
    words = np.linspace(0, mem_words - 1, 8).astype(np.int64)
    e["addr"] = rng.choice(words, shape)
    e["data"] = rng.integers(0, 4, shape)
    e["cmp"] = rng.integers(0, 4, shape)
    return e


def test_form_and_layout_follow_memory_size():
    assert _mem_onehot(64, kernel_safe=False)
    assert _mem_onehot(MEM_ONEHOT_MAX_WORDS, kernel_safe=False)
    assert not _mem_onehot(MEM_ONEHOT_MAX_WORDS + 1, kernel_safe=False)
    assert _mem_onehot(LARGE, kernel_safe=True)
    assert mem_shape(SimConfig(nx=3, ny=2, mem_words=64)) == (2, 3, 64)
    # whole (8, MEM_ROW) tiles per tile memory, padding at the end
    words = MEM_ONEHOT_MAX_WORDS + 1
    rows = mem_shape(SimConfig(nx=3, ny=2, mem_words=words))[2]
    assert rows % 8 == 0 and words <= rows * MEM_ROW < words + 8 * MEM_ROW
    assert mem_shape(SimConfig(nx=3, ny=2, mem_words=16384)) == \
        (2, 3, 16384 // MEM_ROW, MEM_ROW)
    small, large = (SimConfig(nx=3, ny=2, mem_words=w) for w in (64, LARGE))
    assert mem_access_form(small) == mem_access_form(small, "tpu") == "onehot"
    assert mem_access_form(large) == mem_access_form(large, "cpu") == \
        "scatter"
    assert mem_access_form(large, "tpu") == "store_kernel"


@pytest.mark.parametrize("mem_words,impl", [
    (64, "fused"), (MEM_ONEHOT_MAX_WORDS, "fused"), (LARGE, "fused"),
    (LARGE - 3, "fused"), (LARGE - 3, "pallas")])
def test_drain_matches_oracle_with_memory(mem_words, impl):
    """On a preset memory image, so loads and compare-and-swaps read
    words the run did not write; ``LARGE - 3`` words leave padding at
    the end of the last row."""
    entries = _mixed_traffic(mem_words, seed=mem_words)
    cfg = MeshConfig(nx=NX, ny=NY, max_out_credits=8, mem_words=mem_words)
    image = np.random.default_rng(1).integers(0, 4, (NY, NX, mem_words))
    oracle = Simulator(cfg, backend="numpy")
    oracle.set_mem(image)
    oracle.attach({k: v.copy() for k, v in entries.items()})
    fused = Simulator(cfg, backend="jax", impl=impl, cycles_per_call=4)
    fused.set_mem(image)
    fused.attach(entries)
    assert oracle.run_until_drained() == fused.run_until_drained()
    assert_state_equal(oracle, fused)
    oracle.telemetry().assert_bit_identical(fused.telemetry())
    mem = np.asarray(fused.mem)
    np.testing.assert_array_equal(np.asarray(oracle.mem), mem)
    # the run wrote words all over the memory, the last one included
    assert (mem[..., -1] != image[..., -1]).any()
    assert ((mem != image).sum(-1) > 0).all()


def test_vmapped_phases_match_each_point_at_large_memory():
    cfg = MeshConfig(nx=NX, ny=NY, router_fifo=4, max_out_credits=16,
                     mem_words=LARGE).to_sim()
    key = SweepKey(cfg, warmup=20, measure=40, drain=40)
    progs = [load_program(_mixed_traffic(LARGE, seed=s, length=60))
             for s in range(3)]
    depths, credits = np.array([2, 4, 3]), np.array([16, 4, 8])
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *progs)
    got = jax.jit(batch_stats_fn(key))(stacked, jnp.asarray(depths),
                                       jnp.asarray(credits))
    for i, prog in enumerate(progs):
        want = phased_stats(cfg, prog,
                            init_state(cfg, depths[i], credits[i]),
                            20, 40, 40)
        for field, w in want._asdict().items():
            np.testing.assert_array_equal(np.asarray(getattr(got, field))[i],
                                          np.asarray(w), err_msg=field)


@pytest.mark.parametrize("mem_words", [64, LARGE - 3])
def test_memory_digest_sums_up_the_image_in_either_layout(mem_words):
    """The sum and the place-weighted sum mod 2**32 of the ``(ny, nx,
    mem_words)`` image, negative words taken mod 2**32; a word moved to
    another place keeps the first and moves the second."""
    cfg = SimConfig(nx=3, ny=2, mem_words=mem_words)
    image = np.random.default_rng(mem_words).integers(
        -2**31, 2**31, (2, 3, mem_words))
    digest = jax.jit(lambda m: memory_digest(cfg, m))

    def want(img):
        words = (img % 2**32).astype(np.uint64).ravel()
        place = np.arange(1, words.size + 1, dtype=np.uint64)
        return [int(words.sum() % 2**32),
                int((words * place % 2**32).sum() % 2**32)]
    got = np.asarray(digest(mem_to_state(cfg, image))).tolist()
    assert got == want(image)
    moved = image.copy()
    moved[1, 2, -1], moved[1, 2, 0] = image[1, 2, 0], 0
    got_moved = np.asarray(digest(mem_to_state(cfg, moved))).tolist()
    assert got_moved == want(moved)
    assert got_moved[0] == (got[0] - int(image[1, 2, -1])) % 2**32
    assert got_moved[1] != got[1]


@pytest.fixture
def store_kernel(monkeypatch):
    """The step's write of a memory stored in rows forced to the
    ``tile_mem_store`` kernel in the TPU interpreter; returns the shapes
    of the memories it was traced with.  Jitted programs traced before,
    with the scatter, are dropped, and those traced here with the
    kernel are dropped after."""
    traces = []

    def store(*args):
        traces.append(args[0].shape)
        return tile_mem_store(*args, interpret=True)
    jax.clear_caches()
    monkeypatch.setattr(sim, "_mem_store", store)
    yield traces
    monkeypatch.undo()
    jax.clear_caches()


@contextlib.contextmanager
def _scatter_store():
    """The step's write of a memory stored in rows as the XLA scatter,
    traced afresh, within a test that forced the store kernel; yields
    the list of its traces."""
    traces = []

    def store(*args):
        traces.append(args[0].shape)
        return sim._mem_scatter(*args)
    with pytest.MonkeyPatch.context() as mp:
        jax.clear_caches()
        mp.setattr(sim, "_mem_store", store)
        yield traces
    jax.clear_caches()


def _store_case_entries(case: str):
    """``mixed``: stores, loads and compare-and-swaps, hits and misses;
    ``same_word``: stores alone, every tile to one word of tile (0, 0),
    so that word is stored in consecutive cycles; ``idle_tiles``: the
    mixed traffic sent to the west half of the mesh, so the east half's
    tiles never store."""
    e = _mixed_traffic(LARGE - 3, seed=5,
                       length=8 if case == "same_word" else 24)
    if case == "same_word":
        e["op"][:] = OP_STORE
        e["dst_x"][:], e["dst_y"][:] = 0, 0
        e["addr"][:] = LARGE - 4
        e["data"] = np.arange(e["op"].size).reshape(e["op"].shape) + 1
    elif case == "idle_tiles":
        e["dst_x"] = e["dst_x"] % (NX // 2)
    return e


@pytest.mark.parametrize("case", ["mixed", "same_word", "idle_tiles",
                                  "vmapped"])
def test_store_kernel_matches_scatter_and_oracle(store_kernel, case):
    """The in-place store kernel leaves the memory, and every other part
    of the state, as the XLA scatter and the numpy oracle do; under
    ``vmap`` a batch of three points folds into one kernel launch."""
    words = LARGE - 3
    cfg = MeshConfig(nx=NX, ny=NY, max_out_credits=8, mem_words=words)
    image = np.random.default_rng(2).integers(0, 4, (NY, NX, words))
    if case == "vmapped":
        scfg, cycles = cfg.to_sim(), 120
        entries = [_mixed_traffic(words, seed=s, length=30) for s in range(3)]
        progs = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *map(load_program, entries))
        fresh = init_state(scfg)._replace(mem=mem_to_state(scfg, image))
        run = jax.jit(jax.vmap(lambda p: simulate(scfg, p, fresh, cycles)))
        got_st, got_done = run(progs)
        assert store_kernel and set(store_kernel) == {mem_shape(scfg)}
        # one launch a cycle over the three points' rows
        rows = 3 * NY * NX * mem_shape(scfg)[2]
        assert f"i32[{rows},{MEM_ROW}]" in str(jax.make_jaxpr(run)(progs))
        with _scatter_store() as scattered:
            want_st, want_done = run(progs)
        assert scattered
        np.testing.assert_array_equal(np.asarray(got_done),
                                      np.asarray(want_done))
        for w, g in zip(jax.tree_util.tree_leaves(want_st),
                        jax.tree_util.tree_leaves(got_st)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        for i, e in enumerate(entries):
            oracle = Simulator(cfg, backend="numpy")
            oracle.set_mem(image)
            oracle.attach({k: v.copy() for k, v in e.items()})
            oracle.run(cycles)
            np.testing.assert_array_equal(
                mem_from_state(scfg, got_st.mem[i]), np.asarray(oracle.mem))
        return
    entries = _store_case_entries(case)

    def drain(backend):
        s = Simulator(cfg, backend=backend)
        s.set_mem(image)
        s.attach({k: v.copy() for k, v in entries.items()})
        return s, s.run_until_drained()
    oracle, drained = drain("numpy")
    kernel = drain("jax")
    assert store_kernel
    with _scatter_store() as scattered:
        scatter = drain("jax")
    assert scattered
    for s, d in (kernel, scatter):
        assert d == drained
        assert_state_equal(oracle, s)
        oracle.telemetry().assert_bit_identical(s.telemetry())
    mem = np.asarray(kernel[0].mem)
    if case == "same_word":
        # tile (0, 0) took every store; the last one served stays
        assert (mem != image).sum() == 1 and mem[0, 0, LARGE - 4] > 0
    if case == "idle_tiles":
        np.testing.assert_array_equal(mem[:, NX // 2:], image[:, NX // 2:])
        assert (mem[:, :NX // 2] != image[:, :NX // 2]).any()
