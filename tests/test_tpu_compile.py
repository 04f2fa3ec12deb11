"""The simulator's device programs compile for a TPU v5e.

Nothing here runs: each test lowers a program for a described (not
attached) ``v5e:2x2`` topology and compiles it with the TPU compiler,
which refuses what the chip would refuse — an op Mosaic cannot lower, a
kernel over the VMEM limit, a program over device memory.  Interpret
mode on the CPU cannot catch any of these.

The topology is described only inside a fixture: the TPU library may be
loaded by one process at a time, and every test worker imports this
module.  Each compile runs with the persistent compilation cache off (a
TPU executable written there cannot be read back without a chip).
"""
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.netsim import LAT_BINS
from repro.kernels.router_step import VMEM_LIMIT_BYTES, router_step_call
from repro.netsim_jax.measure import SweepKey, batch_stats_fn
from repro.netsim_jax.sim import (I32, PROG_FIELDS, Program, SimConfig,
                                  init_state, run_until_drained_traced,
                                  simulate)
from repro.sim_service.streaming import _block_jit


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _program(cfg: SimConfig, length: int, batch=()):
    return Program(
        buf=jax.ShapeDtypeStruct(batch + (len(PROG_FIELDS), cfg.ny, cfg.nx,
                                          length), I32),
        length=jax.ShapeDtypeStruct(batch + (cfg.ny, cfg.nx), I32))


def _cfg(n: int, m: int) -> SimConfig:
    return SimConfig(nx=n, ny=m, max_out_credits=64, router_fifo=4)


@pytest.mark.parametrize("nx,ny", [(32, 32), (64, 64)])
def test_fused_simulate_compiles(one_chip, no_persistent_cache, nx, ny):
    cfg = _cfg(nx, ny)
    state = jax.eval_shape(lambda: init_state(cfg))
    compiled = simulate.lower(cfg, _on(one_chip, _program(cfg, 64)),
                              _on(one_chip, state), 64).compile()
    assert compiled.memory_analysis() is not None


def test_fused_drain_compiles_without_gather(one_chip, no_persistent_cache):
    """The drain benchmark's program (16x32, 128 entries per tile, fence
    every cycle) fetches program entries and memory words by one-hot
    select: the compiled program holds no gather."""
    cfg = _cfg(16, 32)
    state = jax.eval_shape(lambda: init_state(cfg))
    compiled = run_until_drained_traced.lower(
        cfg, _on(one_chip, _program(cfg, 128)), _on(one_chip, state),
        100_000, 1, "fused", 1).compile()
    assert "gather(" not in compiled.as_text()


def test_batched_bucket_compiles(one_chip, no_persistent_cache):
    """The vmapped phased-measurement bucket that ``dse.run_sweep`` and
    ``sim_service`` run: 16x16, a batch of 8."""
    cfg = _cfg(16, 16)
    key = SweepKey(cfg, warmup=50, measure=100, drain=100)
    progs = _on(one_chip, _program(cfg, 64, batch=(8,)))
    knob = jax.ShapeDtypeStruct((8,), I32, sharding=one_chip)
    jax.jit(batch_stats_fn(key)).lower(progs, knob, knob).compile()


# an op whose result spans every tile's whole memory of 16,384 words,
# as words or as rows of 128: a one-hot access over the memory (select,
# iota, compare) or a change of its layout (copy, reshape)
WHOLE_MEMORY = re.compile(
    r"= (?:s32|pred)\[[0-9,]*(?:,16384|,128,128)\]\{[^}]*\} "
    r"(?:select|iota|compare|copy|reshape)\(")


def _scatter_sizes(text: str):
    """Element counts of the int32 scatters in a compiled program."""
    return [int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(r"= s32\[([0-9,]*)\]\{[^}]*\} scatter\(",
                                   text)]


def test_deployment_memory_bucket_compiles_without_onehot(
        one_chip, no_persistent_cache):
    """The Epiphany-V sweep's bucket, one chip's chunk of 16 points at
    32x32 with 16,384 words per tile and 121 entries per tile, compiles,
    and reads one word per tile and writes the storing tiles' words in
    place: the ``tile_mem_store`` kernel, no scatter over the memory (the
    one scatter left is the latency histogram's), no one-hot over the
    whole memory and no copy of it into another layout.  The memory
    digest reads the whole memory once a point, as uint32."""
    cfg = SimConfig(nx=32, ny=32, router_fifo=4, max_out_credits=32,
                    mem_words=16384)
    key = SweepKey(cfg, warmup=200, measure=400, drain=400)
    progs = _on(one_chip, _program(cfg, 121, batch=(16,)))
    knob = jax.ShapeDtypeStruct((16,), I32, sharding=one_chip)
    text = jax.jit(batch_stats_fn(key, digest=True)).lower(
        progs, knob, knob).compile().as_text()
    assert "tile_mem_store" in text
    assert max(_scatter_sizes(text), default=0) <= 16 * LAT_BINS
    assert WHOLE_MEMORY.search(text) is None


@pytest.mark.parametrize("program", ["drain", "service_block"])
def test_celerity_programs_compile_without_store_kernel(
        one_chip, no_persistent_cache, program):
    """At the Celerity cells' 64 words per tile the memory is read and
    written by one-hot select: the drain program (16x32, 128 entries per
    tile) and the service's block (a batch of 8) hold neither the store
    kernel nor a scatter over the memory, only the latency histogram's."""
    cfg = _cfg(16, 32)
    state = jax.eval_shape(lambda: init_state(cfg))
    if program == "drain":
        batch = 1
        lowered = run_until_drained_traced.lower(
            cfg, _on(one_chip, _program(cfg, 128)), _on(one_chip, state),
            100_000, 1, "fused", 1)
    else:
        batch = 8
        states = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((batch,) + x.shape, x.dtype),
            state)
        lowered = _block_jit(SweepKey(cfg, 200, 400, 400), 100).lower(
            _on(one_chip, _program(cfg, 64, batch=(batch,))),
            _on(one_chip, states))
    text = lowered.compile().as_text()
    assert "tile_mem_store" not in text
    assert max(_scatter_sizes(text), default=0) <= batch * LAT_BINS


@pytest.mark.parametrize("n", [8, 16])
def test_router_kernel_compiles(one_chip, no_persistent_cache, n):
    """The Pallas router kernel, 4 cycles per launch, lowers through
    Mosaic (not interpret mode) and fits the scoped VMEM limit."""
    cfg = _cfg(n, n)
    state = jax.eval_shape(lambda: init_state(cfg))
    step = jax.jit(lambda p, s: router_step_call(cfg, p, s, 4,
                                                 interpret=False))
    compiled = step.lower(_on(one_chip, _program(cfg, 8)),
                          _on(one_chip, state)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_router_kernel_refuses_mesh_over_vmem():
    """A mesh whose ungridded state cannot fit VMEM is refused before
    lowering, naming the mesh and the bytes."""
    cfg = _cfg(64, 64)
    state = jax.eval_shape(lambda: init_state(cfg))
    with pytest.raises(ValueError, match=rf"64x64.*{VMEM_LIMIT_BYTES}"):
        jax.eval_shape(lambda p, s: router_step_call(cfg, p, s, 1,
                                                     interpret=False),
                       _program(cfg, 8), state)
