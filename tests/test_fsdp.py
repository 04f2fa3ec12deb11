"""FSDP (ZeRO-3) strategy: banked params + equal semantics + less memory."""
import dataclasses

import jax
import numpy as np
import pytest

from repro import optim
from repro.configs import get_config, reduced_config
from repro.configs.base import ShapeConfig
from repro.launch import step as step_mod

SHAPE = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
OPT = optim.OptConfig(warmup_steps=2, total_steps=10)


def _param_bytes(cell):
    total = 0
    for k, sds in cell.args[0].items():
        local = cell.in_shardings[0][k].shard_shape(sds.shape)
        total += int(np.prod(local)) * sds.dtype.itemsize
    return total


def test_fsdp_banks_params(mesh_dm):
    cfg = reduced_config(get_config("qwen2-72b"))
    base = step_mod.build_cell(cfg, SHAPE, mesh_dm, "baseline", OPT)
    fsdp = step_mod.build_cell(cfg, SHAPE, mesh_dm, "fsdp", OPT)
    assert _param_bytes(fsdp) < _param_bytes(base)


def test_fsdp_compiles_and_matches(mesh_dm):
    cfg = dataclasses.replace(reduced_config(get_config("stablelm-3b")),
                              dtype="float32")
    from repro.models.api import get_model
    model = get_model(cfg)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (8, 33)).astype(np.int32)
    batch = {"tokens": jax.numpy.asarray(toks[:, :-1]),
             "labels": jax.numpy.asarray(toks[:, 1:]),
             "mask": jax.numpy.ones((8, 32), jax.numpy.float32)}
    losses = {}
    for strat in ("baseline", "fsdp"):
        cell = step_mod.build_cell(cfg, SHAPE, mesh_dm, strat, OPT)
        with mesh_dm:
            params = jax.jit(model.init_params, static_argnums=0,
                             out_shardings=cell.in_shardings[0])(
                cfg, jax.random.key(0))
            opt_state = jax.jit(optim.init,
                                out_shardings=cell.in_shardings[1])(params)
            _, _, m = cell.jitted()(params, opt_state, batch)
        losses[strat] = float(m["loss"])
    assert losses["fsdp"] == pytest.approx(losses["baseline"], rel=1e-5)