"""The training cell's step compiles for a described TPU v5e at its full
size, with its Pallas kernels, and fits the chip.

Nothing runs: the step the window drives (the ParameterDB train engine's
jitted step, ``delta=2`` packed ring, SmolLM-360M, batch 8 x 1024) is
lowered and compiled by the TPU compiler for a v5e chip that is described,
not attached.  The topology is described inside a fixture, so collecting
this file loads no TPU library.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness, program

TRAIN = "smollm360m.train.ring_d2"
HBM = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_train_step_compiles_and_fits_v5e(one_chip, monkeypatch):
    from repro.core.sync_jax import SyncConfig
    from repro.launch.steps import make_train_engine
    from repro.models import paramlib
    from repro.models.transformer import model_specs
    from repro.optim import OptConfig, make_optimizer
    from repro.pdb.jax_backend import init_delayed_state

    cell = harness.resolve(TRAIN)
    t = cell.traffic
    monkeypatch.setenv("REPRO_KERNEL_IMPL", t["kernels"])   # read at trace
    cfg = program.model_config(cell.config)
    o = t["optimizer"]
    opt = make_optimizer(OptConfig(
        name="adamw", lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"], grad_clip=o["grad_clip"]))
    sync = SyncConfig(delta=t["delta"], remat=t["remat"])
    params = paramlib.abstract_tree(model_specs(cfg), cfg.param_dtype)
    engine = make_train_engine(cfg, opt, sync, params)

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    state = placed(jax.eval_shape(lambda p: init_delayed_state(
        p, opt.init, t["delta"], packed=True), params))
    B, S = t["batch"], t["seq"]
    batch = placed({"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                    "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
                    "mask": jax.ShapeDtypeStruct((B, S), jnp.float32)})
    compiled = engine.step_fn.lower(state, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert need < HBM, mem
