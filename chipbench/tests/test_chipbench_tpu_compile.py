"""The cut of ``mamba2-780m``: its depth is the deepest whose train step,
at the cell's 4 x 2048 tokens, compiles for one v5e chip with at least
1 GB of HBM to spare.  Nothing runs: the TPU compiler builds the step for
a described (not attached) v5e:2x2 topology and reports its memory.  The
topology is described inside a fixture, never while a module is imported,
and the persistent compilation cache is off around the compiles."""
import dataclasses
import os

import pytest

import tinycell
import spec

# bytes_limit of one v5e chip as its runtime reports it (chip_smoke.py)
V5E_HBM = 16_909_336_064
HEADROOM = 10 ** 9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _step_bytes(model, layers, one_chip):
    import jax
    import jax.numpy as jnp
    import cell
    from repro.models import build_model
    from repro.train.train_step import (TrainStepConfig,
                                        abstract_train_state,
                                        make_train_step)

    cfg = dataclasses.replace(cell.model_config(model), num_layers=layers)
    net = build_model(cfg)
    tsc = TrainStepConfig(remat_policy=model["train"]["remat"])
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,  # noqa: E731
                                             sharding=one_chip)
    state = jax.tree_util.tree_map(on_chip, abstract_train_state(net, tsc))
    b, s = model["train"]["global_batch"], model["train"]["seq_len"]
    batch = {k: jax.ShapeDtypeStruct((b, s), dt, sharding=one_chip)
             for k, dt in (("tokens", jnp.int32), ("targets", jnp.int32),
                           ("loss_mask", jnp.float32))}
    compiled = jax.jit(make_train_step(net, tsc), donate_argnums=(0,)).lower(
        state, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("extra, fits", [(0, True), (1, False)],
                         ids=["configured-depth", "one-layer-deeper"])
def test_mamba2_depth_is_the_deepest_that_fits(one_chip, monkeypatch,
                                               extra, fits):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    model = spec.config("mamba2-780m")
    tinycell.program_computes_as_configured(monkeypatch, model)
    layers = model["n_layer"] + extra
    used = _step_bytes(model, layers, one_chip)
    assert (V5E_HBM - used >= HEADROOM) is fits, (layers, used)
