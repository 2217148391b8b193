"""``granite-4.0-h-micro``'s cell: a tiny copy of its configuration through
``cell.run_cell`` on the CPU, the output check's control and a planted
fault against it, and the step compiled for a described v5e: its Pallas
calls found by ``hlo.pallas_calls`` under the layer-kind scopes, and the
configured step's memory.

The tiny copy has three layers (mamba, attention, mamba: both kinds, and
a kind's stack split by the other) at ``tinycell``'s widths.  At width 64
the bfloat16 program's gradient strays from the float32 reference with
depth (a seven-layer copy read a global-norm gap of up to 7.6% on CPU
seeds, a 512-wide ten-layer one 0.2-0.45%): tiny widths sum few terms a
product.  The configuration's limits are set from full-size chip readings
(``PERF.md``).

The v5e topology is described inside a fixture, never while a module is
imported, and the persistent compilation cache is off around the
compiles."""
import os

import pytest

import tinycell
import check
import faults
import hlo
import plain
import spec
import traffic

CONFIG = "granite-4.0-h-micro"
TINY = ({"hidden_size": 64, "intermediate_size": 128,
         "shared_intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 3,
         "layer_types": ["mamba", "attention", "mamba"], "vocab_size": 512,
         "mamba_d_state": 16, "mamba_d_head": 16, "mamba_n_heads": 8,
         "mamba_chunk_size": 16, "chunk_size": 16},
        {"global_batch": 2, "seq_len": 64})
SCOPES = ("mixer.mamba", "mixer.attention", "mlp")
# bytes_limit of one v5e chip as its runtime reports it (chip_smoke.py)
V5E_HBM = 16_909_336_064


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setitem(tinycell.TINY, CONFIG, TINY)
    return tinycell.tiny_model(CONFIG)


def test_tiny_run_is_correct(monkeypatch, tiny):
    out = tinycell.run_tiny(monkeypatch, CONFIG)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_control_fails_the_limits(tiny, seed):
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    items = traffic.make_items(tinycell.MIX, tiny["train"]["seq_len"],
                               tiny["vocab_size"], seed)
    batches = check.reference_batches(tiny, tinycell.MIX, items)
    ref_mod = spec.reference(tiny["reference"])
    wseed = traffic.seeds(seed)["weights"]
    ref = plain.run_steps(ref_mod, tiny, wseed, batches)
    control = plain.run_steps(ref_mod, tiny, wseed, batches,
                              precision="fp8")
    got = check.numbers(control, ref)
    assert any(got[k] > tiny["limits"][k] for k in got), got


def test_half_batch_is_incorrect(monkeypatch, tiny):
    out = tinycell.run_tiny(monkeypatch, CONFIG, plant=faults.half_batch)
    assert out["correct"] is False
    assert out["checks"]["grad_norm_gap"]["value"] > \
        out["checks"]["grad_norm_gap"]["limit"]


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


def _compiled_step(model, one_chip, monkeypatch):
    import jax
    import jax.numpy as jnp
    import cell
    from repro.models import build_model
    from repro.train.train_step import (TrainStepConfig,
                                        abstract_train_state,
                                        make_train_step)

    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    tinycell.program_computes_as_configured(monkeypatch, model)
    net = build_model(cell.model_config(model))
    tsc = TrainStepConfig(remat_policy=model["train"]["remat"])
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,  # noqa: E731
                                             sharding=one_chip)
    state = jax.tree_util.tree_map(on_chip, abstract_train_state(net, tsc))
    b, s = model["train"]["global_batch"], model["train"]["seq_len"]
    batch = {k: jax.ShapeDtypeStruct((b, s), dt, sharding=one_chip)
             for k, dt in (("tokens", jnp.int32), ("targets", jnp.int32),
                           ("loss_mask", jnp.float32))}
    return jax.jit(make_train_step(net, tsc), donate_argnums=(0,)).lower(
        state, batch).compile()


@pytest.mark.parametrize("config, kernels, scopes", [
    (CONFIG, ("flash_attention", "ssd_scan"), SCOPES),
    ("qwen2-0.5b", ("flash_attention",), ("mixer.attention", "mlp")),
], ids=[CONFIG, "qwen2-0.5b"])
def test_pallas_calls_found_under_the_scopes(one_chip, monkeypatch, tiny,
                                             config, kernels, scopes):
    """Every call of a kernel that a roofline reads keeps its
    ``jit(<kernel>)/pallas_call`` op name inside the layer-kind scopes,
    which name the step's other ops."""
    text = _compiled_step(tinycell.tiny_model(config), one_chip,
                          monkeypatch).as_text()
    calls = hlo.pallas_calls(text)
    customs = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in kernels:
        found = [c for c in calls if c["kernel"] == kernel]
        assert found and len(found) == sum(
            f"{kernel})/pallas_call" in line for line in customs), kernel
    for scope in scopes:
        assert f"/{scope}/" in text, scope


def test_configured_step_fits_one_chip(one_chip, monkeypatch):
    """The cell's step, at full size with the configured remat, compiles
    for one v5e with at least 0.5 GB of HBM to spare."""
    model = spec.config(CONFIG)
    m = _compiled_step(model, one_chip, monkeypatch).memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert V5E_HBM - used >= 5 * 10 ** 8, used
