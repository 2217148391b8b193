"""The output check's control: the float32 reference put in the program's
place and computed in the precision below the configuration's bfloat16
(every matrix product, forward and backward, on float8 e4m3 operands).
Held to the configuration's limits it has to come out as not correct.
On the chip this runs at the cell's own size (``calibrate.py``); here at a
size a test run can hold."""
import pytest

import tinycell
import check
import plain
import spec
import traffic


@pytest.mark.parametrize("config", ["qwen2-0.5b", "mamba2-780m"])
@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_control_fails_the_limits(config, seed):
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    model = tinycell.tiny_model(config)
    items = traffic.make_items(tinycell.MIX, model["train"]["seq_len"],
                               model["vocab_size"], seed)
    batches = check.reference_batches(model, tinycell.MIX, items)
    ref_mod = spec.reference(model["reference"])
    wseed = traffic.seeds(seed)["weights"]
    ref = plain.run_steps(ref_mod, model, wseed, batches)
    control = plain.run_steps(ref_mod, model, wseed, batches,
                              precision="fp8")
    got = check.numbers(control, ref)
    assert any(got[k] > model["limits"][k] for k in got), got
