"""The benchmark finds every piece by name: a new configuration, traffic
mix, metric or kernel is a new file and a new entry, with no edit to a
file that is there.  And the counts the rooflines and ``mfu`` rest on,
against hand counts."""
import json
import shutil

import pytest

import tinycell  # noqa: F401  (puts chipbench/ on the path)
import hlo
import spec


@pytest.fixture
def copy_of_bench(tmp_path, monkeypatch):
    root = tmp_path / "chipbench"
    shutil.copytree(tinycell.BENCH_DIR, root,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    monkeypatch.setattr(spec, "HERE", str(root))
    return root


def test_new_files_are_found_by_name(copy_of_bench):
    root = copy_of_bench
    model = json.loads((root / "configs" / "qwen2-0.5b.json").read_text())
    model["name"] = "new-model"
    (root / "configs" / "new-model.json").write_text(json.dumps(model))
    (root / "traffic" / "new-mix.json").write_text(json.dumps(tinycell.MIX))
    (root / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    (root / "kernels" / "new_kernel.py").write_text(
        "def cost(call, model):\n    return 1, 2\n")

    assert spec.config("new-model")["hidden_size"] == 896
    assert spec.traffic("new-mix")["items"] == tinycell.MIX["items"]
    assert spec.metric_reader("new_metric")(None) == 42.0
    assert spec.kernel_cost("new_kernel")(None, None) == (1, 2)

    bench = spec.benchmark()
    bench["workloads"].append({"name": "new-model.new-mix",
                               "config": "new-model", "traffic": "new-mix",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "kernels",
                               "moves": "train_tokens_per_s"})
    names = [m["name"] for m in
             spec.metrics_for(bench, "new-model.new-mix", trace=True)]
    assert names == ["new_metric"]
    e2e = [m["name"] for m in
           spec.metrics_for(bench, "new-model.new-mix", trace=False)]
    assert e2e == ["train_tokens_per_s", "step_ms.p90", "setup_s"]


def test_missing_pieces_are_spec_errors(copy_of_bench):
    with pytest.raises(spec.SpecError):
        spec.config("no-such-model")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9 imaginary")
    with pytest.raises(spec.SpecError):
        spec.config("../outside")


def _call(operands, result):
    t = lambda dt, shape: {"dtype": dt, "shape": shape}  # noqa: E731
    return {"operands": [t(*o) for o in operands],
            "result": [t(*r) for r in result]}


def test_flash_attention_counts_at_published_head_dim():
    call = _call([("bf16", (4, 14, 1024, 128)), ("bf16", (4, 2, 1024, 128)),
                  ("bf16", (4, 2, 1024, 128))], [("bf16", (4, 14, 1024, 128))])
    flops, nbytes = spec.kernel_cost("flash_attention")(
        call, spec.config("qwen2-0.5b"))
    # 4 ops per head dim per causal pair: 4 * B * H * S(S+1)/2 * 64
    assert flops == 4 * 4 * 14 * 524800 * 64 == 7_523_532_800
    # q and o (14 heads), k and v (2 heads), bf16, at head dim 64
    assert nbytes == 2 * 4 * 1024 * 64 * (14 + 14 + 2 + 2) == 16_777_216


def test_ssd_scan_counts():
    call = _call([("bf16", (4, 48, 2048, 64)), ("f32", (4, 48, 2048, 1)),
                  ("f32", (48,)), ("bf16", (4, 1, 2048, 128)),
                  ("bf16", (4, 1, 2048, 128))], [("bf16", (4, 48, 2048, 64))])
    flops, nbytes = spec.kernel_cost("ssd_scan")(
        call, spec.config("mamba2-780m"))
    # per position: scores 128.5 * 128, intra 48 * 128.5 * 64, state and
    # inter 2 * 48 * 64 * 128; 2 ops each, 4 * 2048 positions
    assert flops == 2 * 4 * 2048 * (16448 + 394752 + 786432) \
        == 19_622_002_688
    assert nbytes == 2 * 50_331_648 + 1_572_864 + 192 + 2 * 2_097_152


def test_flops_per_token_hand_counts():
    q = spec.config("qwen2-0.5b")
    # 24 layers of (q, o: 896x896; k, v: 896x128; MLP 3 x 896x4864) and
    # the tied 896x151936 unembedding, 6 ops a weight; causal attention
    # 6 * 14 heads * 64 * 1025 per layer
    weights = 24 * (2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864) \
        + 896 * 151936
    assert spec.reference("dense_lm").flops_per_token(q) == \
        6 * weights + 24 * 6 * 14 * 64 * 1025
    m = spec.config("mamba2-780m")
    weights = 23 * (1536 * (2 * 3072 + 2 * 128 + 48) + 3072 * 1536
                    + 4 * (3072 + 256)) + 1536 * 50280
    scan = 23 * 2 * (128.5 * 128 + 48 * 128.5 * 64 + 2 * 48 * 64 * 128)
    assert spec.reference("mamba2_lm").flops_per_token(m) == \
        6 * weights + 3 * scan


def test_pallas_calls_from_compiled_hlo():
    line = ('  %flash_attention.21 = bf16[4,14,1024,128]{3,2,1,0:T(8,128)(2,1)'
            'S(1)} custom-call(%pad.176, %pad.177, %pad.178), custom_call_'
            'target="tpu_custom_call", operand_layout_constraints={bf16[4,14,'
            '1024,128]{3,2,1,0}, bf16[4,2,1024,128]{3,2,1,0}, bf16[4,2,1024,'
            '128]{3,2,1,0}}, frontend_attributes={kernel_metadata={}}, '
            'metadata={op_name="jit(step)/jvp()/while/body/closed_call/jit('
            'flash_attention)/pallas_call" stack_frame_id=75}')
    (call,) = hlo.pallas_calls("%fusion.1 = f32[2] add(...)\n" + line)
    assert call["name"] == "flash_attention.21"
    assert call["kernel"] == "flash_attention"
    assert [o["shape"] for o in call["operands"]] == [
        (4, 14, 1024, 128), (4, 2, 1024, 128), (4, 2, 1024, 128)]
    assert call["result"] == [{"dtype": "bf16", "shape": (4, 14, 1024, 128)}]
