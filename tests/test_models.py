"""Per-architecture smoke tests (reduced configs, CPU): one train step and
a prefill->decode consistency check for every assigned arch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (SHAPES, applicable_shapes, get_config,
                           list_configs, reduced)
from repro.models import build_model

ALL_ARCHS = list_configs()
DECODE_ARCHS = [a for a in ALL_ARCHS if get_config(a).has_decoder]


def make_batch(cfg, B=2, S=24, seed=0, with_targets=True):
    rng = np.random.default_rng(seed)
    text = S - (cfg.num_patches or 0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, text)).astype(np.int32))}
    if with_targets:
        batch["targets"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, text)).astype(np.int32))
        batch["loss_mask"] = jnp.ones((B, text), jnp.float32)
    if cfg.num_patches:
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(0, 1, (B, cfg.num_patches, cfg.patch_embed_dim))
        ).astype(jnp.float32)
    if cfg.encoder_layers:
        batch["frames"] = jnp.asarray(
            rng.normal(0, 1, (B, cfg.max_source_positions, cfg.d_model))
        ).astype(jnp.float32)
    return batch


def test_all_ten_archs_registered():
    """The ten assigned architectures, and granite-4.0-h-micro."""
    assert len(ALL_ARCHS) == 11
    assert set(ALL_ARCHS) == {
        "yi-34b", "qwen2-0.5b", "mistral-large-123b", "qwen3-1.7b",
        "granite-moe-3b-a800m", "mixtral-8x22b", "mamba2-780m",
        "phi-3-vision-4.2b", "whisper-large-v3", "hymba-1.5b",
        "granite-4.0-h-micro"}


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_loss(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    loss, metrics = model.loss(params, batch, remat_policy="none")
    assert jnp.isfinite(loss), arch
    assert 3.0 < float(loss) < 8.0   # ~ln(vocab) at random init


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_train_step(arch):
    from repro.train.train_step import (TrainStepConfig, init_train_state,
                                        make_train_step)
    from repro.train.optimizer import AdamWConfig

    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    scfg = TrainStepConfig(remat_policy="none",
                           optimizer=AdamWConfig(peak_lr=1e-3,
                                                 warmup_steps=1,
                                                 total_steps=4))
    state = init_train_state(model, jax.random.PRNGKey(0), scfg)
    step = jax.jit(make_train_step(model, scfg))
    batch = make_batch(cfg)
    state, m1 = step(state, batch)
    state, m2 = step(state, batch)
    assert jnp.isfinite(m2["loss"])
    assert float(m2["loss"]) < float(m1["loss"])   # same batch -> improves
    for leaf in jax.tree_util.tree_leaves(state.params):
        assert bool(jnp.all(jnp.isfinite(leaf)))


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_smoke_decode_matches_prefill(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 24
    batch = make_batch(cfg, B=B, S=S, with_targets=False)

    logits_full, _ = model.prefill(params, batch, model.init_cache(B, S + 8))

    tokens = batch["tokens"]
    part = dict(batch)
    part["tokens"] = tokens[:, :-1]
    cache = model.init_cache(B, S + 8)
    _, cache = model.prefill(params, part, cache)
    pos = jnp.full((B,), tokens.shape[1] - 1, jnp.int32)
    logits_step, _ = model.decode_step(params, cache, tokens[:, -1:], pos)

    a = np.asarray(logits_full[:, -1], np.float32)
    b = np.asarray(logits_step[:, 0], np.float32)
    np.testing.assert_allclose(a, b, atol=5e-3 * max(1, np.abs(a).max()),
                               rtol=1e-2)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_microbatched_grads_match_full_batch(arch):
    """Gradient accumulation must equal the full-batch gradient."""
    from repro.train.train_step import TrainStepConfig, make_train_step, \
        init_train_state

    cfg = reduced(get_config(arch))
    if cfg.family == "moe":
        pytest.skip("MoE capacity depends on token count; not bitwise equal")
    model = build_model(cfg)
    batch = make_batch(cfg, B=4, S=16)

    def loss_only(params):
        return model.loss(params, batch, remat_policy="none")[0]

    params = model.init(jax.random.PRNGKey(0))
    g_full = jax.grad(loss_only)(params)

    def loss_mb(params, mb):
        return model.loss(params, mb, remat_policy="none")[0]

    mbs = jax.tree_util.tree_map(
        lambda x: x.reshape(2, 2, *x.shape[1:]), batch)
    g_acc = None
    for i in range(2):
        mb = jax.tree_util.tree_map(lambda x: x[i], mbs)
        g = jax.grad(loss_mb)(params, mb)
        g_acc = g if g_acc is None else jax.tree_util.tree_map(
            jnp.add, g_acc, g)
    g_acc = jax.tree_util.tree_map(lambda x: x / 2, g_acc)

    flat_full = jax.tree_util.tree_leaves(g_full)
    flat_acc = jax.tree_util.tree_leaves(g_acc)
    for a, b in zip(flat_full, flat_acc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)


def test_long_context_applicability_matches_design():
    """long_500k runs exactly for the sub-quadratic archs (DESIGN.md)."""
    expect_long = {"mamba2-780m", "hymba-1.5b", "mixtral-8x22b"}
    got = {a for a in ALL_ARCHS
           if any(s.name == "long_500k"
                  for s in applicable_shapes(get_config(a)))}
    assert got == expect_long


def test_param_counts_in_expected_range():
    """Analytic N should land near the published sizes."""
    expected = {
        "yi-34b": (30e9, 40e9),
        "qwen2-0.5b": (0.4e9, 0.65e9),
        "mistral-large-123b": (110e9, 130e9),
        "qwen3-1.7b": (1.4e9, 2.4e9),
        "granite-moe-3b-a800m": (2.5e9, 4.0e9),
        "mixtral-8x22b": (130e9, 150e9),   # total incl. all experts
        "mamba2-780m": (0.6e9, 1.0e9),
        "phi-3-vision-4.2b": (3.3e9, 4.5e9),   # backbone only (stub frontend)
        "whisper-large-v3": (1.2e9, 1.9e9),
        "hymba-1.5b": (1.2e9, 2.0e9),
        "granite-4.0-h-micro": (2.9e9, 3.4e9),   # "3B"
    }
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)
    # MoE active params much smaller than total
    mix = get_config("mixtral-8x22b")
    assert mix.active_param_count() < 0.45 * mix.param_count()


def test_hymba_meta_tokens_change_output():
    cfg = reduced(get_config("hymba-1.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, with_targets=False)
    logits, _ = model.prefill(params, batch, model.init_cache(2, 40))
    params2 = dict(params)
    params2["meta_tokens"] = params["meta_tokens"] + 1.0
    logits2, _ = model.prefill(params2, batch, model.init_cache(2, 40))
    assert float(jnp.abs(logits - logits2).max()) > 1e-4


def test_vlm_patches_affect_text_logits():
    cfg = reduced(get_config("phi-3-vision-4.2b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, with_targets=False)
    l1, _ = model.prefill(params, batch, model.init_cache(2, 40))
    batch2 = dict(batch)
    batch2["patch_embeds"] = batch["patch_embeds"] + 5.0
    l2, _ = model.prefill(params, batch2, model.init_cache(2, 40))
    assert float(jnp.abs(l1 - l2).max()) > 1e-4


def test_whisper_encoder_affects_decoder():
    cfg = reduced(get_config("whisper-large-v3"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, with_targets=False)
    l1, _ = model.prefill(params, batch, model.init_cache(2, 40))
    batch2 = dict(batch)
    batch2["frames"] = batch["frames"] * 2.0 + 1.0
    l2, _ = model.prefill(params, batch2, model.init_cache(2, 40))
    assert float(jnp.abs(l1 - l2).max()) > 1e-4


def test_mixtral_sliding_window_masks_distant_tokens():
    """Stacked SWA has receptive field L*(window-1); beyond that a token
    perturbation must not reach the output."""
    cfg = reduced(get_config("mixtral-8x22b"))   # 2 layers, window 16
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 48
    field = cfg.num_layers * (cfg.sliding_window - 1)   # 30
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32))
    l1, _ = model.prefill(params, {"tokens": toks}, model.init_cache(B, S + 4))
    toks2 = toks.at[0, 0].set((int(toks[0, 0]) + 1) % cfg.vocab_size)
    l2, _ = model.prefill(params, {"tokens": toks2},
                          model.init_cache(B, S + 4))
    # last position (47) is > receptive field (30) from token 0
    assert S - 1 > field
    np.testing.assert_allclose(np.asarray(l1[:, -1]), np.asarray(l2[:, -1]),
                               atol=1e-5)


# --------------------------------------------------------------------------
# granite-4.0-h-micro: interleaved Mamba-2 and attention layers
# --------------------------------------------------------------------------
GRANITE = "granite-4.0-h-micro"

# sha256 (first 16 hex digits) of each architecture's full-size parameter
# spec tree: every leaf's path, shape, logical axes, dtype and init.
PARAM_TREE_DIGESTS = {
    "granite-moe-3b-a800m": "1fba248eee29bbd2",
    "hymba-1.5b": "f0e3ab0d091ad36d",
    "mamba2-780m": "0d3196d1563b410d",
    "mistral-large-123b": "b7e5e3bc74a76f48",
    "mixtral-8x22b": "3f1b8746c8fbb703",
    "phi-3-vision-4.2b": "f2116213f8f438e2",
    "qwen2-0.5b": "e014d168174346c8",
    "qwen3-1.7b": "7bd7ab2a256d1c6a",
    "whisper-large-v3": "fbf18d89c8aab144",
    "yi-34b": "46679dd1a2e6c90a",
}


@pytest.mark.parametrize("arch", sorted(PARAM_TREE_DIGESTS))
def test_existing_param_trees_unchanged(arch):
    import hashlib
    from repro.models.module import is_spec
    flat, _ = jax.tree_util.tree_flatten_with_path(
        build_model(get_config(arch)).param_specs(), is_leaf=is_spec)
    text = ";".join(f"{jax.tree_util.keystr(p)}:{s}" for p, s in flat)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARAM_TREE_DIGESTS[arch]


def test_granite_cut_has_its_parameter_count():
    """The benchmark's cut: the first period (10 layers) and a quarter of
    the vocabulary.  A mamba layer is 76,182,976 parameters, an attention
    layer 60,821,504, the embedding 25,088 x 2,048, the final norm 2,048."""
    import dataclasses
    cfg = dataclasses.replace(get_config(GRANITE), num_layers=10,
                              vocab_size=25088)
    assert cfg.layer_kinds == ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    specs = build_model(cfg).abstract_params()
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(specs))
    assert n == cfg.param_count() == 797_850_560
    assert 9 * 76_182_976 + 60_821_504 + 25088 * 2048 + 2048 == n


def test_granite_pattern_accepts_a_json_list():
    """A configuration file hands the pattern over as a list; the config
    stays hashable (a static argument of jitted functions)."""
    import dataclasses
    cfg = dataclasses.replace(get_config(GRANITE),
                              layer_pattern=["mamba", "attention"])
    assert cfg.layer_pattern == ("mamba", "attention")
    hash(cfg)
    with pytest.raises(ValueError, match="unknown layer kinds"):
        dataclasses.replace(cfg, layer_pattern=["mlp"])


def test_granite_stack_scans_each_run():
    """The first period, at tiny widths: one scan per maximal run of one
    kind (5 mamba, 1 attention, 4 mamba layers), not ten unrolled layers,
    each mixer and MLP under its named scope in the compiled op names."""
    import dataclasses
    from repro.models import stack
    full = get_config(GRANITE)
    assert stack.kind_runs(full.layer_kinds[:10]) == [
        ("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 4)]
    cfg = dataclasses.replace(reduced(full), num_layers=10,
                              layer_pattern=full.layer_pattern)
    model = build_model(cfg)
    params, batch = model.abstract_params(), make_batch(cfg)

    def loss(p, b):
        return model.loss(p, b)[0]

    scans = [e.params["length"] for e in jax.make_jaxpr(loss)(
        params, batch).eqns if e.primitive.name == "scan"]
    assert scans == [5, 1, 4]
    hlo = jax.jit(loss).lower(params, batch).compile().as_text()
    for scope in ("mixer.mamba", "mixer.attention", "mlp"):
        assert f"/{scope}/" in hlo, scope


def test_granite_refuses_to_decode():
    from repro.launch import serve
    cfg = reduced(get_config(GRANITE))
    model = build_model(cfg)
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        model.init_cache(2, 8)
    args = serve.make_parser().parse_args(["--arch", GRANITE, "--reduced"])
    with pytest.raises(SystemExit, match="attention"):
        serve.build_frontend(args)


def _granite_reference():
    import importlib
    import os
    import sys
    bench = os.path.join(os.path.dirname(__file__), "..", "chipbench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return (importlib.import_module("plain"),
            importlib.import_module("references.granite_hybrid_lm"))


def _granite_file(cfg, seq_len):
    """The reference's configuration keys for a program config."""
    return {
        "hidden_size": cfg.d_model, "shared_intermediate_size": cfg.d_ff,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.num_layers, "vocab_size": cfg.vocab_size,
        "mamba_d_state": cfg.ssm_state_dim, "mamba_d_head": cfg.ssm_head_dim,
        "mamba_n_heads": cfg.ssm_num_heads, "mamba_expand": cfg.ssm_expand,
        "mamba_d_conv": cfg.ssm_conv_width,
        "mamba_n_groups": cfg.ssm_num_groups, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "chunk_size": cfg.ssm_chunk,
        "layer_types": list(cfg.layer_pattern),
        "position_embedding_type": cfg.position_embedding,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "logits_scaling": cfg.logits_scaling, "rms_norm_eps": cfg.norm_eps,
        "train": {"seq_len": seq_len}}


def test_granite_matches_the_plain_reference():
    """A tiny granite-pattern model (mamba, attention, mamba; every
    multiplier as published) against ``chipbench``'s float32 reference on
    the same seeded weights: the loss, and each leaf's gradient norm.

    Both sides compute in float32; they differ in the order of summation
    only (the chunked scan against the quadratic form, the program's
    attention against the plain softmax), a few float32 ulps: measured
    1.7e-7 relative on the loss and 5.3e-7 on the worst leaf.  The
    tolerances leave more than ten times that: 1e-5 on the loss, 2e-5 on
    a leaf.  The same reference with its matrix products on bfloat16
    operands misses the leaf tolerance (measured 7.5e-3)."""
    plain, ref = _granite_reference()
    cfg = reduced(get_config(GRANITE))
    assert set(cfg.layer_kinds) == {"mamba", "attention"}
    B, S = 2, 32
    m = _granite_file(cfg, S)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    # the reference's own init gives the same weights from the same seed
    # (its float32 std and the program's float64 one round apart by 1 ulp)
    same = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.allclose(a, b, rtol=1e-6, atol=0)), params,
        plain.init_params(ref.param_specs(m), 3))
    assert all(jax.tree_util.tree_leaves(same))
    batch = make_batch(cfg, B=B, S=S, seed=3)

    def program(p):
        return model.loss(p, batch, remat_policy="dots")[0]

    def reference(dot):
        def loss(p):
            return sum(ref.row_loss(p, batch["tokens"][r],
                                    batch["targets"][r],
                                    batch["loss_mask"][r], m, dot)
                       for r in range(B)) / (B * S)
        return loss

    def bf16_dot(sub, a, b):
        return jnp.einsum(sub, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    def gaps(fn):
        loss, grads = jax.value_and_grad(fn)(params)
        got = plain.leaf_norms(grads)
        return (abs(float(loss) - float(want_loss)) / float(want_loss),
                max(abs(got[k] - want[k]) / want[k] for k in want))

    want_loss, want_grads = jax.value_and_grad(
        reference(plain.make_dot("highest")))(params)
    want = plain.leaf_norms(want_grads)
    loss_gap, leaf_gap = gaps(program)
    assert loss_gap < 1e-5 and leaf_gap < 2e-5, (loss_gap, leaf_gap)
    loss_gap, leaf_gap = gaps(reference(bf16_dot))
    assert loss_gap >= 1e-5 or leaf_gap >= 2e-5, (loss_gap, leaf_gap)
