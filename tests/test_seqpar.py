"""Tests for the sequence-parallel mesh axis: ring + Ulysses attention
(`jimm_tpu.parallel.seqpar`), the topology/tune/obs wiring around it, and
the temporal presets that motivate it.

Parity discipline mirrors the flash-attention suite: f32 allclose against
the reference oracles, bf16 by cosine (>= 0.999). The einsum hops run
everywhere; the per-hop Pallas flash hops run in interpret mode and are
marked slow.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jimm_tpu.ops.attention import (dot_product_attention,
                                    reference_attention,
                                    reference_sigmoid_attention)
from jimm_tpu.parallel.mesh import make_mesh
from jimm_tpu.parallel.seqpar import (plan_seq_parallel, ring_attention_sp,
                                      seq_parallel_attention,
                                      seqpar_comm_bytes)
from jimm_tpu.parallel.sharding import PRESET_RULES, use_sharding
from jimm_tpu.serve.topology import TopologyPlan, plan_topology


def _devices(n):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} devices, have {len(devs)}")
    return devs[:n]


def _seq_mesh(p):
    return make_mesh({"seq": p}, devices=_devices(p))


def _qkv(b, s, n, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, n, d), dtype) for k in ks)


def _cosine(a, b):
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-30))


def _ref(q, k, v, mask=None, kind="softmax", is_causal=False):
    if kind == "sigmoid":
        return reference_sigmoid_attention(q, k, v, mask=mask)
    m4 = None if mask is None else (mask != 0)[:, None, None, :]
    return reference_attention(q, k, v, mask=m4, is_causal=is_causal)


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------

class TestPlanner:
    def test_ulysses_iff_divisible_and_cheaper(self):
        # heads % p != 0 -> ring, always
        assert plan_seq_parallel(6, 4) == "ring"
        # divisible but p == 2: ring (comm tie, ring overlaps hops)
        assert plan_seq_parallel(8, 2) == "ring"
        # divisible and p > 2: head scatter moves fewer bytes
        assert plan_seq_parallel(8, 4) == "ulysses"
        assert plan_seq_parallel(16, 8) == "ulysses"

    def test_forced_plans_validate(self):
        assert plan_seq_parallel(6, 4, plan="ring") == "ring"
        with pytest.raises(ValueError, match="divisible"):
            plan_seq_parallel(6, 4, plan="ulysses")
        with pytest.raises(ValueError, match="unknown"):
            plan_seq_parallel(8, 4, plan="zigzag")

    def test_comm_bytes_formulas(self):
        b, s, n, d, p = 2, 256, 8, 64, 4
        local = (s // p) * n * d * 2 * b
        assert seqpar_comm_bytes(b, s, n, d, p) == 2 * (p - 1) * local
        assert seqpar_comm_bytes(b, s, n, d, p, masked=True) == \
            2 * (p - 1) * local + (p - 1) * b * (s // p) * 4
        assert seqpar_comm_bytes(b, s, n, d, p, plan="ulysses") == \
            4 * local * (p - 1) // p
        # the auto rule's premise: ulysses strictly cheaper for p > 2
        assert seqpar_comm_bytes(b, s, n, d, 4, plan="ulysses") < \
            seqpar_comm_bytes(b, s, n, d, 4)
        with pytest.raises(ValueError):
            seqpar_comm_bytes(b, s, n, d, p, plan="nope")


# ---------------------------------------------------------------------------
# Ring parity — einsum hops, f32
# ---------------------------------------------------------------------------

class TestRingParityF32:
    TOL = 2e-5

    @pytest.fixture()
    def mesh(self):
        return _seq_mesh(4)

    @pytest.fixture()
    def qkv(self):
        return _qkv(2, 64, 6, 16)

    @pytest.fixture()
    def mask(self):
        m = jax.random.bernoulli(jax.random.PRNGKey(9), 0.8, (2, 64))
        return m.at[:, 0].set(True)

    def test_softmax_forward(self, mesh, qkv):
        q, k, v = qkv
        o = ring_attention_sp(q, k, v, mesh=mesh, impl="einsum")
        np.testing.assert_allclose(o, _ref(q, k, v), atol=self.TOL)

    def test_masked_forward(self, mesh, qkv, mask):
        q, k, v = qkv
        o = ring_attention_sp(q, k, v, mask=mask, mesh=mesh, impl="einsum")
        np.testing.assert_allclose(o, _ref(q, k, v, mask=mask),
                                   atol=self.TOL)

    def test_masked_accepts_4d_key_padding(self, mesh, qkv, mask):
        q, k, v = qkv
        o = ring_attention_sp(q, k, v, mask=mask[:, None, None, :],
                              mesh=mesh, impl="einsum")
        np.testing.assert_allclose(o, _ref(q, k, v, mask=mask),
                                   atol=self.TOL)

    def test_sigmoid_forward(self, mesh, qkv, mask):
        q, k, v = qkv
        o = ring_attention_sp(q, k, v, kind="sigmoid", mask=mask, mesh=mesh,
                              impl="einsum")
        np.testing.assert_allclose(o, _ref(q, k, v, mask=mask,
                                           kind="sigmoid"), atol=self.TOL)

    def test_causal_forward(self, mesh, qkv):
        q, k, v = qkv
        o = ring_attention_sp(q, k, v, is_causal=True, mesh=mesh,
                              impl="einsum")
        np.testing.assert_allclose(o, _ref(q, k, v, is_causal=True),
                                   atol=self.TOL)

    @pytest.mark.parametrize("kw", [
        {}, {"masked": True}, {"kind": "sigmoid", "masked": True},
    ], ids=["softmax", "masked", "sigmoid"])
    def test_grads_match_reference(self, mesh, qkv, mask, kw):
        q, k, v = qkv
        m = mask if kw.get("masked") else None
        kind = kw.get("kind", "softmax")

        def ring_loss(q, k, v):
            o = ring_attention_sp(q, k, v, mask=m, kind=kind, mesh=mesh,
                                  impl="einsum")
            return jnp.sum(jnp.sin(o))

        def ref_loss(q, k, v):
            return jnp.sum(jnp.sin(_ref(q, k, v, mask=m, kind=kind)))

        got = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", got, want):
            np.testing.assert_allclose(a, b, atol=1e-4,
                                       err_msg=f"d{name} ({kind})")

    def test_rejects_indivisible_sequence(self, mesh):
        q, k, v = _qkv(1, 66, 4, 8)
        with pytest.raises(ValueError, match="not divisible"):
            ring_attention_sp(q, k, v, mesh=mesh)

    def test_rejects_dense_mask(self, mesh, qkv):
        q, k, v = qkv
        dense = jnp.ones((2, 1, 64, 64), bool)
        with pytest.raises(ValueError, match="KEY-PADDING"):
            ring_attention_sp(q, k, v, mask=dense, mesh=mesh)


# ---------------------------------------------------------------------------
# Ring parity — bf16 by cosine
# ---------------------------------------------------------------------------

class TestRingParityBf16:
    COS = 0.999

    @pytest.mark.parametrize("kw", [
        {}, {"masked": True}, {"kind": "sigmoid", "masked": True},
    ], ids=["softmax", "masked", "sigmoid"])
    def test_forward_and_grads_cosine(self, kw):
        mesh = _seq_mesh(4)
        q, k, v = _qkv(2, 64, 4, 16, dtype=jnp.bfloat16)
        mask = (jax.random.bernoulli(jax.random.PRNGKey(9), 0.8, (2, 64))
                .at[:, 0].set(True)) if kw.get("masked") else None
        kind = kw.get("kind", "softmax")
        o = ring_attention_sp(q, k, v, mask=mask, kind=kind, mesh=mesh,
                              impl="einsum")
        want = _ref(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), mask=mask, kind=kind)
        assert o.dtype == jnp.bfloat16
        assert _cosine(o.astype(jnp.float32), want) >= self.COS

        def ring_loss(q, k, v):
            return jnp.sum(jnp.sin(ring_attention_sp(
                q, k, v, mask=mask, kind=kind, mesh=mesh,
                impl="einsum").astype(jnp.float32)))

        def ref_loss(q, k, v):
            return jnp.sum(jnp.sin(_ref(q, k, v, mask=mask, kind=kind)))

        got = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        want_g = jax.grad(ref_loss, argnums=(0, 1, 2))(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32))
        for name, a, b in zip("qkv", got, want_g):
            assert _cosine(a, b) >= self.COS, f"d{name} ({kind})"


# ---------------------------------------------------------------------------
# Mask placement across ring shards; NaFlex-style odd lengths
# ---------------------------------------------------------------------------

class TestMaskPlacement:
    """The traveling mask rows must be exact no matter where the padding
    falls relative to the ring's shard boundaries (S=64, p=4 -> shard
    boundaries at 16/32/48)."""

    def _check(self, keep_slices, s=64, p=4):
        mesh = _seq_mesh(p)
        q, k, v = _qkv(2, s, 4, 16, seed=3)
        keep = np.ones((2, s), bool)
        for sl in keep_slices:
            keep[:, sl] = False
        mask = jnp.asarray(keep)
        o = ring_attention_sp(q, k, v, mask=mask, mesh=mesh, impl="einsum")
        np.testing.assert_allclose(o, _ref(q, k, v, mask=mask), atol=2e-5)

    def test_padding_inside_one_shard(self):
        # dropped keys 20..27 sit strictly inside shard 1 (16..31)
        self._check([slice(20, 28)])

    def test_padding_straddles_shard_boundary(self):
        # dropped keys 44..51 cross the shard 2 -> 3 boundary at 48
        self._check([slice(44, 52)])

    def test_whole_shard_masked_out(self):
        # shard 2 (32..47) contributes nothing; its hop must be a no-op
        self._check([slice(32, 48)])

    def test_trailing_naflex_padding(self):
        self._check([slice(50, 64)])

    @pytest.mark.parametrize("s_real", [257, 577])
    def test_odd_lengths_pad_to_ring(self, s_real):
        """NaFlex workflow for ring-indivisible sequences: pad to the next
        multiple of the axis, mask the tail, compare the real rows against
        the unsharded masked oracle at the padded length."""
        p = 4
        s_pad = -(-s_real // p) * p
        mesh = _seq_mesh(p)
        q, k, v = _qkv(1, s_pad, 2, 16, seed=s_real)
        keep = np.zeros((1, s_pad), bool)
        keep[:, :s_real] = True
        mask = jnp.asarray(keep)
        o = ring_attention_sp(q, k, v, mask=mask, mesh=mesh, impl="einsum")
        want = _ref(q, k, v, mask=mask)
        np.testing.assert_allclose(np.asarray(o)[:, :s_real],
                                   np.asarray(want)[:, :s_real], atol=2e-5)


# ---------------------------------------------------------------------------
# Ulysses head scatter
# ---------------------------------------------------------------------------

class TestUlysses:
    def test_masked_parity_exact(self):
        mesh = _seq_mesh(4)
        q, k, v = _qkv(2, 64, 8, 16, seed=5)
        mask = (jax.random.bernoulli(jax.random.PRNGKey(9), 0.8, (2, 64))
                .at[:, 0].set(True))
        o = seq_parallel_attention(q, k, v, mask=mask, mesh=mesh,
                                   plan="ulysses", impl="einsum")
        np.testing.assert_allclose(o, _ref(q, k, v, mask=mask), atol=2e-5)

    def test_auto_plan_picks_ulysses_when_divisible(self):
        mesh = _seq_mesh(4)
        q, k, v = _qkv(2, 64, 8, 16, seed=5)
        mask = jnp.ones((2, 64), bool)
        got = seq_parallel_attention(q, k, v, mask=mask, kind="sigmoid",
                                     mesh=mesh, plan="auto", impl="einsum")
        np.testing.assert_allclose(
            got, _ref(q, k, v, mask=mask, kind="sigmoid"), atol=2e-5)

    def test_auto_plan_falls_back_to_ring(self):
        # 6 heads % 4 != 0: the planner must choose ring, and still be exact
        mesh = _seq_mesh(4)
        q, k, v = _qkv(2, 64, 6, 16, seed=7)
        o = seq_parallel_attention(q, k, v, mesh=mesh, plan="auto",
                                   impl="einsum")
        np.testing.assert_allclose(o, _ref(q, k, v), atol=2e-5)


# ---------------------------------------------------------------------------
# dot_product_attention routing
# ---------------------------------------------------------------------------

class TestAttentionRouting:
    def _inputs(self, s=64, n=4):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        return tuple(jax.random.normal(k, (2, s, n, 16), jnp.float32)
                     for k in ks)

    def test_auto_routes_under_ambient_seq_mesh(self):
        q, k, v = self._inputs()
        mesh = _seq_mesh(4)
        want = dot_product_attention(q, k, v, impl="xla")
        with use_sharding(mesh, PRESET_RULES["sp"]):
            got = dot_product_attention(q, k, v)
        np.testing.assert_allclose(got, want, atol=2e-5)

    def test_explicit_ring_and_ulysses_impls(self):
        q, k, v = self._inputs()
        mesh = _seq_mesh(4)
        want = dot_product_attention(q, k, v, impl="xla")
        with use_sharding(mesh, PRESET_RULES["sp"]):
            ring = dot_product_attention(q, k, v, impl="ring")
            uly = dot_product_attention(q, k, v, impl="ulysses")
        np.testing.assert_allclose(ring, want, atol=2e-5)
        np.testing.assert_allclose(uly, want, atol=2e-5)

    def test_indivisible_sequence_falls_through(self):
        # the MAP pool's 1-row probe (and any S % p != 0) must not try to
        # ring-shard — it silently stays on the single-chip path
        q, _, _ = self._inputs()
        kv = jax.random.normal(jax.random.PRNGKey(3), (2, 63, 4, 16))
        probe = jax.random.normal(jax.random.PRNGKey(4), (2, 1, 4, 16))
        mesh = _seq_mesh(4)
        want = dot_product_attention(probe, kv, kv, impl="xla")
        with use_sharding(mesh, PRESET_RULES["sp"]):
            got = dot_product_attention(probe, kv, kv)
        np.testing.assert_allclose(got, want, atol=2e-5)

    def test_no_mesh_means_single_chip(self):
        q, k, v = self._inputs()
        got = dot_product_attention(q, k, v)
        np.testing.assert_allclose(got, dot_product_attention(q, k, v,
                                                              impl="xla"),
                                   atol=2e-5)

    def test_explicit_impl_without_seq_axis_raises(self):
        q, k, v = self._inputs()
        with pytest.raises(ValueError):
            dot_product_attention(q, k, v, impl="ring")


# ---------------------------------------------------------------------------
# Per-hop flash hops (interpret mode — slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestRingFlashHops:
    @pytest.mark.parametrize("kw", [
        {}, {"masked": True}, {"kind": "sigmoid", "masked": True},
    ], ids=["softmax", "masked", "sigmoid"])
    def test_flash_forward_and_grads(self, kw):
        mesh = _seq_mesh(2)
        q, k, v = _qkv(1, 64, 2, 64)
        mask = (jax.random.bernoulli(jax.random.PRNGKey(9), 0.8, (1, 64))
                .at[:, 0].set(True)) if kw.get("masked") else None
        kind = kw.get("kind", "softmax")
        o = ring_attention_sp(q, k, v, mask=mask, kind=kind, mesh=mesh,
                              impl="flash")
        np.testing.assert_allclose(o, _ref(q, k, v, mask=mask, kind=kind),
                                   atol=2e-4)

        def ring_loss(q, k, v):
            return jnp.sum(jnp.sin(ring_attention_sp(
                q, k, v, mask=mask, kind=kind, mesh=mesh, impl="flash")))

        def ref_loss(q, k, v):
            return jnp.sum(jnp.sin(_ref(q, k, v, mask=mask, kind=kind)))

        got = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", got, want):
            np.testing.assert_allclose(a, b, atol=5e-4,
                                       err_msg=f"flash d{name} ({kind})")

    def test_flash_causal_rejected(self):
        mesh = _seq_mesh(2)
        q, k, v = _qkv(1, 64, 2, 64)
        with pytest.raises(ValueError, match="non-causal"):
            ring_attention_sp(q, k, v, is_causal=True, mesh=mesh,
                              impl="flash")


# ---------------------------------------------------------------------------
# Topology: the third mesh axis
# ---------------------------------------------------------------------------

class TestTopologySeqAxis:
    def test_plan_carries_seq_parallel(self):
        devs = _devices(8)
        plan = plan_topology(2, 1, 4, devices=devs)
        assert plan.seq_parallel == 4
        assert plan.devices_used == 8
        assert len(plan.device_groups) == 2
        assert all(len(g) == 4 for g in plan.device_groups)
        assert plan.describe()["seq_parallel"] == 4
        for mesh in plan.meshes():
            assert dict(mesh.shape)["seq"] == 4

    def test_seq1_collapses_to_legacy_plan(self):
        """Degenerate seq=1 must be byte-identical to the two-axis world:
        same groups, same describe, same mesh axis names — which is what
        keeps AOT fingerprints shared with pre-seq artifacts."""
        devs = _devices(8)
        legacy = plan_topology(2, 2, devices=devs)
        degenerate = plan_topology(2, 2, 1, devices=devs)
        assert degenerate == legacy
        assert degenerate.describe() == legacy.describe()
        for a, b in zip(degenerate.meshes(), legacy.meshes()):
            assert a.shape == b.shape
            assert a.axis_names == b.axis_names
            assert "seq" not in a.axis_names

    def test_default_seq_parallel_is_one(self):
        plan = plan_topology(devices=_devices(1))
        assert plan.seq_parallel == 1
        assert plan.is_trivial
        assert not plan_topology(1, 1, 2, devices=_devices(2)).is_trivial

    def test_infeasible_error_enumerates_splits(self):
        devs = _devices(8)
        with pytest.raises(ValueError) as e:
            plan_topology(3, 3, 1, devices=devs)
        msg = str(e.value)
        assert "feasible splits" in msg
        # every (data, model, seq) factorization of 8 shows up
        assert "data=2 model=2 seq=2" in msg
        assert "data=1 model=1 seq=8" in msg
        assert "data=8 model=1 seq=1" in msg
        assert str(3 * 3 * 1) in msg
        assert "xla_force_host_platform_device_count" in msg

    def test_mesh_group_is_model_times_seq(self):
        devs = _devices(8)
        plan = plan_topology(2, 2, 2, devices=devs)
        assert all(len(g) == 4 for g in plan.device_groups)
        for mesh in plan.meshes():
            shape = dict(mesh.shape)
            assert shape.get("model") == 2 and shape.get("seq") == 2


# ---------------------------------------------------------------------------
# Tune registration
# ---------------------------------------------------------------------------

class TestRingTune:
    def test_ring_kernel_registered(self):
        from jimm_tpu.tune.api import KERNELS
        from jimm_tpu.tune.space import ring_space
        spec = KERNELS["ring_attention"]
        assert spec.space is ring_space

    def test_ring_vmem_model_syncs_with_kernel(self):
        """The ring hop runs the masked-flash kernel on local chunks, so its
        VMEM model must track the kernel's own estimate exactly — the same
        sync discipline as every other tuned kernel."""
        import jimm_tpu.ops.flash_attention as fa
        from jimm_tpu.tune.space import ring_vmem_bytes
        for bq in (128, 256):
            for bk in (128, 256, 512):
                for d in (64, 128, 256):
                    assert ring_vmem_bytes(bq, bk, d) == \
                        fa._per_head_vmem_bytes(bq, bk, d, has_mask=True)

    def test_ring_space_keys_on_local_chunks(self):
        from jimm_tpu.tune.space import FLASH_VMEM_BUDGET, ring_space, \
            ring_vmem_bytes
        local = (4, 512, 8, 64)  # (B, S/p, N, D)
        cands = ring_space((local, local, local))
        assert cands, "no feasible ring hop configs for a 512-token chunk"
        for c in cands:
            assert ring_vmem_bytes(c["block_q"], c["block_k"], 64) \
                <= FLASH_VMEM_BUDGET

    def test_best_config_resolves_ring_default(self):
        from jimm_tpu.tune import best_config
        cfg = best_config("ring_attention",
                          ((2, 64, 4, 16),) * 3,
                          (jnp.float32,) * 3,
                          default={"block_q": 128, "block_k": 512})
        assert cfg == {"block_q": 128, "block_k": 512}


# ---------------------------------------------------------------------------
# Temporal presets
# ---------------------------------------------------------------------------

class TestTemporalPreset:
    def test_presets_exist_and_flatten_frames(self):
        from jimm_tpu.configs import preset
        cfg = preset("vit-temporal-small-patch16-224-f8")
        v = cfg.vision
        assert v.num_frames == 8
        grid = v.image_size // v.patch_size
        # MAP pooling: no CLS token, so T * grid^2 divides any even ring
        assert v.pooling == "map"
        assert v.num_patches == 8 * grid * grid
        assert v.seq_len == v.num_patches
        assert v.seq_len % 8 == 0

    def test_tower_forward_on_clips(self):
        from flax import nnx

        from jimm_tpu.cli import _tiny_override
        from jimm_tpu.configs import preset
        from jimm_tpu.nn.vision import VisionTower
        cfg = _tiny_override(preset("vit-temporal-small-patch16-224-f8"))
        v = cfg.vision
        tower = VisionTower(v, rngs=nnx.Rngs(0))
        clips = jnp.zeros((2, v.num_frames, v.image_size, v.image_size, 3))
        out = tower(clips)
        assert out.shape == (2, v.width)

    def test_tower_rejects_wrong_frame_count(self):
        from flax import nnx

        from jimm_tpu.cli import _tiny_override
        from jimm_tpu.configs import preset
        from jimm_tpu.nn.vision import VisionTower
        cfg = _tiny_override(preset("vit-temporal-small-patch16-224-f8"))
        v = cfg.vision
        tower = VisionTower(v, rngs=nnx.Rngs(0))
        with pytest.raises(ValueError, match="temporal tower expects"):
            tower(jnp.zeros((2, 4, v.image_size, v.image_size, 3)))
        with pytest.raises(ValueError, match="temporal tower expects"):
            tower(jnp.zeros((2, v.image_size, v.image_size, 3)))

    def test_synthetic_clips(self):
        from jimm_tpu.data.synthetic import blob_classification
        imgs, labels = next(blob_classification(4, image_size=16,
                                                num_frames=8))
        assert imgs.shape == (4, 8, 16, 16, 3)
        assert labels.shape == (4,)
        # num_frames=1 keeps the legacy stream byte for byte
        legacy, _ = next(blob_classification(4, image_size=16))
        tagged, _ = next(blob_classification(4, image_size=16, num_frames=1))
        np.testing.assert_array_equal(legacy, tagged)


# ---------------------------------------------------------------------------
# Observability: permuted-bytes accounting
# ---------------------------------------------------------------------------

class TestRingObservability:
    def test_bytes_permuted_counter_accounts_the_plan(self):
        from jimm_tpu.obs.registry import get_registry
        counter = get_registry("jimm_ring").counter(
            "jimm_ring_bytes_permuted_total")
        mesh = _seq_mesh(4)
        q, k, v = _qkv(2, 64, 6, 16)
        before = counter.value
        ring_attention_sp(q, k, v, mesh=mesh, impl="einsum")
        expect = seqpar_comm_bytes(2, 64, 6, 16, 4, itemsize=4) * 4
        assert counter.value - before == expect
