"""The chunked delta rule of Kimi Delta Attention (`ops/delta_rule.py`) on
the CPU: the chunked form against the token-by-token recurrence (forward
and every gradient) on its XLA path and on its Pallas kernels (interpret
mode), the kernels' backward against the XLA path's, the rule that picks
them, and the counters. The model around it is
`tests/test_kimi_linear.py`'s."""

import jax
import jax.numpy as jnp
import pytest

from jimm_tpu.ops import delta_rule
from jimm_tpu.ops.delta_rule import chunk_kda


# -- the chunked delta rule ---------------------------------------------------

def _recurrence(q, k, v, g, beta):
    """The equations as written, one token at a time."""
    def token(state, xs):
        q, k, v, g, b = xs
        state = state * jnp.exp(g)[..., None]
        read = jnp.einsum("bhd,bhde->bhe", k, state)
        state = state + jnp.einsum("bhd,bhe->bhde", k,
                                   (v - read) * b[..., None])
        return state, jnp.einsum("bhd,bhde->bhe", q, state)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    zero = jnp.zeros((*q.shape[:1], *q.shape[2:], v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(token, zero, xs)[1], 0, 1)


def _scan_inputs(decay: float, s=150, b=2, h=3, d=32):
    keys = jax.random.split(jax.random.key(0), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(keys[0], (b, s, h, d))) * d ** -0.5
    k = unit(jax.random.normal(keys[1], (b, s, h, d)))
    v = jax.random.normal(keys[2], (b, s, h, d))
    g = -decay * jax.nn.softplus(jax.random.normal(keys[3], (b, s, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, h)))
    return (q, k, v, g, beta), jax.random.normal(keys[5], (b, s, h, d))


#: (path, chunk, bytes of pairwise exponents a slab): the XLA path in one
#: slab of chunks and in several, at heads of 32; the Pallas kernels (in
#: interpret mode here) at the widths they take, heads of 128, in segments of
#: two chunks of 64 and of one chunk of 128
SCAN_PATHS = [("xla", 16, 256 << 20), ("xla", 16, 1 << 16),
              ("xla", 64, 256 << 20), ("xla", 64, 1 << 16),
              ("kernel", 64, None), ("kernel", 128, None)]


def _on_path(monkeypatch, path: str) -> None:
    """What `chunk_kda` decides from the backend: the kernels where the
    backend is a TPU (interpret mode follows the real backend)."""
    monkeypatch.setattr(delta_rule, "_default_backend",
                        lambda: "tpu" if path == "kernel" else "cpu")


@pytest.mark.parametrize("path,chunk,slab_bytes", SCAN_PATHS,
                         ids=["xla-16-one_slab", "xla-16-many_slabs",
                              "xla-64-one_slab", "xla-64-many_slabs",
                              "kernel-64", "kernel-128"])
@pytest.mark.parametrize("decay", [1.0, 16.0], ids=["mild", "strongest"])
def test_chunked_delta_rule_is_the_recurrence(decay, path, chunk, slab_bytes,
                                              monkeypatch):
    """Forward and the gradients of all five inputs, at 150 tokens (no
    multiple of either chunk), in one slab of chunks and in several, and on
    the kernels. At ``exp(A_log) = 16`` a channel loses up to 16 * softplus
    nats a token: ``exp(-G)`` overflows float32 inside a chunk, so only
    exponents of differences ``G_i - G_j``, ``i >= j``, keep this finite and
    equal."""
    _on_path(monkeypatch, path)
    if slab_bytes is not None:
        monkeypatch.setattr(delta_rule, "_PAIRWISE_BYTES", slab_bytes)
    inputs, w = _scan_inputs(decay, **({"b": 1, "h": 3, "d": 128}
                                       if path == "kernel" else {}))
    assert float(jnp.min(jnp.cumsum(inputs[3][:, :64], axis=1))) < (
        -88 if decay == 16.0 else -20)
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*inputs)
        want_grads = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * w),
                              argnums=range(5))(*inputs)
    got = chunk_kda(*inputs, chunk=chunk)
    got_grads = jax.grad(lambda *a: jnp.sum(chunk_kda(*a, chunk=chunk) * w),
                         argnums=range(5))(*inputs)
    assert got.shape == want.shape and got.dtype == jnp.float32

    def rel(a, b):
        assert bool(jnp.all(jnp.isfinite(a)))
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    assert rel(got, want) < 5e-6
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        assert rel(a, b) < (1e-4 if name == "g" else 1e-5), name


def test_the_kernels_backward_is_the_xla_paths(monkeypatch):
    """The kernels' five gradients against the XLA path's (``jax.vjp`` of
    `_slab` a slab at a time) to float32 rounding: 512 tokens, four segments
    of two chunks, at a gate between the two above."""
    inputs, w = _scan_inputs(4.0, s=512, b=1, h=2, d=128)
    grads = {}
    for path in ("xla", "kernel"):
        _on_path(monkeypatch, path)
        o, vjp = jax.vjp(lambda *a: chunk_kda(*a, chunk=64), *inputs)
        grads[path] = (o, *vjp(w))
    for name, a, b in zip("o q k v g beta".split(), grads["kernel"],
                          grads["xla"]):
        err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert err < 3e-6, (name, err)


@pytest.mark.parametrize("k_shape,v_shape,chunk,backend,takes", [
    ((1, 16384, 32, 128), (1, 16384, 32, 128), 64, "tpu", True),
    ((2, 100, 4, 256), (2, 100, 4, 256), 128, "tpu", True),
    ((1, 16384, 32, 128), (1, 16384, 32, 128), 64, "cpu", False),
    ((2, 32, 2, 16), (2, 32, 2, 16), 16, "tpu", False),    # the tiny preset
    ((1, 4096, 8, 72), (1, 4096, 8, 72), 64, "tpu", False),
    ((1, 4096, 8, 128), (1, 4096, 8, 256), 64, "tpu", False),
    ((1, 4096, 8, 128), (1, 4096, 8, 128), 32, "tpu", False),
    ((1, 4096, 8, 128), (1, 4096, 8, 128), 256, "tpu", False),
])
def test_the_kernels_take_the_tpu_shapes_and_xla_the_rest(
        k_shape, v_shape, chunk, backend, takes):
    assert delta_rule.kernel_takes(k_shape, v_shape, chunk, backend) is takes


def test_the_kernel_counter_counts_the_calls_built_on_the_kernels(
        monkeypatch):
    from jimm_tpu import obs
    _on_path(monkeypatch, "kernel")
    inputs, _ = _scan_inputs(1.0, s=128, b=1, h=2, d=128)
    small, _ = _scan_inputs(1.0, s=32)

    def counts():
        snap = obs.snapshot()
        return [snap.get(f"jimm_kda_{k}_total", 0)
                for k in ("calls", "chunks", "kernel")]

    before = counts()
    chunk_kda(*inputs, chunk=64)
    after_kernel = counts()
    chunk_kda(*small, chunk=16)
    after_xla = counts()
    assert [a - b for a, b in zip(after_kernel, before)] == [1, 2, 1]
    assert [a - b for a, b in zip(after_xla, after_kernel)] == [1, 2, 0]


@pytest.mark.parametrize("chunk", [4, 24])
def test_chunk_is_a_power_of_two_that_holds_a_sub_chunk(chunk):
    inputs, _ = _scan_inputs(1.0, s=32)
    with pytest.raises(ValueError, match="no power of two from 8 up"):
        chunk_kda(*inputs, chunk=chunk)
