"""The chunked selective scan of Mamba-2 (`ops/ssd.py`) on the CPU: the
chunked form against the token-by-token recurrence of the plain float32
reference `benchmarks/reference/granite.py` (forward and every gradient),
its segment sums, its Pallas kernels against its XLA path (interpret mode)
and the rule that picks them, the products on three passes and their
counters. The model around it is `tests/test_granite.py`'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granite as ref
from benchmarks.reference import parity_granite
from jimm_tpu.ops import ssd
from jimm_tpu.ops.ssd import chunk_ssd


def _rel(a, b) -> float:
    assert bool(jnp.all(jnp.isfinite(a)))
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# -- the chunked selective scan ----------------------------------------------

def _scan_inputs(strength: float, s=150, b=2, h=4, p=8, g=1, n=16):
    """x, dt (softplus around 1), A = -(1 .. H) * strength, B, C and a
    cotangent; head h loses about 1.3 h * strength nats a token."""
    keys = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(keys[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h)) + 1.0)
    A = -jnp.arange(1, h + 1, dtype=jnp.float32) * strength
    B = jax.random.normal(keys[2], (b, s, g, n))
    C = jax.random.normal(keys[3], (b, s, g, n))
    return (x, dt, A, B, C), jax.random.normal(keys[4], (b, s, h, p))


@pytest.mark.parametrize("strength", [0.05, 1.0, 20.0],
                         ids=["long_memory", "the_start", "strong"])
@pytest.mark.parametrize("groups,chunk,pair_bytes", [
    (1, 64, 32 << 20), (1, 64, 1), (4, 32, 32 << 20), (2, 256, 32 << 20)],
    ids=["G1-one_slab", "G1-slabs_of_one", "GH-chunk32", "G2-chunk256"])
def test_chunked_scan_is_the_recurrence(strength, groups, chunk, pair_bytes,
                                        monkeypatch):
    """Forward and the gradients of all five inputs at 150 tokens (no
    multiple of any chunk here), with one group, one group a head and two,
    in one slab of chunks and in slabs of one. At ``strong`` the running sum
    of a chunk reaches -2e4 (float32's step there is 2e-3)."""
    monkeypatch.setattr(ssd, "_PAIR_BYTES", pair_bytes)
    inputs, w = _scan_inputs(strength, g=groups)
    with jax.default_matmul_precision("highest"):
        want = ref.ssm_scan(*inputs)
        want_grads = jax.grad(lambda *a: jnp.sum(ref.ssm_scan(*a) * w),
                              argnums=range(5))(*inputs)
    got = chunk_ssd(*inputs, chunk=chunk)
    got_grads = jax.grad(lambda *a: jnp.sum(chunk_ssd(*a, chunk=chunk) * w),
                         argnums=range(5))(*inputs)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert _rel(got, want) < 2e-6
    for name, a, b in zip(("x", "dt", "A", "B", "C"), got_grads, want_grads):
        assert _rel(a, b) < 1e-5, name


def test_segment_sums_hold_digits_a_difference_of_running_sums_loses():
    """Within a chunk of 256 tokens at 80 nats a token the running sum
    reaches -2e4. Where an exponent still matters (above -20), a difference of
    two running sums is off by up to 1e-3 (float32's step at -2e4); the sums
    of same-signed parts `chunk_ssd` takes are off by the step of the exponent
    itself."""
    a = -80.0 * jax.nn.softplus(jax.random.normal(jax.random.key(1), (3, 256))
                                + 1.0)
    into, out_of, pair = ssd._segment_sums(a)
    a64 = np.asarray(a, np.float64)
    run = np.cumsum(a64, axis=-1)
    exact = run[:, :, None] - run[:, None, :]
    lower = np.tril(np.ones((256, 256), bool))
    near = lower & (exact > -20.0)
    assert near.sum() >= 3 * 256     # the diagonal, and more
    ours = np.abs(np.asarray(pair, np.float64) - exact)[near]
    running = np.cumsum(np.asarray(a), axis=-1)    # float32
    differences = np.abs((running[:, :, None] - running[:, None, :])
                         .astype(np.float64) - exact)[near]
    assert ours.max() < 1e-5 and differences.max() > 5e-4
    assert np.all(np.isneginf(np.asarray(pair)[:, ~lower]))
    np.testing.assert_allclose(into, run, rtol=1e-6)
    np.testing.assert_allclose(out_of, run[:, -1:] - run, rtol=1e-6,
                               atol=1e-3)


def test_padding_tokens_leave_the_state_alone():
    """A length of one token past a chunk: the last token's output is the
    recurrence's, whatever the padding holds."""
    inputs, _ = _scan_inputs(1.0, s=65)
    want = ref.ssm_scan(*inputs)
    np.testing.assert_allclose(chunk_ssd(*inputs, chunk=64)[:, -1],
                               want[:, -1], rtol=1e-5, atol=1e-5)


# -- the Pallas kernels (interpret mode here) ----------------------------------

def _on_path(monkeypatch, path: str) -> None:
    """What `chunk_ssd` decides from the backend: the kernels where the
    backend is a TPU (interpret mode follows the real backend)."""
    monkeypatch.setattr(ssd, "_default_backend",
                        lambda: "tpu" if path == "kernel" else "cpu")


def _kernel_inputs(case: str, s: int, h=4, p=64, n=128):
    """Heads of 64 and a state of 128, the widths the kernels take. At
    ``the_start`` ``dt`` is about softplus(1) and A = -(1 .. H), as the
    model starts (no state outlives a chunk), x, B, C in bfloat16 as the
    model hands them over; at ``memory`` each head's step is log-spaced over
    `parity_granite.DT_RANGE` at A = -1, so the state handed from chunk to
    chunk carries the output, all in float32 (``memory_bfloat16``: x, B, C in
    bfloat16, so the state enters the kernels' three-pass products)."""
    keys = jax.random.split(jax.random.key(3), 5)
    x = jax.nn.silu(jax.random.normal(keys[0], (1, s, h, p)))
    if case == "the_start":
        bias, A = 1.0, -jnp.arange(1, h + 1, dtype=jnp.float32)
    else:
        bias = jnp.log(jnp.expm1(jnp.geomspace(*parity_granite.DT_RANGE, h)))
        A = -jnp.ones((h,))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, s, h)) + bias)
    B = jax.nn.silu(jax.random.normal(keys[2], (1, s, 1, n)))
    C = jax.nn.silu(jax.random.normal(keys[3], (1, s, 1, n)))
    if case != "memory":
        x, B, C = (t.astype(jnp.bfloat16) for t in (x, B, C))
    return (x, dt, A, B, C), jax.random.normal(keys[4], (1, s, h, p))


@pytest.mark.parametrize("case,tokens", [
    ("the_start", 512), ("memory", 1024), ("memory", 384),
    ("memory_bfloat16", 1024)],
    ids=["the_start", "memory", "memory-ragged_length", "memory-bfloat16"])
def test_the_kernels_are_the_xla_path(case, tokens, monkeypatch):
    """y and the gradients of all five inputs on the kernels against the
    XLA path, chunks of 256 as two tiles of 128: at the model's start, with
    a state that lives over many chunks (in float32 and in bfloat16), and at
    a length that is no multiple of the chunk (padded with tokens of ``dt =
    0``). A gradient in bfloat16 is held to its rounding, one in float32 to
    float32's."""
    inputs, w = _kernel_inputs(case, tokens)
    got = {}
    for path in ("xla", "kernel"):
        _on_path(monkeypatch, path)
        y, vjp = jax.vjp(lambda *a: chunk_ssd(*a, chunk=256), *inputs)
        got[path] = (y, *vjp(w))
    for name, a, b in zip(("y", "x", "dt", "A", "B", "C"), got["kernel"],
                          got["xla"]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        limit = 5e-4 if a.dtype == jnp.bfloat16 else 2e-6
        assert _rel(a.astype(jnp.float32), b.astype(jnp.float32)) < limit, \
            name


@pytest.mark.parametrize("x_shape,b_shape,chunk,backend,takes", [
    ((1, 16384, 64, 64), (1, 16384, 1, 128), 256, "tpu", True),  # the cell
    ((2, 300, 8, 128), (2, 300, 2, 256), 128, "tpu", True),
    ((1, 16384, 64, 64), (1, 16384, 1, 128), 256, "cpu", False),
    ((2, 32, 4, 16), (2, 32, 1, 16), 16, "tpu", False),    # the tiny preset
    ((1, 4096, 64, 64), (1, 4096, 1, 64), 256, "tpu", False),
    ((1, 4096, 64, 64), (1, 4096, 1, 128), 64, "tpu", False),
    ((1, 4096, 8, 64), (1, 4096, 8, 128), 256, "tpu", False),  # a head a group
    ((1, 4096, 8, 72), (1, 4096, 1, 128), 256, "tpu", False),
])
def test_the_kernels_take_the_tpu_shapes_and_xla_the_rest(
        x_shape, b_shape, chunk, backend, takes):
    assert ssd.kernel_takes(x_shape, b_shape, chunk, backend) is takes


def test_the_kernel_counter_counts_the_scans_built_on_the_kernels(
        monkeypatch):
    """``jimm_ssm_kernel_total`` beside ``calls_total`` / ``chunks_total``
    (every counter starts the test at zero: `tests/conftest.py`)."""
    from jimm_tpu import obs
    _on_path(monkeypatch, "kernel")
    wide, _ = _kernel_inputs("memory", 256)
    small, _ = _scan_inputs(1.0, s=32)

    def counts():
        snap = obs.snapshot()
        return [snap.get(f"jimm_ssm_{k}_total", 0)
                for k in ("calls", "chunks", "kernel")]

    chunk_ssd(*wide, chunk=128)
    assert counts() == [1, 2, 1]
    chunk_ssd(*small, chunk=16)
    assert counts() == [2, 4, 1]


_FORMS = {"nn": ((4, 128, 96), (4, 96, 64)), "nt": ((4, 128, 96), (4, 64, 96)),
          "tn": ((4, 96, 128), (4, 96, 64))}


@pytest.mark.parametrize("values", ["random", "bfloat16_exact"])
@pytest.mark.parametrize("exact_side", ["left", "right"])
@pytest.mark.parametrize("form", sorted(_FORMS))
def test_a_split_product_is_the_highest_product(form, exact_side, values):
    """`ssd._mx` of a float32 operand and a bfloat16 one (its three pieces
    side by side against the exact operand three times) against the
    ``HIGHEST`` product, batched over heads: to float32's rounding of a sum
    for random values, and exactly for a float32 operand that is itself
    bfloat16-exact, on a grid where every sum is exact in float32 whatever
    its order (so a dropped or rounded piece shows, and the order of the
    summation does not)."""
    keys = jax.random.split(jax.random.key(11), 2)

    def draw(key, shape, exact):
        v = jax.random.normal(key, shape)
        if values == "bfloat16_exact":
            v = jnp.round(v * 32) / 64
        return v.astype(jnp.bfloat16) if exact else v

    shape_a, shape_b = _FORMS[form]
    a = draw(keys[0], shape_a, exact_side == "left")
    b = draw(keys[1], shape_b, exact_side == "right")
    got = jax.jit(lambda a, b: ssd._mx(a, b, form))(a, b)
    want = ssd._dot(a.astype(jnp.float32), b.astype(jnp.float32), form)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    if values == "random":
        assert _rel(got, want) < 1e-6
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_pieces_sum_to_the_operand_exactly():
    """`ssd._pieces` of float32 values over many binades: three bfloat16
    pieces, each at most a third of the bits, whose float32 sum is the value
    bit for bit."""
    v = jax.random.normal(jax.random.key(5), (4096,)) \
        * jnp.exp2(jnp.linspace(-60.0, 60.0, 4096))
    pieces = ssd._pieces(v)
    assert all(t.dtype == jnp.bfloat16 for t in pieces)
    hi, mid, lo = (t.astype(jnp.float32) for t in pieces)
    np.testing.assert_array_equal(np.asarray(hi + mid + lo), np.asarray(v))


@pytest.mark.parametrize("path,dtype,want", [
    ("kernel", jnp.bfloat16, (9, 28)), ("kernel", jnp.float32, (0, 0)),
    ("xla", jnp.bfloat16, (0, 0))], ids=["kernel-bfloat16", "kernel-float32",
                                        "xla-bfloat16"])
def test_the_split_counter_counts_the_products_on_three_passes(
        path, dtype, want, monkeypatch):
    """``jimm_ssm_split_products_total``: the kernels' products built on the
    three-piece form, counted as a body is traced. In chunks of two tiles,
    with x, B, C in bfloat16, every product of the forward but ``C B^T``
    (9) and then the backward's, all but its three of two float32 operands
    (19 more); none with float32 inputs or on the XLA path."""
    from jimm_tpu import obs
    _on_path(monkeypatch, path)
    (x, dt, A, B, C), w = _kernel_inputs("memory", 256)
    x, B, C = (t.astype(dtype) for t in (x, B, C))

    def split():
        return obs.snapshot().get("jimm_ssm_split_products_total", 0)

    def scan(*a):
        return chunk_ssd(*a, chunk=256)

    jax.jit(scan).lower(x, dt, A, B, C)
    forward = split()
    jax.jit(lambda *a: jax.vjp(scan, *a)[1](w)).lower(x, dt, A, B, C)
    assert (forward, split()) == (want[0], want[0] + want[1])
