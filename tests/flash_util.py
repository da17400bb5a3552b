"""Helpers shared by the flash-attention test files: random inputs, what a
traced call builds (kernels, their grids, the regime counters), the error of
a gradient against the reference, and the TPU lowering's text."""

import re

import jax
import jax.numpy as jnp
import numpy as np

from jimm_tpu.obs.registry import get_registry


def qkv(rng, b=2, s=256, n=2, d=64, dtype=np.float32):
    return tuple(jnp.asarray(rng.randn(b, s, n, d).astype(dtype) * 0.5)
                 for _ in range(3))


def qkv_split(rng, b, s, n, d_qk, d_v, dtype=np.float32):
    q, k = (jnp.asarray(rng.randn(b, s, n, d_qk).astype(dtype) * 0.5)
            for _ in range(2))
    return q, k, jnp.asarray(rng.randn(b, s, n, d_v).astype(dtype) * 0.5)


def key_mask(rng, b, s):
    m = rng.rand(b, s) > 0.3
    m[:, 0] = True
    return jnp.asarray(m)


def kernel_calls(fn, *args, regimes=("single_tile", "tiled")):
    """(single-tile, tiled) pallas_calls that tracing ``fn`` builds, or the
    counts of whichever ``jimm_flash_<regime>_total`` are asked for."""
    reg = get_registry("jimm_flash")
    counters = [reg.counter(f"{r}_total") for r in regimes]
    before = [c.value for c in counters]
    jax.make_jaxpr(fn)(*args)
    return tuple(int(c.value - b) for c, b in zip(counters, before))


def kernel_grids(fn, *args):
    """The grid of every pallas_call in ``fn``'s jaxpr, custom_vjp rules
    and all (take them from a gradient's jaxpr)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def grad_err(flash_loss, ref_loss, args):
    gf = jax.grad(flash_loss, argnums=tuple(range(len(args))))(*args)
    gr = jax.grad(ref_loss, argnums=tuple(range(len(args))))(*args)
    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(gf, gr))


def ref_lse(q, k, v):
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(q.shape[-1])
    return jax.nn.logsumexp(logits, axis=-1)


def tpu_text(fn, *specs):
    """StableHLO of ``fn`` lowered for the TPU with the kernels as Mosaic
    calls (their serialized bodies cut out: they carry source lines)."""
    text = jax.jit(fn).trace(*specs).lower(
        lowering_platforms=("tpu",)).as_text()
    return re.sub(r'backend_config = "[^"]*"', "backend_config = <kernel>",
                  text)
