"""The plain references against the program's models at tiny widths, both in
float32: the same mathematics must agree to float32 rounding. (On the chip the
benchmark compares the bfloat16 model at the published widths, with the
tolerances written in benchmarks/reference/vit.py.)"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

from benchmarks import harness
from benchmarks.reference import parity
from jimm_tpu import preset
from jimm_tpu.cli import _model_cls, _tiny_override

TIGHT = {"outputs": 2e-4, "loss": 2e-5, "grads": 2e-3}


def _run(config_name: str, loss: str) -> harness.Run:
    import json
    config = json.loads((harness.BENCH / "configs"
                         / f"{config_name}.json").read_text())
    return harness.Run(workload={"name": "t", "chips": 1},
                       cell={"traffic_params": {"loss": loss}}, config=config,
                       seed=3, seconds=1, trace=False, rehearse=True,
                       t_process_start=0.0)


@pytest.mark.parametrize("config_name,family,loss", [
    ("siglip_b16_256", "siglip", "siglip"),
    ("vit_l16_384", "vit", "cross_entropy"),
])
def test_float32_model_agrees_with_reference(config_name, family, loss,
                                             monkeypatch):
    run = _run(config_name, loss)
    cfg = _tiny_override(preset(run.config["preset"]))
    model = _model_cls(family)(cfg, rngs=nnx.Rngs(0), dtype=jnp.float32,
                               param_dtype=jnp.float32)
    ref = __import__(f"benchmarks.reference.{family}", fromlist=["x"])
    monkeypatch.setattr(ref, "TOLERANCE", TIGHT)
    result = types.SimpleNamespace(model=model, mesh=None, rules=None)
    with jax.default_matmul_precision("highest"):
        agree = parity.check_train(run, result)
    assert agree["ok"], agree
    assert np.isfinite(agree["loss_reference"])


def test_a_dropped_term_fails_the_comparison(monkeypatch):
    """Tolerances must be tight enough to catch left-out mathematics: drop the
    MLP bias in the reference and the bfloat16 bounds already refuse it."""
    from benchmarks.reference import vit
    run = _run("siglip_b16_256", "siglip")
    cfg = _tiny_override(preset(run.config["preset"]))
    model = _model_cls("siglip")(cfg, rngs=nnx.Rngs(0), dtype=jnp.float32,
                                 param_dtype=jnp.float32)
    # give the biases weight (they are initialised to zero)
    state = nnx.state(model, nnx.Param)
    keys = iter(jax.random.split(jax.random.key(0), 1000))
    state = jax.tree.map(
        lambda a: a + 0.5 * jax.random.normal(next(keys), a.shape, a.dtype)
        if a.ndim <= 2 else a, state)
    nnx.update(model, state)
    monkeypatch.setattr(vit, "linear", lambda x, p: x @ p["kernel"])
    result = types.SimpleNamespace(model=model, mesh=None, rules=None)
    agree = parity.check_train(run, result)
    assert not agree["ok"]
    assert agree["errors"]["outputs"] > 3 * agree["tolerance"]["outputs"]
