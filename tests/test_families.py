"""What every decoder family holds in the CLI's tables, one row a family:
its entries in `cli._family`, `cli._model_cls`, `cli.LM_FAMILIES`,
`trainer.LM_STEPS` and `cli._lm_counters`, its line in ``jimm-tpu presets``,
and the modules that no other preset may import. ``presets`` runs once and
one fresh Python builds every preset, and each family reads its part.

A new decoder family adds a row here and calls `family_util.train_rows` from
its own file's CLI test."""

import contextlib
import io
import json
import os
import subprocess
import sys
from collections.abc import Callable
from dataclasses import dataclass

import pytest

from jimm_tpu import (PRESETS, Granite, Kanana, KimiLinear, Ouro, Trinity,
                      cli, preset)
from jimm_tpu.train import trainer

MOE_COUNTERS = ["tokens_total", "assignments_total", "held_assignments_total"]


@dataclass(frozen=True)
class Family:
    preset: str
    name: str
    model_cls: type
    loss_fn: Callable                # `trainer.LM_STEPS[name][0]`
    counters: list[str]              # `cli._lm_counters` at the tiny size
    listed: tuple[str, ...]          # in its line of ``jimm-tpu presets``
    #: modules no other preset may import (its model's last), but for the
    #: presets that start with one of ``shared_with``
    modules: tuple[str, ...] = ()
    shared_with: tuple[str, ...] = ()


FAMILIES = {f.name: f for f in [
    Family("ouro-2.6b", "ouro", Ouro, trainer.lm_loss_fn,
           ["tokens_total", "block_applications_total"],
           ("x 4 passes", "depth=48", "seq=4096")),
    Family("kanana-2-30b-a3b", "kanana", Kanana, trainer.moe_lm_loss_fn,
           MOE_COUNTERS, ("experts=16/128 held", "vocab=16032")),
    Family("trinity-large", "trinity", Trinity, trainer.moe_lm_loss_fn,
           MOE_COUNTERS, ("experts=8/256 held", "vocab=25024")),
    Family("kimi-linear-48b-a3b", "kimi", KimiLinear, trainer.moe_lm_loss_fn,
           MOE_COUNTERS,
           ("828.9M", "experts=16/256 held", "depth=5", "seq=16384"),
           ("jimm_tpu.nn.kda", "jimm_tpu.ops.delta_rule",
            "jimm_tpu.models.kimi_linear"),
           # granite's Mamba-2 shares nn/kda.py's convolution
           ("granite",)),
    Family("granite-4.0-h-micro", "granite", Granite, trainer.dense_lm_loss_fn,
           ["tokens_total"], ("952.0M", "dense", "depth=10", "seq=16384"),
           ("jimm_tpu.nn.mamba2", "jimm_tpu.ops.ssd",
            "jimm_tpu.models.granite")),
]}
ISOLATED = [f for f in FAMILIES.values() if f.modules]


# -- the tables -------------------------------------------------------------

@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_family_is_an_entry_of_the_tables(family):
    row = FAMILIES[family]
    assert cli._family(row.preset) == family
    assert cli._model_cls(family) is row.model_cls
    assert cli.LM_FAMILIES[family] == {"lr": 1e-4, "warmup_steps": 20}
    assert trainer.LM_STEPS[family][0] is row.loss_fn
    assert [name for _, name, _ in cli._lm_counters(
        cli._tiny_override(preset(row.preset)), 2)] == row.counters


def test_language_model_families_are_one_table():
    """A second decoder is an entry, not a fourth ``if``: the step, the
    counters and the optimizer defaults of each are looked up by family."""
    assert set(cli.LM_FAMILIES) == set(trainer.LM_STEPS) == set(FAMILIES)
    assert set(cli.LM_FAMILIES) < set(cli._FAMILIES)


# -- ``jimm-tpu presets``, listed once ----------------------------------------

@pytest.fixture(scope="module")
def presets_out() -> str:
    with contextlib.redirect_stdout(io.StringIO()) as out:
        assert cli.main(["presets"]) == 0
    return out.getvalue()


def test_presets_lists_all(presets_out):
    for name in ("vit-base-patch16-224", "clip-vit-large-patch14",
                 "siglip-so400m-patch14-384", "siglip2-large-patch16-512"):
        assert name in presets_out


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_presets_lists_the_family(presets_out, family):
    row = FAMILIES[family]
    line = next(ln for ln in presets_out.splitlines()
                if ln.startswith(row.preset))
    for fragment in row.listed:
        assert fragment in line, (fragment, line)


# -- what a family may not cost the others ------------------------------------

#: one fresh Python: the watched modules present at import, then for each
#: preset in the order given (as its tiny size) those present once its model
#: class is looked up and once the model is built
_BUILD_EVERY_PRESET = """\
import json, sys
import jimm_tpu, jimm_tpu.cli as cli
from flax import nnx
from jimm_tpu import PRESETS
order, watched = json.loads(sys.argv[1])
present = lambda: sorted(m for m in watched if m in sys.modules)
record = {"at_import": present(), "presets": []}
for name in order:
    cls = cli._model_cls(cli._family(name))
    looked_up = present()
    cfg = cli._tiny_override(PRESETS[name])
    nnx.eval_shape(lambda: cls(cfg, rngs=nnx.Rngs(0)))
    record["presets"].append([name, looked_up, present()])
print(json.dumps(record))
"""


def _build_rank(name: str) -> int:
    """Presets of the watched families come last, in `ISOLATED`'s order:
    every other preset is built while a family's modules can still show."""
    return next((i for i, row in enumerate(ISOLATED)
                 if name.startswith(row.name)), -1)


@pytest.fixture(scope="module")
def every_preset_built() -> dict:
    order = sorted(PRESETS, key=_build_rank)
    watched = sorted(m for row in ISOLATED for m in row.modules)
    done = subprocess.run(
        [sys.executable, "-c", _BUILD_EVERY_PRESET,
         json.dumps([order, watched])], capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("family", [row.name for row in ISOLATED])
def test_no_other_preset_imports_the_familys_modules(every_preset_built,
                                                     family):
    row = FAMILIES[family]
    mine = set(row.modules)
    assert not mine & set(every_preset_built["at_import"]), "at import"
    checked = []
    for name, looked_up, built in every_preset_built["presets"]:
        if name.startswith((family, *row.shared_with)):
            break
        assert not mine & set(looked_up) and not mine & set(built), name
        checked.append(name)
    assert set(checked) == {name for name in PRESETS if not name.startswith(
        (family, *row.shared_with))}
    # its own model class, looked up, imports the family's model
    own = [looked_up for name, looked_up, _ in every_preset_built["presets"]
           if name.startswith(family)]
    assert own and row.modules[-1] in own[0]
