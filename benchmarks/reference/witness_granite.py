"""The float32 witness of the ``granite_4_0_h_micro`` train cell's comparison:
the program built in float32 and run at ``HIGHEST``, and built in bfloat16 as
the cell runs it, each against the plain float32 reference on the same
weights and tokens, at the timed sizes (ten layers, 16,384 tokens).
``--forward-only`` reads the final hidden state and the loss alone: at
16,384 tokens the float32 program's backward does not fit a 16 GB chip (24.7
GB by XLA's count for a v5e), so its gradients are read at 4,096.

Where the float32 program agrees with the reference to float32 rounding, the
program computes the reference's mathematics, and what the bfloat16 one reads
beyond that (its gradients 0.16-0.67 from the reference, a token's hidden
state up to 0.78) is bfloat16 rounding and not a fault. Both are read by the
comparison's own functions (``parity_granite.model_side``,
``reference_side``, ``hidden_by_token``); the weights are the family's start
from ``--seed``.

    python3 benchmarks/reference/witness_granite.py [--seq-len 16384] [--seed n] [--forward-only] [--tiny]

prints one JSON line for each build of the program.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))


def readings(seq_len: int, seed: int, tiny: bool = False,
             grads: bool = True) -> list[dict]:
    """The two builds' distances from the reference: ``hidden``,
    ``hidden_by_token``, ``loss`` (relative) and, with ``grads``, each
    ``GRAD_LEAVES`` leaf's ``||a - b|| / ||b||``."""
    import jax
    import jax.numpy as jnp
    from flax import nnx

    from benchmarks.reference import granite as ref
    from benchmarks.reference import parity_granite as pg
    from benchmarks.reference.parity import _rel_norm
    from benchmarks.reference.parity_gqa_moe_lm import to_host
    from benchmarks.reference.parity_hybrid_lm import placements, put_back
    from jimm_tpu import Granite, preset
    from jimm_tpu.train.trainer import dense_lm_forward

    cfg = preset("granite-4.0-h-micro")
    if tiny:
        from jimm_tpu.cli import _tiny_override
        cfg = _tiny_override(cfg)
    cfg = dataclasses.replace(cfg, decoder=dataclasses.replace(
        cfg.decoder, seq_len=seq_len, remat=True))
    model = Granite(cfg, rngs=nnx.Rngs(seed), dtype=jnp.float32,
                    param_dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(seed + 1), (1, seq_len + 1),
                                0, cfg.decoder.vocab_size, jnp.int32)
    leaves = pg.grad_leaves(ref, model)
    sizes = pg.sizes_of(model)

    # the reference first, with the model's weights off the device (through
    # the host: a float32 weight would otherwise be the reference's own)
    where = placements(model)
    start = jax.device_get(nnx.to_pure_dict(nnx.state(model, nnx.Param)))
    to_host(model)
    params = ref.params_from_state(start, device=jax.devices()[0])
    del start
    wrap, attend = pg.reference_wrap(ref, sizes, seq_len)

    @jax.jit
    def forward(params, tokens):
        h = ref.hidden_states(params, tokens[:, :-1], sizes, wrap, attend)
        return h, ref.loss_of_hidden(params, h, tokens[:, 1:], sizes, wrap), {}

    with jax.default_matmul_precision("highest"):
        want = jax.device_get(
            pg.reference_side(ref, params, tokens, sizes, leaves, wrap, attend)
            if grads else forward(params, tokens))
    del params
    want_hidden, want_loss, want_grads = want
    put_back(model, where)

    def read(name: str, model, precision: str | None) -> dict:
        t0 = time.time()
        with jax.default_matmul_precision(precision):
            if grads:
                hidden, loss, _, got = jax.device_get(
                    pg.model_side(model, tokens, leaves))
            else:
                loss, hidden = jax.device_get(
                    nnx.jit(dense_lm_forward)(model, tokens))
                got = {}
        return {"build": name, "seq_len": seq_len, "seed": seed,
                "seconds": time.time() - t0,
                "hidden": _rel_norm(hidden, want_hidden),
                "hidden_by_token": pg.hidden_by_token(hidden, want_hidden),
                "loss": abs(float(loss) - float(want_loss))
                / max(1.0, abs(float(want_loss))),
                "grads": {n: _rel_norm(got[n], want_grads[n])
                          for n in got}}

    out = [read("float32_highest", model, "highest")]
    # the same weights in bfloat16, as the cell holds them
    cast = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                        nnx.state(model, nnx.Param))
    to_host(model)
    half = Granite(cfg, rngs=nnx.Rngs(seed), dtype=jnp.bfloat16,
                   param_dtype=jnp.bfloat16)
    nnx.update(half, cast)
    del model, cast
    out.append(read("bfloat16", half, None))
    return out


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seq-len", type=int, default=16384)
    parser.add_argument("--seed", type=int, default=5)
    parser.add_argument("--forward-only", action="store_true",
                        help="the hidden state and the loss, no gradients")
    parser.add_argument("--tiny", action="store_true",
                        help="the preset's tiny widths (a CPU check)")
    args = parser.parse_args(argv)
    for line in readings(args.seq_len, args.seed, args.tiny,
                         not args.forward_only):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
