"""Contrastive losses: CLIP softmax and SigLIP sigmoid, plus ICI ring
implementations of both (chunked sigmoid, and streaming-logsumexp InfoNCE).

The reference has no training losses for its dual-tower models at all (only
the MNIST example's cross-entropy, ref `examples/vit_training.py:76`). The
north star (`BASELINE.json`) requires the SigLIP sigmoid all-pairs loss as an
ICI ring: text embeddings travel around the data-parallel ring via
``jax.lax.ppermute`` inside ``shard_map`` and each device accumulates its
local-images x traveling-texts chunk — the SigLIP paper's "chunked" algorithm
— so the full B x B logit matrix is never materialized on one chip.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def clip_softmax_loss(img: jax.Array, txt: jax.Array, logit_scale: jax.Array
                      ) -> jax.Array:
    """Symmetric InfoNCE over the global batch (CLIP). Under pjit with batch
    sharded over "data", XLA inserts the all-gathers for the full logits."""
    img = img / jnp.linalg.norm(img, axis=-1, keepdims=True)
    txt = txt / jnp.linalg.norm(txt, axis=-1, keepdims=True)
    logits = jnp.exp(logit_scale) * img @ txt.T
    labels = jnp.arange(logits.shape[0])
    li = optax_softmax_ce(logits, labels)
    lt = optax_softmax_ce(logits.T, labels)
    return (li + lt) / 2


def optax_softmax_ce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(logp[jnp.arange(logits.shape[0]), labels])


def sigmoid_pairwise_loss(img: jax.Array, txt: jax.Array,
                          logit_scale: jax.Array, logit_bias: jax.Array
                          ) -> jax.Array:
    """Dense SigLIP sigmoid loss over the full batch — the numerical oracle
    for the ring version (and fine on a single chip).

    loss = -mean_i sum_j log sigmoid(z_ij * (scale * <img_i, txt_j> + bias)),
    z_ij = +1 on the diagonal, -1 elsewhere (SigLIP paper eq. 1).
    """
    img = img / jnp.linalg.norm(img, axis=-1, keepdims=True)
    txt = txt / jnp.linalg.norm(txt, axis=-1, keepdims=True)
    logits = jnp.exp(logit_scale) * img @ txt.T + logit_bias
    n = logits.shape[0]
    z = 2 * jnp.eye(n, dtype=logits.dtype) - 1
    return -jnp.sum(jax.nn.log_sigmoid(z * logits)) / n


def _ring_sigmoid_local(img: jax.Array, txt: jax.Array, scale: jax.Array,
                        bias: jax.Array, *, axis_name) -> jax.Array:
    """Per-device body: local images stay put; text chunks ride the ring.
    ``axis_name`` may be a tuple of mesh axes (e.g. ``("replica", "data")``
    on a hybrid DCN x ICI mesh) — the ring then runs over the linearized
    product axis."""
    n_dev = jax.lax.axis_size(axis_name)
    b = img.shape[0]
    img = img / jnp.linalg.norm(img, axis=-1, keepdims=True)
    txt = txt / jnp.linalg.norm(txt, axis=-1, keepdims=True)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def chunk_loss(txt_chunk: jax.Array, positives: jax.Array) -> jax.Array:
        logits = jnp.exp(scale) * img @ txt_chunk.T + bias
        z = jnp.where(positives, 1.0, -1.0).astype(logits.dtype)
        return -jnp.sum(jax.nn.log_sigmoid(z * logits))

    def step(carry, _):
        txt_chunk, acc = carry
        # traveling chunks are all negatives (positives live in chunk 0,
        # handled outside the scan)
        txt_chunk = jax.lax.ppermute(txt_chunk, axis_name, perm)
        acc = acc + chunk_loss(txt_chunk, jnp.zeros((b, b), bool))
        return (txt_chunk, acc), None

    # own chunk first (diagonal positives), then n_dev-1 permute+accumulate
    # steps — no wasted final ppermute (same shape as ring_attention.py:72-75)
    total0 = chunk_loss(txt, jnp.eye(b, dtype=bool))
    (_, total), _ = jax.lax.scan(step, (txt, total0),
                                 jnp.arange(n_dev - 1))
    # average over the *global* batch like the dense reference
    total = jax.lax.psum(total, axis_name)
    return total / (b * n_dev)


def _ring_infonce_local(img: jax.Array, txt: jax.Array, scale: jax.Array,
                        *, axis_name) -> jax.Array:
    """Per-device body of the ring InfoNCE (CLIP) loss.

    Same ring topology as ``_ring_sigmoid_local``: local images stay put,
    text chunks ride the ``ppermute`` ring. Softmax needs a *global*
    normalizer in both directions, so two streaming logsumexps run at once:

    - image→text: each device keeps a running (max, sumexp) over every text
      chunk that visits its local image rows.
    - text→image: a running (max, sumexp) *travels with the text chunk* —
      each visited device folds in its local images' logits, so when the
      chunk has gone all the way around, its column normalizer has seen the
      whole global image batch. One extra ``ppermute`` at the end brings the
      finished column stats home.

    The positive logit is the diagonal of the step-0 (own-chunk) block. No
    device ever materializes more than its local b x b logit tile.
    """
    n_dev = jax.lax.axis_size(axis_name)
    b = img.shape[0]
    img = img / jnp.linalg.norm(img, axis=-1, keepdims=True)
    txt = txt / jnp.linalg.norm(txt, axis=-1, keepdims=True)
    s = jnp.exp(scale)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    ring = partial(jax.lax.ppermute, axis_name=axis_name, perm=perm)

    logits0 = s * img @ txt.T
    pos = jnp.diagonal(logits0)
    row_m = jnp.max(logits0, axis=1)
    row_s = jnp.sum(jnp.exp(logits0 - row_m[:, None]), axis=1)
    col_m = jnp.max(logits0, axis=0)
    col_s = jnp.sum(jnp.exp(logits0 - col_m[None, :]), axis=0)

    def fold(m, se, logits, axis):
        """Streaming logsumexp update: fold a new logit block into (m, se)."""
        m_new = jnp.maximum(m, jnp.max(logits, axis=axis))
        expand = (lambda a: a[:, None]) if axis == 1 else (lambda a: a[None, :])
        se = se * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - expand(m_new)), axis=axis)
        return m_new, se

    def step(carry, _):
        txt_c, col_m_c, col_s_c, row_m_a, row_s_a = carry
        txt_c, col_m_c, col_s_c = jax.tree.map(ring, (txt_c, col_m_c, col_s_c))
        logits = s * img @ txt_c.T
        row_m_a, row_s_a = fold(row_m_a, row_s_a, logits, axis=1)
        col_m_c, col_s_c = fold(col_m_c, col_s_c, logits, axis=0)
        return (txt_c, col_m_c, col_s_c, row_m_a, row_s_a), None

    carry = (txt, col_m, col_s, row_m, row_s)
    (_, col_m, col_s, row_m, row_s), _ = jax.lax.scan(
        step, carry, jnp.arange(n_dev - 1))
    # after n_dev-1 hops, chunk d's column stats sit on device d-1 — one
    # final hop (texts themselves no longer needed) brings them home
    col_m, col_s = jax.tree.map(ring, (col_m, col_s))
    row_lse = row_m + jnp.log(row_s)
    col_lse = col_m + jnp.log(col_s)
    li = -jnp.sum(pos - row_lse)  # image→text CE over the global text axis
    lt = -jnp.sum(pos - col_lse)  # text→image CE over the global image axis
    total = jax.lax.psum(li + lt, axis_name)
    return total / (2 * b * n_dev)


def ring_clip_infonce_loss(img: jax.Array, txt: jax.Array,
                           logit_scale: jax.Array, *, mesh: Mesh,
                           axis_name: str | tuple[str, ...] = "data"
                           ) -> jax.Array:
    """Symmetric CLIP InfoNCE over a batch sharded on ``axis_name``, computed
    as a ``ppermute`` ring with streaming (carried-max) logsumexps so no
    device ever holds the global text batch or the full B x B logit matrix —
    the softmax counterpart of ``ring_sigmoid_loss`` (the dense
    ``clip_softmax_loss`` all-gathers the global batch, which stops scaling
    at pod batch sizes). Numerically identical to the dense loss and
    differentiable end-to-end; ``axis_name`` may be a tuple of mesh axes for
    hybrid DCN x ICI meshes."""
    fn = jax.shard_map(
        partial(_ring_infonce_local, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P()),
        out_specs=P(),
        check_vma=False)
    return fn(img, txt, logit_scale)


def ring_sigmoid_loss(img: jax.Array, txt: jax.Array, logit_scale: jax.Array,
                      logit_bias: jax.Array, *, mesh: Mesh,
                      axis_name: str | tuple[str, ...] = "data") -> jax.Array:
    """SigLIP sigmoid loss over a batch sharded on ``axis_name``, computed as
    a ``ppermute`` ring so no device ever holds the global text batch or the
    full logit matrix. Differentiable end-to-end (``ppermute``'s transpose is
    the reverse permute, handled by JAX AD)."""
    fn = jax.shard_map(
        partial(_ring_sigmoid_local, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(), P()),
        out_specs=P(),
        check_vma=False)
    return fn(img, txt, logit_scale, logit_bias)


# ---------------------------------------------------------------------------
# Language-model losses (the looped decoder family, `models/ouro.py`)
# ---------------------------------------------------------------------------

def blocked_cross_entropy(hidden: jax.Array, head_kernel: jax.Array,
                          targets: jax.Array, *, block: int = 1024,
                          vocab_major: bool = False) -> jax.Array:
    """Per-position softmax cross-entropy of ``hidden @ head_kernel`` against
    ``targets``, float32, without ever holding the logits: positions go
    through in blocks of ``block``, and each block's ``(block, vocab)``
    logits are recomputed in the backward (at 4096 positions and a 49152-word
    vocabulary one pass's float32 logits are 0.8 GB, and a looped model has
    one set per pass). ``hidden`` is ``(..., width)`` and ``targets``
    broadcasts against its leading axes. The matmul runs in ``hidden``'s
    dtype and the softmax in float32. ``vocab_major``: ``head_kernel`` is
    ``(vocab, width)``, a tied embedding, contracted on its last axis as it
    lies (no transposed copy)."""
    lead, width = hidden.shape[:-1], hidden.shape[-1]
    h = hidden.reshape(-1, width)
    t = jnp.broadcast_to(targets, lead).reshape(-1)
    n = h.shape[0]
    block = min(block, n)
    pad = -n % block
    if pad:
        h, t = jnp.pad(h, ((0, pad), (0, 0))), jnp.pad(t, (0, pad))
    kernel = head_kernel.astype(h.dtype)

    @jax.checkpoint
    def one_block(args):
        h_blk, t_blk = args
        if vocab_major:
            logits = jax.lax.dot_general(
                h_blk, kernel, (((1,), (1,)), ((), ())),
                preferred_element_type=h_blk.dtype).astype(jnp.float32)
        else:
            logits = (h_blk @ kernel).astype(jnp.float32)
        picked = jnp.take_along_axis(logits, t_blk[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    ce = jax.lax.map(one_block, (h.reshape(-1, block, width),
                                 t.reshape(-1, block)))
    return ce.reshape(-1)[:n].reshape(lead)


def exit_distribution(gate_logits: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(p, log p)`` of the pass at which a position exits, from the exit
    gates' logits ``(R, ...)``: ``lam_r = sigmoid(g_r)``, ``p_r = lam_r *
    prod_{j<r} (1 - lam_j)`` for ``r < R``, and the last pass takes what is
    left, ``p_R = prod_{j<R} (1 - lam_j)``, so the R masses sum to one."""
    g = gate_logits.astype(jnp.float32)
    log_stay = jax.nn.log_sigmoid(-g)                 # log(1 - lam_r)
    stayed = jnp.cumsum(log_stay, axis=0) - log_stay  # sum over j < r
    log_exit = jnp.concatenate(
        [jax.nn.log_sigmoid(g[:-1]), jnp.zeros_like(g[:1])], axis=0)
    log_p = stayed + log_exit
    return jnp.exp(log_p), log_p


def expected_exit_loss(ce: jax.Array, gate_logits: jax.Array, *,
                       beta: float) -> tuple[jax.Array, dict[str, jax.Array]]:
    """The looped model's stage-one objective: the mean over positions of
    ``sum_r p_r * CE_r - beta * H(p)`` with ``p`` the `exit_distribution`.
    ``ce`` and ``gate_logits`` are ``(R, ...)``. Also returns, per pass, the
    mean cross-entropy and the mean exit mass (``(R,)`` each): where a looped
    model's training is watched."""
    p, log_p = exit_distribution(gate_logits)
    expected = jnp.sum(p * ce, axis=0)
    entropy = -jnp.sum(p * log_p, axis=0)
    positions = tuple(range(1, ce.ndim))
    return jnp.mean(expected - beta * entropy), {
        "ce": jnp.mean(ce, axis=positions), "p": jnp.mean(p, axis=positions)}
