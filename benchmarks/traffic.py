"""The benchmark's own training inputs: a small pool of batches made from
``--seed`` in set-up and fed to the CLI's loop in place of the program's
procedural generators.

Why not the program's generators: ``data/synthetic.py`` draws every batch
with float64 numpy on one host thread, in series with the step on one chip.
On the chip machine that took 1.1 to 2.1 s a batch of 128 at 256 px against
a 0.27 s step (my chip runs, PR 22), so a cell fed by it measures numpy on a
shared host, and its runs differ by tens of percent. The loop under test stays
the CLI's own; what the benchmark replaces is the traffic, which is its to
make.

A pool is ``POOL`` distinct batches of float32 numpy on the host, cycled:
every step pays the CLI's own placement (the host-to-device copy) and nothing
for generation. So the program's Data layer does not run in these cells, and
``data_wait_ms`` there is placement only; the cell that measures the input
pipeline itself (``--data``, or the generators once they are cheap) is the
first open item of PERF.md section 7.
"""

from __future__ import annotations

import contextlib
import itertools

import numpy as np

#: distinct batches in a pool
POOL = 4


def make_pool(kind: str, batch_size: int, *, image_size: int, seed: int,
              vocab_size: int = 0, seq_len: int = 0, num_classes: int = 0,
              channels: int = 3) -> list[tuple]:
    """``POOL`` batches of (images f32 [B,S,S,C] standard normal, second)
    where second is int32 tokens [B,L] (``contrastive``) or int32 labels [B]
    (``classification``)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(POOL):
        images = rng.standard_normal(
            (batch_size, image_size, image_size, channels), dtype=np.float32)
        if kind == "contrastive":
            second = rng.integers(0, vocab_size, (batch_size, seq_len),
                                  dtype=np.int32)
        else:
            second = rng.integers(0, num_classes, (batch_size,),
                                  dtype=np.int32)
        out.append((images, second))
    return out


@contextlib.contextmanager
def feed_cli(seed: int):
    """While active, ``jimm_tpu.data.contrastive_pairs`` and
    ``blob_classification`` (what ``cli train`` draws from when it is given
    no ``--data``) yield the benchmark's pool. Yields a dict that counts the
    batches drawn, so a run that was not fed by the pool can be refused."""
    import jimm_tpu.data as data
    drawn = {"batches": 0}

    def cycle(batches):
        for batch in itertools.cycle(batches):
            drawn["batches"] += 1
            yield batch

    def contrastive_pairs(batch_size, *, image_size, vocab_size, seq_len,
                          channels=3, **_):
        return cycle(make_pool("contrastive", batch_size, seed=seed,
                               image_size=image_size, vocab_size=vocab_size,
                               seq_len=seq_len, channels=channels))

    def blob_classification(batch_size, *, image_size, num_classes,
                            channels=3, **_):
        return cycle(make_pool("classification", batch_size, seed=seed,
                               image_size=image_size, num_classes=num_classes,
                               channels=channels))

    saved = (data.contrastive_pairs, data.blob_classification)
    data.contrastive_pairs = contrastive_pairs
    data.blob_classification = blob_classification
    try:
        yield drawn
    finally:
        data.contrastive_pairs, data.blob_classification = saved
