"""The batch of block-diffusion training (BD3-LM, arXiv:2503.09573; the
objective the SDAR models are trained by, arXiv:2510.06303): beside a loader's
``inputs`` (the clean row ``x_0``), ``targets`` and ``mask``, a noised copy of
the row and the weight of every position's loss.

A row of ``L`` tokens is cut into blocks of ``block_length``. A block draws a
rate ``t = eps + (1 - eps) u``, ``u ~ U[0, 1)`` (the linear schedule, a rate a
block); each of its tokens is replaced by ``mask_id`` with probability ``t``;
the loss of a replaced position ``i`` weighs ``1 / t_block(i)`` (the linear
schedule's bound), of any other 0. A position predicts the token *at* it, so
``targets`` becomes ``x_0``, unshifted.

The draw is made on the host from ``(seed, stream, batch index, process)``
alone: a resumed job draws for batch ``n`` what the first run drew, whatever
was drawn before, and validation (its own stream, index = the batch's place in
the pass) reads the same noise at every evaluation.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator

import numpy as np

TRAIN_STREAM, VALIDATION_STREAM = 0, 1


def noise_batch(batch: Dict[str, np.ndarray], seed: int, index: int, block_length: int,
                eps: float, mask_id: int, stream: int = TRAIN_STREAM,
                process_index: int = 0) -> Dict[str, np.ndarray]:
    """``batch`` with ``targets = inputs``, ``noised_inputs [B, L]`` and
    ``loss_weights [B, L]`` float32 (``mask_i m_i / t_block(i)``)."""
    x0 = np.asarray(batch["inputs"])
    B, L = x0.shape
    if L % block_length:
        raise ValueError(f"block length {block_length} does not divide a row of {L}")
    rng = np.random.default_rng([int(seed), int(stream), int(index), int(process_index)])
    rate = eps + (1.0 - eps) * rng.random((B, L // block_length))      # t of every block
    t = np.repeat(rate, block_length, axis=1)
    replaced = rng.random((B, L)) < t
    weights = np.asarray(batch["mask"], np.float32) * replaced / t
    return {**batch, "targets": x0,
            "noised_inputs": np.where(replaced, np.asarray(mask_id, x0.dtype), x0),
            "loss_weights": weights.astype(np.float32)}


class BlockDiffusionBatches:
    """A loader of the trainer's surface (``generate_batch(step)``,
    ``iter_validation``, the state of the loader it wraps) whose batches carry
    the noise of :func:`noise_batch`. Everything else is the wrapped loader's."""

    def __init__(self, loader: Any, seed: int, block_length: int, eps: float, mask_id: int,
                 process_index: int = 0):
        self.loader = loader
        self._draw = dict(seed=int(seed), block_length=int(block_length), eps=float(eps),
                          mask_id=int(mask_id), process_index=int(process_index))

    def __getattr__(self, name: str):  # state, epochs, validation flags, stop()
        return getattr(self.loader, name)

    def generate_batch(self, step: int) -> Dict[str, np.ndarray]:
        return noise_batch(self.loader.generate_batch(step), index=step, **self._draw)

    def iter_validation(self, cap: int = 50) -> Iterator[Dict[str, np.ndarray]]:
        for i, batch in enumerate(self.loader.iter_validation(cap)):
            yield noise_batch(batch, index=i, stream=VALIDATION_STREAM, **self._draw)
