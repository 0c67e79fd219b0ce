"""Deterministic synthetic data pipeline with per-host sharding + prefetch.

Production shape: each host generates only its shard of the global batch
(``host_batch = global_batch / n_hosts``), deterministically from
``(seed, step, host_id)`` so restarts and elastic resizes reproduce the
same global stream regardless of host count.  A background thread
prefetches ``prefetch`` steps ahead, overlapping host-side generation
with device compute.
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass
from typing import Dict, Iterator, Optional

import numpy as np

from ..models.config import ArchConfig, ShapeConfig


@dataclass
class DataConfig:
    seed: int = 1234
    prefetch: int = 2


# conditioning lengths are uniform on [COND_MIN_LEN, shape.cond_len] (text
# prompts of 8 T5 tokens and more); the rest of each row is padding
COND_MIN_LEN = 8


def delay_pattern(raw: np.ndarray, special: int) -> np.ndarray:
    """MusicGen's delay pattern: ``raw`` [..., K, n] codes; codebook k
    is shifted right by k frames, its first k positions hold
    ``special``, and the result keeps n positions."""
    out = np.full(raw.shape, special, raw.dtype)
    n = raw.shape[-1]
    for k in range(raw.shape[-2]):
        out[..., k, k:] = raw[..., k, :n - k]
    return out


class SyntheticTokenPipeline:
    """Deterministic LM token batches; codebook batches in the delay
    pattern with conditioning states; stub embeddings for the vision
    frontend."""

    def __init__(self, cfg: ArchConfig, shape: ShapeConfig,
                 data_cfg: Optional[DataConfig] = None,
                 host_id: int = 0, n_hosts: int = 1):
        assert shape.global_batch % n_hosts == 0
        self.cfg = cfg
        self.shape = shape
        self.dc = data_cfg or DataConfig()
        self.host_id = host_id
        self.n_hosts = n_hosts
        self.host_batch = shape.global_batch // n_hosts

    # ---------------------------------------------------------------- #
    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        """The batch for ``step`` (pure function of (seed, step, host))."""
        rng = np.random.default_rng(
            (self.dc.seed * 1_000_003 + step) * 4096 + self.host_id)
        b, s = self.host_batch, self.shape.seq_len
        out: Dict[str, np.ndarray] = {}
        if self.cfg.is_codebook:
            out.update(self._codebook_batch(rng, b, s))
        elif self.cfg.frontend == "token":
            toks = rng.integers(0, self.cfg.vocab, size=(b, s + 1),
                                dtype=np.int32)
            out["tokens"] = toks[:, :-1]
            out["labels"] = toks[:, 1:]
        else:
            out["embeds"] = rng.standard_normal(
                (b, s, self.cfg.d_model)).astype(np.float32)
            out["labels"] = rng.integers(0, self.cfg.vocab, size=(b, s),
                                         dtype=np.int32)
        return out

    def _codebook_batch(self, rng: np.random.Generator, b: int,
                        s: int) -> Dict[str, np.ndarray]:
        """Codes [b, K, s] in the delay pattern and their targets (the
        next position's codes; ``vocab`` marks the pattern's filler,
        left out of the loss); with cross-attention, conditioning states
        [b, t, cond_dim] of lengths uniform on [COND_MIN_LEN, t], zero
        past each row's length, and their mask."""
        cfg = self.cfg
        raw = rng.integers(0, cfg.vocab, size=(b, cfg.n_codebooks, s + 1),
                           dtype=np.int32)
        seq = delay_pattern(raw, cfg.vocab)
        out = {"codes": seq[..., :-1], "labels": seq[..., 1:]}
        if cfg.cond_dim:
            t = self.shape.cond_len
            lengths = rng.integers(COND_MIN_LEN, t + 1, size=b)
            mask = np.arange(t)[None, :] < lengths[:, None]
            cond = rng.standard_normal((b, t, cfg.cond_dim),
                                       dtype=np.float32)
            out["cond"] = cond * mask[..., None]
            out["cond_mask"] = mask
        return out

    # ---------------------------------------------------------------- #
    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        return self.iterate(0)

    def iterate(self, start_step: int) -> Iterator[Dict[str, np.ndarray]]:
        """Prefetching iterator starting at ``start_step`` (checkpoint
        restore passes the restored step so the stream is seamless)."""
        q: "queue.Queue" = queue.Queue(maxsize=self.dc.prefetch)
        stop = threading.Event()

        def producer() -> None:
            step = start_step
            while not stop.is_set():
                try:
                    q.put(self.batch_at(step), timeout=0.1)
                    step += 1
                except queue.Full:
                    continue

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                yield q.get()
        finally:
            stop.set()
