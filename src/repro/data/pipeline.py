"""Deterministic, shardable, checkpointable synthetic-token data pipeline.

Real deployments stream tokenized shards; for a self-contained repo the
stream is a counter-based PRNG (threefry via jax on CPU is slow at scale, so
we use a splitmix64-style integer hash in numpy): batch ``i`` is a pure
function of (seed, i), so any host can materialise any step independently —
which is what makes restart/elastic-reshard trivial: the checkpoint stores
only ``step``.

Token ids follow a Zipf-like law, as tokenized text does: each draw picks
an octave ``k`` uniformly below the vocabulary's bit length, then an id
uniformly in ``[2^k − 1, 2^(k+1) − 1)`` (ids past the vocabulary fold
back), so id ``t`` has probability about ``1 / (t · bits)``.  A uniform
stream would hold nothing a model can learn — its loss cannot fall below
``ln vocab`` — while this one has a unigram law to fit, in exact integer
arithmetic on every host.
"""

from __future__ import annotations

import dataclasses

import numpy as np

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = (x + _GOLDEN).astype(np.uint64)
    z = x
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


@dataclasses.dataclass
class DataConfig:
    vocab: int
    batch: int          # global batch
    seq: int
    seed: int = 0


class TokenPipeline:
    """Iterator with explicit state=(step,) and host-shard slicing."""

    def __init__(self, cfg: DataConfig, shard: int = 0, n_shards: int = 1,
                 step: int = 0):
        assert cfg.batch % n_shards == 0
        self.cfg = cfg
        self.shard = shard
        self.n_shards = n_shards
        self.step = step

    def batch_at(self, step: int) -> np.ndarray:
        """Global batch for ``step`` (any host can compute any shard)."""
        c = self.cfg
        base = (np.uint64(c.seed) << np.uint64(32)) + np.uint64(step)
        idx = np.arange(c.batch * c.seq, dtype=np.uint64) \
            + base * np.uint64(c.batch * c.seq)
        h = _splitmix64(idx)
        octave = (h >> np.uint64(32)) % np.uint64(int(c.vocab).bit_length())
        width = np.uint64(1) << octave
        toks = (width - np.uint64(1) + (h & np.uint64(0xFFFFFFFF)) % width) \
            % np.uint64(c.vocab)
        return toks.astype(np.int32).reshape(c.batch, c.seq)

    def shard_at(self, step: int) -> np.ndarray:
        b = self.cfg.batch // self.n_shards
        return self.batch_at(step)[self.shard * b:(self.shard + 1) * b]

    def __next__(self) -> np.ndarray:
        out = self.shard_at(self.step)
        self.step += 1
        return out

    # -- checkpointable state ------------------------------------------------
    def state(self) -> dict:
        return {"step": self.step, "seed": self.cfg.seed}

    @classmethod
    def restore(cls, cfg: DataConfig, state: dict, shard=0, n_shards=1):
        assert state["seed"] == cfg.seed, "data stream seed changed"
        return cls(cfg, shard, n_shards, step=state["step"])
