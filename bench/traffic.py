"""The one generator every traffic file feeds.

A traffic file (``bench/traffic/<name>.json``) holds parameters only: the
kind of loop that drives it, the batch and sequence length, and the mix of
token streams.  The streams are those of the repository's synthetic LM
pipeline (``data/synthetic.py``), copied here so that the yardstick does not
move with the program:

* ``ramp``:   an arithmetic ramp from a random start, stride from a fixed set;
* ``markov``: an affine Markov chain with the dataset's fixed multiplier and
  noise in {0, 1, 2};
* ``motif``:  one of eight short motifs repeated from a random phase.

Batch ``step`` is a pure function of (seed, step): the same seed gives the
same inputs, in any process.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

KINDS = ("ramp", "markov", "motif")


def seed_words(seed: int) -> tuple:
    """A seed of any size as two unsigned 32-bit words (low, high)."""
    s = int(seed) % (1 << 64)
    return s & 0xFFFFFFFF, s >> 32


@dataclass(frozen=True)
class TokenFeed:
    batch_size: int
    seq_len: int
    vocab_size: int
    mix: tuple          # weights of KINDS, in that order
    seed: int

    @classmethod
    def from_traffic(cls, traffic: dict, vocab_size: int, seed: int):
        mix = traffic["mix"]
        unknown = set(mix) - set(KINDS)
        if unknown:
            raise ValueError(f"unknown token streams {sorted(unknown)}")
        return cls(traffic["batch"], traffic["seq"], vocab_size,
                   tuple(float(mix.get(k, 0.0)) for k in KINDS), seed)

    def _rules(self) -> dict:
        r = np.random.default_rng(np.random.SeedSequence(
            [*seed_words(self.seed), 9999]))
        return {"strides": r.integers(1, 7, size=4),
                "mult": int(r.integers(2, 6)),
                "motifs": [r.integers(0, self.vocab_size, size=p)
                           for p in r.integers(3, 9, size=8)]}

    def batch(self, step: int) -> np.ndarray:
        """Tokens of batch ``step``: int32 (batch_size, seq_len)."""
        rules = self._rules()
        rng = np.random.default_rng(np.random.SeedSequence(
            [*seed_words(self.seed), int(step)]))
        B, S, V = self.batch_size, self.seq_len, self.vocab_size
        p = np.asarray(self.mix) / sum(self.mix)
        kinds = rng.choice(len(KINDS), size=B, p=p)
        toks = np.empty((B, S), np.int32)
        for b, kind in enumerate(KINDS[k] for k in kinds):
            if kind == "ramp":
                start = int(rng.integers(0, V))
                stride = int(rules["strides"][rng.integers(0, 4)])
                toks[b] = (start + stride * np.arange(S)) % V
            elif kind == "motif":
                motif = rules["motifs"][rng.integers(0, len(rules["motifs"]))]
                reps = -(-S // len(motif)) + 1
                phase = int(rng.integers(0, len(motif)))
                toks[b] = np.tile(motif, reps)[phase:phase + S]
            else:
                noise = rng.integers(0, 3, size=S).tolist()
                x = int(rng.integers(0, V))
                row = [x]
                mult = rules["mult"]
                for t in range(1, S):
                    x = (mult * x + noise[t]) % V
                    row.append(x)
                toks[b] = row
        return toks
