"""Synthetic instruction-tuning data (stand-in for Table 1's datasets,
which aren't shipped offline).

Generates deterministic token sequences with learnable structure: each
"domain" (code / conversation / manim / ...) has a distinct Markov
transition matrix over the vocabulary, so LoRA fine-tuning on a domain
measurably reduces CE loss on that domain — which is what the paper's
quality metric (1/CE) needs to show continuous adaptation.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

DOMAINS = ("manim", "code_alpaca", "code_instruct",     # code generation
           "alpaca", "gpteacher", "open_instruct", "instruct3m")  # conv


@dataclasses.dataclass
class SyntheticDataset:
    domain: str
    vocab_size: int = 512
    seq_len: int = 64
    seed: int = 0
    branching: int = 7   # candidate next-tokens per token (lower=easier)

    def __post_init__(self):
        # crc32, not hash(): str hashes are salted per process, and the
        # same seed must give the same corpus in every run
        rng = np.random.default_rng(
            zlib.crc32(f"{self.domain}:{self.seed}".encode()))
        v, k = self.vocab_size, self.branching
        self.next_tokens = rng.integers(0, v, size=(v, k))
        self.next_probs = rng.dirichlet(np.ones(k) * 0.6, size=v)
        self._rng = np.random.default_rng(self.seed + 17)

    def sample_tokens(self, batch: int, rng: Optional[np.random.Generator]
                      = None) -> np.ndarray:
        rng = rng or self._rng
        out = np.zeros((batch, self.seq_len + 1), dtype=np.int32)
        out[:, 0] = rng.integers(0, self.vocab_size, size=batch)
        for t in range(self.seq_len):
            cur = out[:, t]
            choice = np.array([
                rng.choice(self.next_tokens[c], p=self.next_probs[c])
                for c in cur])
            out[:, t + 1] = choice
        return out

    def batch(self, batch_size: int,
              rng: Optional[np.random.Generator] = None) -> Dict:
        """Training batch: tokens, next-token labels, mask."""
        toks = self.sample_tokens(batch_size, rng)
        return {
            "tokens": toks[:, :-1],
            "labels": toks[:, 1:].astype(np.int32),
            "mask": np.ones((batch_size, self.seq_len), np.float32),
        }


def replica_datasets(n_replicas: int, vocab_size: int = 512,
                     seq_len: int = 64, seed: int = 0
                     ) -> Dict[str, SyntheticDataset]:
    """§8.1: each replica preloaded with a distinct dataset (simulated
    heterogeneous tenant data distribution)."""
    out = {}
    for i in range(n_replicas):
        domain = DOMAINS[i % len(DOMAINS)]
        out[f"r{i:02d}"] = SyntheticDataset(
            domain, vocab_size=vocab_size, seq_len=seq_len,
            seed=seed * 100 + i)
    return out
