"""The one traffic generator: reads a mix file (``bench/traffic/*.json``)
and makes an open-loop arrival schedule, prompts and train batches from
a seed.

Lengths, gaps and tenants are fixed quantiles of their distributions.
Each request's prompt and output length are paired once, by a key made
of the mix's domain and the segment (not the seed), so every seed gets
the same requests; the seed puts them, the gaps and the tenants in
another order.  A seed thus changes which request comes when, never the
work: pairs drawn per seed would move the decode work of a window of 12
chat requests (the sum of prompt x output) by 13% from seed to seed.
The schedule has three segments with quantile sets of their own, so the
measured window always holds the same requests:

  ramp    ``ramp_s`` seconds before the window (brings the batcher to
          its steady state; not counted)
  window  ``round(rate * seconds)`` requests due in [0, seconds)
  drain   arrivals keep coming after the window while the window's
          requests finish, so their tails see the same load

Gaps are gamma with the mix's coefficient of variation (the gap and
length arithmetic of ``repro.data.traces``, without its diurnal cycle
and surges, which mean nothing in a window of seconds).  Prompt tokens
follow a seeded Markov chain like ``repro.data.synthetic``, sampled a
position at a time over all rows at once.
"""
from __future__ import annotations

import dataclasses
import statistics
import zlib
from typing import Any, Dict, List

import numpy as np
from scipy.special import gammaincinv

SEGMENTS = ("ramp", "window", "drain")


@dataclasses.dataclass
class Arrival:
    rid: int
    due: float            # seconds from window open (ramp is negative)
    prompt: np.ndarray    # int32 token ids
    max_new: int
    tenant: int
    segment: str


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths at fixed quantiles of the spec's distribution,
    sorted, rounded and clipped to [min, max]."""
    q = _quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["kind"] == "uniform":
        vals = lo + q * (hi - lo + 1)
        vals = np.floor(vals)
    elif spec["kind"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(float(x)) for x in q])
        vals = np.round(float(spec["median"]) * np.exp(float(spec["sigma"])
                                                        * z))
    else:
        raise ValueError(f"unknown length distribution {spec['kind']!r}")
    return np.clip(vals, lo, hi).astype(np.int64)


def gap_quantiles(rate: float, cv: float, n: int) -> np.ndarray:
    """``n`` gamma gaps (mean ``1/rate``, coefficient of variation
    ``cv``) at fixed quantiles; shape k = 1/cv^2."""
    k = 1.0 / (cv * cv)
    return gammaincinv(k, _quantiles(n)) / (k * rate)


def tenant_counts(n_tenants: int, zipf: float, n: int) -> np.ndarray:
    """How many of ``n`` requests each tenant sends under Zipf(zipf),
    by largest remainder so the counts sum to ``n``."""
    p = 1.0 / np.arange(1, n_tenants + 1) ** zipf
    p /= p.sum()
    raw = p * n
    counts = np.floor(raw).astype(np.int64)
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:n - int(counts.sum())]] += 1
    return counts


def segment_size(traffic: Dict[str, Any], segment: str,
                 seconds: float) -> int:
    span = {"ramp": traffic["ramp_s"], "window": seconds,
            "drain": traffic["drain_s"]}[segment]
    return int(round(float(traffic["rate_rps"]) * float(span)))


def markov_tables(vocab: int, domain: str, branching: int = 7):
    """Next-token candidates and their cumulative probabilities, from a
    fixed per-domain seed (the corpus shape, not the run's seed)."""
    rng = np.random.default_rng(zlib.crc32(f"bench:{domain}".encode()))
    nxt = rng.integers(0, vocab, size=(vocab, branching)).astype(np.int32)
    probs = rng.dirichlet(np.ones(branching) * 0.6, size=vocab)
    return nxt, np.cumsum(probs, axis=1)


def markov_rows(tables, lengths: np.ndarray, rng: np.random.Generator,
                vocab: int) -> List[np.ndarray]:
    """One Markov token row per length, sampled over all rows at once."""
    nxt, cum = tables
    n, width = len(lengths), int(lengths.max())
    out = np.zeros((n, width), np.int32)
    out[:, 0] = rng.integers(0, vocab, size=n)
    for t in range(1, width):
        cur = out[:, t - 1]
        u = rng.random(n)
        pick = (u[:, None] > cum[cur]).sum(axis=1)
        pick = np.minimum(pick, nxt.shape[1] - 1)
        out[:, t] = nxt[cur, pick]
    return [out[i, :int(lengths[i])].copy() for i in range(n)]


def request_sizes(traffic: Dict[str, Any], segment: str, n: int):
    """The segment's ``n`` (prompt, output) length pairs, the same for
    every seed: prompts in quantile order, outputs in an order keyed by
    the mix's domain and the segment."""
    key = f"bench:pairs:{traffic.get('domain', 'chat')}:{segment}:{n}"
    pair_rng = np.random.default_rng(zlib.crc32(key.encode()))
    return (length_quantiles(traffic["prompt"], n),
            pair_rng.permutation(length_quantiles(traffic["output"], n)))


def generate(traffic: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[Arrival]:
    """The run's whole arrival schedule, in due order."""
    rng = np.random.default_rng(seed)
    rate, cv = float(traffic["rate_rps"]), float(traffic["arrival_cv"])
    starts = {"ramp": -float(traffic["ramp_s"]), "window": 0.0,
              "drain": float(seconds)}
    spans = {"ramp": float(traffic["ramp_s"]), "window": float(seconds),
             "drain": float(traffic["drain_s"])}
    tables = markov_tables(vocab, traffic.get("domain", "chat"))
    out: List[Arrival] = []
    for seg in SEGMENTS:
        n = segment_size(traffic, seg, seconds)
        if n == 0:
            continue
        gaps = rng.permutation(gap_quantiles(rate, cv, n))
        # scale so the segment's n arrivals span exactly its length:
        # the first is due at its start, none at its end
        due = starts[seg] + spans[seg] * (np.cumsum(gaps) - gaps) \
            / gaps.sum()
        plen, olen = request_sizes(traffic, seg, n)
        order = rng.permutation(n)
        plen, olen = plen[order], olen[order]
        tenants = rng.permutation(np.repeat(
            np.arange(int(traffic["tenants"])),
            tenant_counts(int(traffic["tenants"]),
                          float(traffic["tenant_zipf"]), n)))
        prompts = markov_rows(tables, plen, rng, vocab)
        for i in range(n):
            out.append(Arrival(rid=len(out), due=float(due[i]),
                               prompt=prompts[i], max_new=int(olen[i]),
                               tenant=int(tenants[i]), segment=seg))
    return out


def train_pool(traffic: Dict[str, Any], seed: int, vocab: int,
               n_batches: int) -> Dict[str, np.ndarray]:
    """``n_batches`` distinct train batches of the mix's shape, stacked:
    tokens/labels [N, rows, seq] int32 and mask [N, rows, seq] f32."""
    spec = traffic["train"]
    rows, seq = int(spec["rows"]), int(spec["seq"])
    rng = np.random.default_rng([seed, 1])
    tables = markov_tables(vocab, spec.get("domain", "train"))
    toks = markov_rows(tables, np.full(n_batches * rows, seq + 1), rng,
                       vocab)
    toks = np.stack(toks).reshape(n_batches, rows, seq + 1)
    return {"tokens": toks[:, :, :-1].copy(),
            "labels": toks[:, :, 1:].copy(),
            "mask": np.ones((n_batches, rows, seq), np.float32)}


def max_sizes(traffic: Dict[str, Any]) -> Dict[str, int]:
    """Largest prompt and output any seed can draw (the quantile sets
    are the same for every seed), and the smallest prompt."""
    return {"prompt_max": int(traffic["prompt"]["max"]),
            "prompt_min": int(traffic["prompt"]["min"]),
            "output_max": int(traffic["output"]["max"])}
