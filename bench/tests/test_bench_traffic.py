"""The traffic generator: deterministic per seed, the same work for
every seed, and mixes found by name."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench_testlib import bench_copy, benchmark_with  # noqa: F401
from harness import spec
from harness import traffic as gen

SECONDS = 10.0


def _chat():
    return json.loads((spec.BENCH_DIR / "traffic" / "chat.json").read_text())


def _window(arrivals):
    return [a for a in arrivals if a.segment == "window"]


@pytest.mark.parametrize("mix", ["chat", "chat-cotrain", "longprompt"])
def test_same_seed_same_schedule(mix):
    m = json.loads((spec.BENCH_DIR / "traffic" / f"{mix}.json").read_text())
    a = gen.generate(m, 2**31 + 77, SECONDS, vocab=1000)
    b = gen.generate(m, 2**31 + 77, SECONDS, vocab=1000)
    assert [(x.due, x.max_new, x.tenant) for x in a] == \
        [(x.due, x.max_new, x.tenant) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seed_changes_order_not_totals():
    m = _chat()
    runs = [_window(gen.generate(m, s, SECONDS, vocab=1000))
            for s in (1, 2, 3_000_000_000)]
    n = int(round(m["rate_rps"] * SECONDS))
    for w in runs:
        assert len(w) == n
        assert all(0.0 <= a.due < SECONDS for a in w)
    totals = {(sum(len(a.prompt) for a in w), sum(a.max_new for a in w),
               tuple(sorted(np.bincount([a.tenant for a in w],
                                        minlength=m["tenants"]))))
              for w in runs}
    assert len(totals) == 1, totals
    orders = {tuple(a.max_new for a in w) for w in runs}
    assert len(orders) == 3


@pytest.mark.parametrize("mix", ["chat", "chat-cotrain", "longprompt"])
def test_every_seed_gets_the_same_requests(mix):
    """Each segment holds the same (prompt, output) length pairs for
    every seed, in another order: a seed moves no work."""
    m = json.loads((spec.BENCH_DIR / "traffic" / f"{mix}.json").read_text())
    runs = [gen.generate(m, s, 30.0, vocab=1000)
            for s in (1, 2, 3_000_000_000)]
    pairs = {tuple(sorted((a.segment, len(a.prompt), a.max_new)
                          for a in run)) for run in runs}
    assert len(pairs) == 1
    orders = {tuple((len(a.prompt), a.max_new) for a in run)
              for run in runs}
    assert len(orders) == 3


def test_lengths_stay_inside_the_mix():
    m = _chat()
    w = _window(gen.generate(m, 5, SECONDS, vocab=1000))
    assert min(len(a.prompt) for a in w) >= m["prompt"]["min"]
    assert max(len(a.prompt) for a in w) <= m["prompt"]["max"]
    assert min(a.max_new for a in w) >= m["output"]["min"]
    assert max(a.max_new for a in w) <= m["output"]["max"]
    assert all(0 <= int(t) < 1000 for a in w for t in a.prompt)


def test_gaps_have_the_mix_mean_and_cv():
    g = gen.gap_quantiles(rate=4.0, cv=1.0, n=20000)
    assert g.mean() == pytest.approx(0.25, rel=1e-3)
    assert g.std() / g.mean() == pytest.approx(1.0, rel=2e-2)
    g2 = gen.gap_quantiles(rate=4.0, cv=2.0, n=20000)
    assert g2.std() / g2.mean() == pytest.approx(2.0, rel=0.1)


def test_zipf_counts_sum_and_skew():
    c = gen.tenant_counts(8, 1.1, 100)
    assert c.sum() == 100
    assert list(c) == sorted(c, reverse=True)
    assert c[0] > 3 * c[-1]


def test_train_pool_rows_differ():
    m = json.loads((spec.BENCH_DIR / "traffic"
                    / "chat-cotrain.json").read_text())
    pool = gen.train_pool(m, 9, vocab=5000, n_batches=3)
    toks = pool["tokens"].reshape(-1, pool["tokens"].shape[-1])
    assert len({t.tobytes() for t in toks}) == len(toks)
    assert np.array_equal(pool["labels"][:, :, :-1], pool["tokens"][:, :, 1:])


def test_a_new_mix_file_is_picked_up(bench_copy):
    """A mix is a data file: adding one under traffic/ and a cell that
    names it needs no edit to any existing file."""
    new = dict(_chat(), rate_rps=1.5, why="bursty chat")
    new["arrival_cv"] = 2.0
    (bench_copy / "traffic" / "bursty.json").write_text(json.dumps(new))
    bm = benchmark_with("tiny-chat")
    bm["workloads"].append({"name": "tiny.bursty", "config": "tiny",
                            "traffic": "bursty", "chips": 1, "why": "t"})
    cell = spec.load_cell("tiny.bursty", bm)
    assert cell.traffic["arrival_cv"] == 2.0
    assert cell.config["hidden_size"] == 64
    w = _window(gen.generate(cell.traffic, 1, SECONDS, vocab=256))
    assert len(w) == 15


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        spec.peaks_for("some chip")
    assert spec.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
