"""Percentiles, rates and failures; the dense family's operation and
byte counts; the roofline and ``mfu`` arithmetic, against values worked
by hand."""
from __future__ import annotations

import json

import pytest

import bench_testlib  # noqa: F401  (puts bench/ on the path)
from harness import counts, readers, spec, stats
from harness import traffic as gen

TINY = json.loads((spec.BENCH_DIR / "tests" / "data" / "configs"
                   / "tiny.json").read_text())
DENSE = spec.family(TINY)
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_percentile_is_linear():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([0, 10], 95) == pytest.approx(9.5)
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_unfinished_requests_count_as_misses():
    rows = [{"due": 0.0, "first": 0.1, "last": 1.1, "n": 11},
            {"due": 1.0, "first": 1.2, "last": 2.2, "n": 11},
            {"due": 2.0, "first": None, "last": None, "n": 0},
            {"due": 3.0, "first": 3.5, "last": None, "n": 3}]
    lat = stats.latencies(rows, drain_end=10.0)
    assert lat["ttft"] == pytest.approx([0.1, 0.2, 8.0, 0.5])
    assert lat["tpot"] == pytest.approx([0.1, 0.1, 8.0 / 1, 6.5 / 2])
    # the censored waits land in the tail
    assert stats.percentile(lat["ttft"], 95) > 5.0
    assert stats.attainment(rows, 10.0, ttft_limit=0.3,
                            tpot_limit=0.2) == 0.5
    assert stats.attainment(rows, 10.0, ttft_limit=0.15,
                            tpot_limit=0.2) == 0.25


def test_rate_is_over_the_window():
    assert stats.rate(300, 30.0) == 10.0


def test_paged_attention_counts_by_hand():
    # tiny: L=2, H=4, Hkv=2, hd=16; rows at contexts 3 and 5 (8 keys)
    need = DENSE.paged_attention(TINY, [3, 5])
    assert need["ops"] == 2 * 4 * 4 * 16 * 8              # 4096
    # K and V of 8 keys x 2 heads x 16 x 2 B, plus q and out of 2 rows
    assert need["bytes"] == 2 * (2 * 8 * 2 * 16 * 2 + 2 * 2 * 4 * 16 * 2)


def test_roofline_share_names_its_bound():
    r = counts.roofline_share(ops=100.0, nbytes=50.0, seconds=10.0,
                              peaks=PEAKS)
    # 1 s for the operations, 5 s for the bytes: memory bound, 50%
    assert r == {"share_pct": 50.0, "bound": "memory"}
    r = counts.roofline_share(ops=1000.0, nbytes=10.0, seconds=20.0,
                              peaks=PEAKS)
    assert r == {"share_pct": 50.0, "bound": "compute"}


def test_model_ops_by_hand():
    # tiny: d=64, f=128, H=4, Hkv=2, hd=16, r=4, V=256, L=2
    proj = 2 * 64 * (2 * 4 * 16 + 2 * 2 * 16)       # q, o, k, v
    mlp = 2 * 3 * 64 * 128
    lora = 2 * 4 * ((64 + 64) + (64 + 32) * 2 + (64 + 64))
    assert DENSE.linear_per_token(TINY) == 2 * (proj + mlp + lora)
    assert DENSE.head_per_token(TINY) == 2 * 64 * 256
    lin, head = DENSE.linear_per_token(TINY), DENSE.head_per_token(TINY)
    # a 3-token prompt: 3 tokens of layers, attention over 1+2+3 keys,
    # the head once
    assert DENSE.prefill_ops(TINY, [3]) == 3 * lin + head \
        + 2 * 4 * 4 * 16 * 6
    assert DENSE.decode_ops(TINY, [5]) == lin + head + 2 * 4 * 4 * 16 * 5
    fwd = 2 * 4 * (lin + head) + 2 * DENSE.attention_ops(TINY, 10)
    assert DENSE.train_ops(TINY, 2, 4) == 2 * fwd + 8 * 2 * lora


class _Rec:
    def __init__(self):
        self.arrival = gen.Arrival(0, 0.0, None, 1, 0, "window")
        self.admit_tick = 0.5


def test_mfu_and_idle_from_a_traced_window():
    # two traced ticks over 2 ms, device busy 0.5 ms of it
    trace = {"device": [[1_000_000, 250_000, "a", ""],
                        [2_000_000, 250_000, "b", ""]],
             "modules": [],
             "host": [[1_000_000, 1_000_000, "bench.tick", 0],
                      [2_000_000, 1_000_000, "bench.tick", 1]]}
    ticks = [(0.0, 0.001, False, 0, [5], [3], 2, 0),
             (0.001, 0.002, False, 0, [6], [], 1, 1)]
    res = {"recs": [_Rec()], "ticks": ticks, "trace": trace}
    ctx = readers.Context(res, TINY, {}, PEAKS)
    assert ctx.window_s == pytest.approx(0.002)
    assert ctx.idle_pct() == pytest.approx(75.0)
    ops = DENSE.prefill_ops(TINY, [3]) + DENSE.decode_ops(TINY, [5, 6])
    assert ctx.mfu_pct() == pytest.approx(100.0 * ops / (0.002 * 100.0))
    assert readers.load_reader("queue_wait_ms")(ctx) == pytest.approx(500.0)


def test_cotrain_output_rate_is_the_window_rate():
    read = readers.load_reader("output_tok_s.cotrain")
    res = {"recs": [], "ticks": [], "trace": None,
           "metrics": {"output_tok_s": 57.5}}
    assert read(readers.Context(res, TINY, {}, PEAKS)) == 57.5
    res = {"recs": [], "ticks": [], "trace": None}
    assert read(readers.Context(res, TINY, {}, PEAKS)) is None


@pytest.mark.parametrize("name,base", [
    ("ttft_p95_ms.longprompt", "ttft_p95_ms"),
    ("tpot_p95_ms.longprompt", "tpot_p95_ms"),
])
def test_longprompt_tails_are_the_window_tails(name, base):
    read = readers.load_reader(name)
    res = {"recs": [], "ticks": [], "trace": None,
           "metrics": {"ttft_p95_ms": 71.5, "tpot_p95_ms": 17.25}}
    assert read(readers.Context(res, TINY, {}, PEAKS)) \
        == res["metrics"][base]
    res = {"recs": [], "ticks": [], "trace": None}
    assert read(readers.Context(res, TINY, {}, PEAKS)) is None


class _Stamped(_Rec):
    def __init__(self, sub, adm):
        super().__init__()
        self.req = type("Req", (), {"submitted_wall": sub,
                                    "admitted_wall": adm})()


@pytest.mark.parametrize("name", ["queue_wait_ms", "admit_wait_ms"])
def test_longprompt_admission_readers_read_as_their_base(name):
    recs = [_Stamped(1.0, 1.003), _Stamped(2.0, 2.001)]
    recs[1].admit_tick = 0.25
    ctx = readers.Context({"recs": recs, "ticks": [], "trace": None},
                          TINY, {}, PEAKS)
    got = readers.load_reader(f"{name}.longprompt")(ctx)
    assert got is not None
    assert got == readers.load_reader(name)(ctx)
