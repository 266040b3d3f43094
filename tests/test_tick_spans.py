"""The serving tick's host spans and the requests' wall-clock stamps.

Every ``collm.*`` span opens through ``serving_loop._span``; a recorder
put in its place sees the tick's span tree.  The stamps
(``submitted_wall`` <= ``admitted_wall`` <= ``first_token_wall`` <=
``finished_wall``) are what ``ServeStats.ttft``/``tpot`` are made of,
and ``LiveReplica`` keeps the control plane's arrival and stamps
``Request.first_token_at`` on the caller's clock."""
import jax
import pytest

from conftest import make_batch, sample_prompts
from repro.configs.registry import get_config
from repro.core.engine import make_engine
from repro.core.interfaces import Request
from repro.runtime import serving_loop
from repro.runtime.replica import LiveReplica
from repro.runtime.serving_loop import ContinuousBatcher, GenRequest


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen1.5-0.5b").scaled()
    engine = make_engine(cfg, lr=3e-3)
    params = engine.model.init(jax.random.key(0))
    lora = engine.model.init_lora(jax.random.key(1))
    return cfg, engine, params, lora


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each
    span as ``[name, args, children]`` under the span open around it."""

    def __init__(self):
        self.roots = []
        self.stack = []
        rec = self

        class Span:
            def __init__(self, name, **kw):
                self.node = [name, dict(kw), []]

            def __enter__(self):
                (rec.stack[-1][2] if rec.stack else rec.roots).append(
                    self.node)
                rec.stack.append(self.node)
                return self

            def __exit__(self, *exc):
                rec.stack.pop()

            def set_metadata(self, **kw):
                self.node[1].update(kw)

        self.span = Span


def _tree(node):
    """(name, [children's trees]) without the args."""
    return node[0], [_tree(c) for c in node[2]]


@pytest.fixture
def recorder(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(serving_loop, "_span", rec.span)
    return rec


def _reqs(cfg, n, gen=3):
    prompts = sample_prompts(cfg, n, [5, 7, 6, 4, 8][:n])
    return [GenRequest(request_id=i, prompt=p, max_new_tokens=gen)
            for i, p in enumerate(prompts)]


def test_decode_tick_span_tree(setup, recorder):
    cfg, engine, params, lora = setup
    b = ContinuousBatcher(engine, params, lora, n_slots=2, max_seq=16,
                          prompt_pad=8, paged=True, block_size=4)
    for r in _reqs(cfg, 2):
        b.submit(r)
    b.step()          # admits both, then decodes
    b.step()          # decodes only
    first, second = recorder.roots
    tail = [("collm.grow_tables", []), ("collm.dispatch", []),
            ("collm.token_pull", []), ("collm.emit", [])]
    assert _tree(first) == ("collm.step", [
        ("collm.admit", [("collm.prefill_wave", [])])] + tail)
    assert _tree(second) == ("collm.step", [("collm.admit", [])] + tail)
    wave = first[2][0][2][0]
    assert wave[1] == {"rows": 2, "tokens": 12, "ids": "0 1"}
    assert first[1] == {"rows": 2, "trained": 0}


def test_cotrain_tick_with_no_rows_trains_in_its_own_span(setup,
                                                          recorder):
    cfg, engine, params, lora = setup
    b = ContinuousBatcher(engine, params, lora, n_slots=2, max_seq=16,
                          prompt_pad=8, paged=True, block_size=4,
                          opt_state=engine.optimizer.init(lora))
    b.step(train_batch=make_batch(cfg, batch=2, seq=8))
    (tick,) = recorder.roots
    assert _tree(tick) == ("collm.step", [("collm.admit", []),
                                          ("collm.train", [])])
    assert tick[1] == {"rows": 0, "trained": 1}


def test_sampling_and_chunked_prefill_spans(setup, recorder):
    cfg, engine, params, lora = setup
    b = ContinuousBatcher(engine, params, lora, n_slots=2, max_seq=16,
                          prompt_pad=8, paged=True, block_size=4,
                          prefill_chunk=4)
    (r,) = _reqs(cfg, 1)
    r.temperature = 0.7
    b.submit(r)
    b.step()          # the prompt's first chunk: no row decodes yet
    b.step()          # its last chunk, then the decode wave samples
    names = [[c[0] for c in tick[2]] for tick in recorder.roots]
    assert names[0] == ["collm.admit", "collm.advance_prefill"]
    assert names[1] == ["collm.admit", "collm.advance_prefill",
                     "collm.grow_tables", "collm.dispatch",
                     "collm.token_pull", "collm.sample", "collm.emit"]


@pytest.mark.parametrize("kw", [
    {},
    {"paged": True, "block_size": 4},
    {"paged": True, "block_size": 4, "prefill_chunk": 4},
], ids=["contiguous", "paged", "paged-chunked"])
def test_stamps_in_order_and_serve_stats_read_them(setup, kw):
    cfg, engine, params, lora = setup
    b = ContinuousBatcher(engine, params, lora, n_slots=2, max_seq=16,
                          prompt_pad=8, **kw)
    reqs = _reqs(cfg, 5)     # more than the slots: some wait a tick
    for r in reqs:
        b.submit(r)
    done = []
    while not b.idle():
        done.extend(b.step())
    assert sorted(r.request_id for r in done) == list(range(5))
    for r in reqs:
        assert r.submitted_wall <= r.admitted_wall \
            <= r.first_token_wall <= r.finished_wall
    assert b.stats.ttft == [r.first_token_wall - r.submitted_wall
                            for r in done]
    assert b.stats.tpot == pytest.approx(
        [(r.finished_wall - r.first_token_wall) / (len(r.tokens) - 1)
         for r in done])


def test_live_replica_keeps_arrival_and_stamps_first_token(setup):
    cfg, engine, params, lora = setup
    rep = LiveReplica("r0", "m", engine, params, lora,
                      engine.optimizer.init(lora),
                      on_result=lambda res, sid: None,
                      data_fn=lambda n: make_batch(cfg, batch=n, seq=8),
                      serve_slots=2, serve_prompt_len=8,
                      max_gen_tokens=3)
    gens = []
    submit = rep.batcher.submit
    rep.batcher.submit = lambda g: (gens.append(g), submit(g))
    prompts = sample_prompts(cfg, 3, [5, 6, 7])
    reqs = [Request(request_id=i, stream_id="s", arrival=1.5 + i,
                    deadline=1e9, tokens=3, prompt=prompts[i])
            for i in range(3)]
    rep.submit_batch(reqs, now=4.0)
    now, first_seen = 10.0, {}
    while rep.pump_once(now=now):
        for r, g in zip(reqs, gens):
            if g.tokens:
                first_seen.setdefault(r.request_id, now)
        now += 1.0
    assert [g.arrival for g in gens] == [1.5, 2.5, 3.5]
    assert [r.first_token_at for r in reqs] == \
        [first_seen[i] for i in range(3)]
    # two slots: the third request waits for a slot to free
    assert reqs[0].first_token_at == 10.0 < reqs[2].first_token_at
    assert all(r.completed_at is not None for r in reqs)
