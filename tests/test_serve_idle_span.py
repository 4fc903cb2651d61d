"""``serve/idle``: the serve loop's no-work wait is a span. A stretch in which
``_serve_once`` found nothing to do is stamped on the loop's thread from the
end of the last thing the loop did to the start of the next tick that stamps a
``serve/tick``, in pieces of at most 0.25 s, and counted with tracing on or
off in ``ServingMetrics.loop_idle_seconds``. The server runs over an engine
double here: what is tested is the loop, not a model."""

import math
import threading
import time

import pytest

from deepspeed_tpu.serving.request import RequestState
from deepspeed_tpu.serving.server import (IDLE_PIECE_S, InferenceServer,
                                          ServingConfig)
from deepspeed_tpu.telemetry.tracer import HOST_GC_TID, get_tracer
from serving_fakes import ResidentEngine

IDLE, TICK = "serve/idle", "serve/tick"
#: what two stamps of one instant may differ by (float sums of a monotonic
#: clock in the 1e5 s) and what the tick's phases already tile to
EXACT, TILE = 1e-6, 20e-3


class _Engine(ResidentEngine):
    """An engine double that hands every resident sequence one token a step,
    from the step's own call: its ``collect`` has nothing pending."""

    def __init__(self, schedulable=True, explode_at=None):
        super().__init__()
        self.schedulable = schedulable
        self.explode_at = explode_at
        self.steps = 0

    def can_schedule(self, uids, needs):
        return self.schedulable

    def step(self):
        self.steps += 1
        if self.steps == self.explode_at:
            raise RuntimeError("transient: one step lost")
        return super().step()


@pytest.fixture
def ring():
    t = get_tracer()
    was = t.enabled
    t.configure(enabled=True)
    t.clear()
    yield t
    t.configure(enabled=was)
    t.clear()


def _spans(tracer):
    """(name, start, end, tid, args) of the ring's complete events but the
    interpreter's collections, which land where they will."""
    return [(e[1], e[4], e[4] + e[5], e[6], e[7] or {})
            for e in tracer.events_snapshot()
            if e[3] == "X" and e[6] != HOST_GC_TID]


def _serve(engine, idle_before=0.6, idle_after=0.3, poll=0.005, budget=3):
    """A started server that idles, serves one request and idles again.
    Returns (server, request, wake-ups the loop made)."""
    server = InferenceServer(engine, ServingConfig(idle_poll_s=poll))
    wakes = []
    wait = server._wake.wait

    def counted(timeout=None):
        woke = wait(timeout)
        wakes.append(time.monotonic())
        return woke
    server._wake.wait = counted
    server.start()
    try:
        time.sleep(idle_before)
        req = server.submit([1, 2, 3], max_new_tokens=budget)
        assert req.wait(timeout=10.0)
        time.sleep(idle_after)
    finally:
        server.stop(drain_timeout=2.0)
    return server, req, wakes


def test_an_idle_stretch_is_stamped_in_quarter_second_pieces(ring):
    server, req, wakes = _serve(_Engine())
    assert req.state is RequestState.FINISHED and len(req.tokens) == 3
    spans = _spans(ring)
    ticks = [s for s in spans if s[0] == TICK]
    idles = [s for s in spans if s[0] == IDLE]
    assert len(ticks) >= 3 and idles
    first_tick = min(s[1] for s in ticks)
    before = [s for s in idles if s[2] <= first_tick + EXACT]
    # while it idles the loop stamps the stretch's pieces and nothing else
    loop = ticks[0][3]
    assert [s[0] for s in spans
            if s[3] == loop and s[1] < first_tick - EXACT] == \
        [IDLE] * len(before)
    stretch = before[-1][2] - before[0][1]
    assert 0.5 < stretch < 5.0
    assert len(before) == math.ceil(stretch / IDLE_PIECE_S)
    assert all(s[2] - s[1] <= IDLE_PIECE_S + EXACT for s in idles)
    assert all(abs(s[2] - s[1] - IDLE_PIECE_S) < EXACT for s in before[:-1])
    for a, b in zip(before, before[1:]):
        assert abs(a[2] - b[1]) < EXACT            # the pieces tile
    # ... and the last one ends where the first working tick begins
    assert abs(before[-1][2] - first_tick) < EXACT
    # on the loop's thread, which is the one that stamps the ticks
    assert {s[3] for s in idles} == {s[3] for s in ticks}
    assert len({s[3] for s in ticks}) == 1
    for s in idles:
        assert set(s[4]) >= {"polls", "queued", "inflight", "woke"}
        assert s[4]["queued"] == 0 and s[4]["inflight"] == 0
    # every wake-up of the stretch is counted in one of its pieces
    in_stretch = sum(1 for t in wakes if t <= first_tick)
    assert sum(s[4]["polls"] for s in before) == in_stretch
    assert in_stretch >= 5
    assert before[-1][4]["woke"] == "submit"
    assert all(s[4]["woke"] == "timeout" for s in before[:-1])


def test_idle_and_tick_spans_tile_the_loops_life(ring):
    server, _, _ = _serve(_Engine(), idle_before=0.6, idle_after=0.6)
    spans = sorted((s for s in _spans(ring) if s[0] in (IDLE, TICK)),
                   key=lambda s: s[1])
    assert spans[0][0] == IDLE and spans[-1][0] == IDLE
    whole = spans[-1][2] - spans[0][1]
    covered = sum(s[2] - s[1] for s in spans)
    assert covered <= whole + EXACT and covered >= 0.99 * whole
    for a, b in zip(spans, spans[1:]):
        assert -EXACT <= b[1] - a[2] < TILE        # no overlap, no hole
    # the stretch after the last tick opens exactly where that tick ended
    last_tick = max(i for i, s in enumerate(spans) if s[0] == TICK)
    assert abs(spans[last_tick + 1][1] - spans[last_tick][2]) < EXACT
    # what the spans say, the counter says
    idle_s = sum(s[2] - s[1] for s in spans if s[0] == IDLE)
    assert server.metrics.snapshot()["loop_idle_seconds"] == \
        pytest.approx(idle_s, abs=1e-4)


def _tiny_engine_with_every_tier():
    from deepspeed_tpu.serving.bench_serve import build_tiny_server
    return build_tiny_server().engine      # a pool, the host tier, the cache


@pytest.mark.parametrize("engine,config", [
    (_Engine, {}),
    # the memory counter tracks are sampled by ticks that did something,
    # whatever engine is served: over the double the parent's server left
    # ``serve/kv_bytes`` out, over an engine it wrote it fifty times a second
    (_tiny_engine_with_every_tier, {"kv_offload_enabled": True,
                                    "prefix_cache_enabled": True}),
], ids=["double", "engine-with-tier-and-cache"])
def test_an_idle_server_writes_at_most_four_events_a_second(ring, engine,
                                                            config):
    server = InferenceServer(engine(), ServingConfig(idle_poll_s=0.002,
                                                     **config)).start()
    try:
        t0 = time.monotonic()
        time.sleep(1.3)
        events = [e for e in ring.events_snapshot() if e[6] != HOST_GC_TID]
        elapsed = time.monotonic() - t0
    finally:
        server.stop(drain_timeout=2.0)
    assert {e[1] for e in events} == {IDLE}
    assert 4 <= len(events) <= 4 * elapsed + 1


def test_with_tracing_off_the_ring_stays_empty_and_the_counter_rises():
    tracer = get_tracer()
    was = tracer.enabled
    tracer.configure(enabled=False)
    tracer.clear()
    server = InferenceServer(_Engine(),
                             ServingConfig(idle_poll_s=0.002)).start()
    try:
        time.sleep(0.4)
        first = server.metrics.snapshot()["loop_idle_seconds"]
        time.sleep(0.4)
        second = server.metrics.snapshot()["loop_idle_seconds"]
        req = server.submit([1, 2, 3], max_new_tokens=2)
        assert req.wait(timeout=10.0)
    finally:
        server.stop(drain_timeout=2.0)
        tracer.configure(enabled=was)
    assert tracer.events_snapshot() == []
    assert first >= IDLE_PIECE_S and second >= first + IDLE_PIECE_S
    total = server.metrics.snapshot()["loop_idle_seconds"]
    assert second <= total < 10.0
    text = server.metrics.prometheus_text()
    assert "# TYPE dstpu_serving_loop_idle_seconds counter" in text
    assert "# TYPE dstpu_serving_ticks_device_starved counter" in text


def test_requests_queued_but_refused_admission_read_idle_with_work(ring):
    engine = _Engine(schedulable=False)
    server = InferenceServer(engine, ServingConfig(idle_poll_s=0.002)).start()
    try:
        req = server.submit([1, 2, 3], max_new_tokens=2)
        time.sleep(0.6)
        assert not req.state.terminal
        engine.schedulable = True
        assert req.wait(timeout=10.0)
    finally:
        server.stop(drain_timeout=2.0)
    idles = [s for s in _spans(ring) if s[0] == IDLE]
    first_tick = min(s[1] for s in _spans(ring) if s[0] == TICK)
    waiting = [s for s in idles if s[2] <= first_tick + EXACT]
    assert len(waiting) >= 2
    # the first piece may open before the submit; every later one saw it
    assert all(s[4]["queued"] == 1 and s[4]["inflight"] == 0
               for s in waiting[1:])
    after = [s for s in idles if s[1] >= first_tick]
    assert after and all(s[4]["queued"] == 0 for s in after)


def test_a_tick_that_raises_is_not_stamped_as_idling(ring):
    engine = _Engine(explode_at=2)
    server, req, _ = _serve(engine, idle_before=0.1, idle_after=0.1, budget=4)
    assert req.state.terminal
    spans = _spans(ring)
    steps = sorted((s for s in spans if s[0] == "serve/engine_step"),
                   key=lambda s: s[1])
    assert len(steps) >= 2
    faulted = steps[1]
    assert not any(s[0] == TICK and s[1] <= faulted[1] <= s[2]
                   for s in spans)                 # it stamped no tick
    for s in spans:
        if s[0] == IDLE:                           # and lies in no idle span
            assert s[2] <= faulted[1] + EXACT or s[1] >= faulted[2] - EXACT


def test_hand_ticked_servers_stamp_no_idle_span(ring):
    """``_serve_once`` called by hand (as the step-in-flight tests do) opens
    no stretch: only the loop's wait does."""
    server = InferenceServer(_Engine(), ServingConfig())
    server._serve_once()
    req = server.submit([1, 2, 3], max_new_tokens=2)
    for _ in range(4):
        server._serve_once()
    assert req.state is RequestState.FINISHED
    names = {s[0] for s in _spans(ring)}
    assert TICK in names and IDLE not in names
    assert server.metrics.snapshot()["loop_idle_seconds"] == 0.0
    assert threading.current_thread().ident in {s[3] for s in _spans(ring)}
