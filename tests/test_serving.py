"""Serving-layer tests: continuous batching over the v2 ragged engine with
request lifecycle, streaming, admission control, drain, and the HTTP front
door — all hermetic on CPU with the tiny fp32 llama.

Every engine here uses the SAME kv/bucket shapes so jit compilations are
shared across tests (XLA static shapes — one compile per shape per process).
"""

import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2, V2EngineConfig
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM, TINY_LLAMA
from deepspeed_tpu.serving import (BackpressureError, InferenceServer,
                                   RequestState, ServerClosedError,
                                   ServingConfig, ServingFrontend)
from serving_fakes import ResidentEngine


def _tiny_fp32():
    return LlamaConfig(**{**TINY_LLAMA.__dict__, "dtype": jnp.float32,
                          "max_seq_len": 512})


@pytest.fixture(scope="module")
def model_and_params():
    cfg = _tiny_fp32()
    model = LlamaForCausalLM(cfg)
    batch = {"input_ids": np.zeros((1, 8), np.int32)}
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    return cfg, params


KV_BLOCKS = 64  # shared across all engines: kv shape is a compile shape


def _engine(cfg, params):
    return InferenceEngineV2(params, cfg, V2EngineConfig(
        kv_block_size=16, kv_num_blocks=KV_BLOCKS,
        scheduler=SchedulerConfig(max_tokens_per_step=64,
                                  prefill_buckets=(16, 32, 64))))


def _server(cfg, params, **kw):
    kw.setdefault("max_queue_depth", 32)
    return InferenceServer(_engine(cfg, params), ServingConfig(**kw))


def _prompts(rng, lengths, vocab):
    return [list(rng.integers(0, vocab, n)) for n in lengths]


# ---------------------------------------------------------------------------
# the acceptance workload: ≥8 concurrent mixed-length requests
# ---------------------------------------------------------------------------
def test_concurrent_workload_interleaving_parity_backpressure(model_and_params):
    cfg, params = model_and_params
    rng = np.random.default_rng(0)
    # request 0 is long (prompt 48, 24 new); 1..7 are short and finish first
    lengths = [48, 8, 12, 16, 8, 20, 8, 12]
    max_new = [24, 4, 6, 4, 8, 4, 6, 4]
    prompts = _prompts(rng, lengths, cfg.vocab_size)
    # worst-case blocks (16-token blocks): 5 + 1+2+2+1+2+1+1 = 15; watermark
    # 0.25 of 64 = 16 blocks, so the 8-request workload fits and a burst of
    # 1-block extras must start rejecting by the second extra
    server = _server(cfg, params, kv_high_watermark=0.25).start()
    try:
        reqs = [server.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, max_new)]
        # stream one short request concurrently to prove live fan-out
        streamed = []
        t = threading.Thread(
            target=lambda: streamed.extend(reqs[1].stream(timeout=120)))
        t.start()

        # (c) backpressure: burst of extras while the 8 are in flight
        rejected, extras = 0, []
        for _ in range(15):
            try:
                extras.append(server.submit(_prompts(rng, [8], cfg.vocab_size)[0],
                                            max_new_tokens=4))
            except BackpressureError as e:
                rejected += 1
                assert e.retry_after_s > 0
        assert rejected > 0, "KV watermark never pushed back"

        for r in reqs + extras:
            r.result(timeout=300)
        t.join(timeout=10)
        assert all(r.state == RequestState.FINISHED for r in reqs + extras)
        assert all(r.finish_reason == "length" for r in reqs)

        # (a) interleaving: a later-submitted short finished before request 0
        assert any(r.finish_ts < reqs[0].finish_ts for r in reqs[1:]), \
            "no short request finished before the long one"

        # (b) parity: streamed tokens == direct single-request engine run
        assert streamed == reqs[1].tokens
        for p, m, r in zip(prompts, max_new, reqs):
            solo = _engine(cfg, params).generate(p, max_new_tokens=m)
            assert r.tokens == solo, f"uid {r.uid} diverged from solo run"

        # request-level metrics populated
        assert all(r.queue_wait_s > 0 and r.ttft_s > 0 for r in reqs)
        snap = server.metrics.snapshot()
        assert snap["requests_completed"] == len(reqs) + len(extras)
        assert snap["requests_rejected"] == rejected
        assert snap["ttft_mean_s"] > 0 and snap["tpot_mean_s"] > 0
        assert snap["queue_wait_mean_s"] > 0
        assert snap["kv_occupancy_peak"] > 0
        assert snap["tokens_generated"] == sum(len(r.tokens)
                                               for r in reqs + extras)
    finally:
        server.stop(drain_timeout=5.0)


def test_queue_depth_backpressure(model_and_params):
    """Queue-bound rejection, deterministic: the loop is not started, so
    submissions sit in the admission queue."""
    cfg, params = model_and_params
    server = _server(cfg, params, max_queue_depth=3)
    for _ in range(3):
        server.submit([1, 2, 3], max_new_tokens=2)
    with pytest.raises(BackpressureError) as ei:
        server.submit([1, 2, 3], max_new_tokens=2)
    assert ei.value.retry_after_s > 0
    assert server.metrics.snapshot()["requests_rejected"] == 1


def test_timeout_and_cancel(model_and_params):
    cfg, params = model_and_params
    server = _server(cfg, params).start()
    try:
        # deadline far shorter than a 500-token decode on this host
        timed = server.submit([3, 1, 4, 1, 5], max_new_tokens=500,
                              timeout_s=0.15)
        timed.wait(timeout=60)
        assert timed.state == RequestState.TIMED_OUT
        assert timed.finish_reason == "timeout"
        assert len(timed.tokens) < 500

        # its blocks count against admission until the loop has reaped it,
        # which with a step in flight is a wait for that step later (the
        # request is settled first): two budgets of 500 pass the watermark
        deadline = time.monotonic() + 30
        while server.metrics.snapshot()["requests_timed_out"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        cancelled = server.submit([2, 7, 1, 8], max_new_tokens=500)
        it = cancelled.stream(timeout=60)
        first = next(it)                      # wait for decode to start
        cancelled.cancel()
        rest = list(it)                       # stream must terminate
        cancelled.wait(timeout=60)
        assert cancelled.state == RequestState.CANCELLED
        assert cancelled.finish_reason == "cancelled"
        assert [first] + rest == cancelled.tokens

        # engine state fully reaped afterwards: KV occupancy returns to 0
        deadline = time.monotonic() + 30
        while server.engine.kv_occupancy() > 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert server.engine.kv_occupancy() == 0.0
        snap = server.metrics.snapshot()
        assert snap["requests_timed_out"] == 1
        assert snap["requests_cancelled"] == 1
    finally:
        server.stop(drain_timeout=5.0)


def test_graceful_drain(model_and_params):
    cfg, params = model_and_params
    server = _server(cfg, params).start()
    reqs = [server.submit([7, 7, 7, i + 1], max_new_tokens=6)
            for i in range(3)]
    assert server.drain(timeout=120), "drain timed out with work in flight"
    with pytest.raises(ServerClosedError):
        server.submit([1, 2, 3])
    # in-flight requests completed with their full budget
    for r in reqs:
        assert r.state == RequestState.FINISHED
        assert len(r.tokens) == 6
    server.stop(drain_timeout=5.0)
    assert not server.running


def test_oversized_request_fails_alone(model_and_params):
    """A request the engine can never hold fails itself, not the server."""
    cfg, params = model_and_params
    server = _server(cfg, params).start()
    try:
        with pytest.raises(ValueError):
            server.submit(list(range(600)), max_new_tokens=4)  # > max_seq_len
        ok = server.submit([5, 5, 5], max_new_tokens=3)
        assert ok.result(timeout=120) == ok.tokens and len(ok.tokens) == 3
    finally:
        server.stop(drain_timeout=5.0)


# ---------------------------------------------------------------------------
# HTTP front door on a real localhost socket
# ---------------------------------------------------------------------------
def _http(method, host, port, path, body=None):
    conn = http.client.HTTPConnection(host, port, timeout=300)
    try:
        conn.request(method, path,
                     body=None if body is None else json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def test_frontend_generate_metrics_healthz(model_and_params):
    cfg, params = model_and_params
    server = _server(cfg, params).start()
    fe = ServingFrontend(server, port=0).start()
    host, port = fe.host, fe.port
    try:
        status, _, body = _http("GET", host, port, "/healthz")
        assert status == 200 and json.loads(body)["status"] == "serving"

        status, _, body = _http("POST", host, port, "/generate",
                                {"prompt_tokens": [9, 8, 7, 6],
                                 "max_new_tokens": 5})
        out = json.loads(body)
        assert status == 200 and len(out["tokens"]) == 5
        assert out["finish_reason"] == "length"
        solo = _engine(cfg, params).generate([9, 8, 7, 6], max_new_tokens=5)
        assert out["tokens"] == solo

        # streaming endpoint: http.client de-chunks transparently
        status, headers, body = _http("POST", host, port, "/generate",
                                      {"prompt_tokens": [9, 8, 7, 6],
                                       "max_new_tokens": 5, "stream": True})
        assert status == 200
        lines = [json.loads(l) for l in body.decode().splitlines() if l]
        assert [l["token"] for l in lines[:-1]] == solo
        assert lines[-1]["done"] is True

        status, _, err = _http("POST", host, port, "/generate", {"nope": 1})
        assert status == 400

        status, headers, body = _http("GET", host, port, "/metrics")
        assert status == 200
        text = body.decode()
        metrics = {}
        for line in text.splitlines():
            if line.startswith("#") or not line.strip():
                continue
            key, val = line.rsplit(" ", 1)
            metrics[key] = float(val)
        assert metrics["dstpu_serving_ttft_seconds_count"] > 0
        assert metrics["dstpu_serving_ttft_seconds_sum"] > 0
        assert metrics["dstpu_serving_tpot_seconds_sum"] > 0
        assert metrics["dstpu_serving_queue_wait_seconds_sum"] > 0
        assert metrics["dstpu_serving_kv_occupancy_peak"] > 0
        assert metrics["dstpu_serving_tokens_generated"] == 10
        assert metrics["dstpu_serving_requests_completed"] == 2

        # backpressure surfaces as 429 + Retry-After
        tiny = InferenceServer(_engine(cfg, params),
                               ServingConfig(max_queue_depth=0))
        fe2 = ServingFrontend(tiny, port=0).start()
        try:
            status, headers, body = _http("POST", fe2.host, fe2.port,
                                          "/generate",
                                          {"prompt_tokens": [1, 2]})
            assert status == 429 and "Retry-After" in headers
        finally:
            fe2.stop()

        # drain: healthz flips to 503, new work refused with 503
        server.drain(timeout=60)
        status, _, body = _http("GET", host, port, "/healthz")
        assert status == 503 and json.loads(body)["status"] == "draining"
        status, _, body = _http("POST", host, port, "/generate",
                                {"prompt_tokens": [1, 2, 3]})
        assert status == 503
    finally:
        fe.stop()
        server.stop(drain_timeout=5.0)


def test_monitor_export(model_and_params, tmp_path):
    """Serving metrics fan out through the deepspeed_tpu.monitor backends."""
    cfg, params = model_and_params
    from deepspeed_tpu.config.config import CSVConfig
    from deepspeed_tpu.monitor import CSVMonitor
    mon = CSVMonitor(CSVConfig(enabled=True, output_path=str(tmp_path),
                               job_name="serve"))
    server = _server(cfg, params).start()
    try:
        server.submit([4, 4, 4], max_new_tokens=3).result(timeout=120)
        server.metrics.export(mon, step=1)
    finally:
        server.stop(drain_timeout=5.0)
    written = list((tmp_path / "serve").glob("*.csv"))
    names = {p.stem for p in written}
    assert "serving_tokens_generated" in names
    assert "serving_ttft_mean_s" in names


# ---------------------------------------------------------------------------
# engine failure -> degraded health (load balancers must stop routing)
# ---------------------------------------------------------------------------
class _ExplodingEngine(ResidentEngine):
    """Minimal engine double whose step() always raises — the serve loop
    must fail the in-flight requests AND flip health to unhealthy."""

    def step(self):
        raise RuntimeError("kaboom: device went away")


def test_health_degraded_after_engine_step_failure():
    server = InferenceServer(_ExplodingEngine(),
                             ServingConfig(idle_poll_s=0.001)).start()
    frontend = ServingFrontend(server).start()
    try:
        req = server.submit([1, 2, 3], max_new_tokens=4)
        assert req.wait(timeout=10.0)
        assert req.state == RequestState.FAILED

        h = server.health()
        assert h["status"] == "degraded"
        assert h["ok"] is False
        assert "engine step failed" in h["degraded_reason"]
        # /healthz mirrors it with a 503 so LBs eject this replica
        status, _, body = _http("GET", frontend.host, frontend.port,
                                "/healthz")
        assert status == 503
        assert json.loads(body)["status"] == "degraded"
        # a suspect engine refuses new work at the door (503, not a slow 500)
        with pytest.raises(ServerClosedError):
            server.submit([1, 2, 3], max_new_tokens=4)
    finally:
        frontend.stop()
        server.stop(drain_timeout=2.0)
