"""The open loop's latency readers on hand-built requests: what enters the
end-to-end metrics of a chat cell, and how a failed request is charged."""

import importlib.util
import pathlib

import numpy as np
import pytest

from benchmarks.harness import readers
from benchmarks.harness.observations import Observations
from benchmarks.harness.run_serve import Record

METRICS = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "metrics"


def metric(name):
    spec = importlib.util.spec_from_file_location(f"_m_{name}",
                                                  METRICS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def request(i, due, stamps, asked=None, measured=True, state="finished"):
    return Record(index=i, measured=measured, prompt_len=10,
                  asked=len(stamps) if asked is None else asked, due=due,
                  sent=due, stamps=list(stamps), state=state)


@pytest.fixture
def obs():
    """Three measured requests and one of the ramp. Gaps of the measured
    ones: 0.1, 0.1, 0.1 | 0.2, 0.4 | 0.05 x 4."""
    o = Observations(kind="serve", cell=None, devices=[])
    o.window = (100.0, 110.0)
    o.counters = {"run_end": 120.0}
    o.requests = [
        request(0, 99.0, [99.5, 99.6], measured=False),
        request(1, 100.0, [100.3, 100.4, 100.5, 100.6]),
        request(2, 101.0, [101.1, 101.3, 101.7]),
        request(3, 102.0, [102.5, 102.55, 102.6, 102.65, 102.7]),
    ]
    return o


def test_token_gaps_are_all_gaps_of_the_measured_requests(obs):
    gaps = readers.token_gaps_s(obs)
    assert sorted(np.round(gaps, 6)) == [0.05] * 4 + [0.1] * 3 + [0.2, 0.4]
    assert metric("serve_tpot_mean_ms")(obs) == pytest.approx(1100 / 9)
    assert metric("serve_tpot_upper_quartile_ms")(obs) == pytest.approx(
        np.percentile(gaps, 75) * 1e3)
    assert metric("serve_tpot_upper_quartile_ms")(obs) == pytest.approx(100.0)
    assert metric("serve_tpot_p50_ms")(obs) == pytest.approx(
        np.percentile(gaps, 50) * 1e3)
    assert metric("serve_tpot_p50_ms")(obs) == pytest.approx(100.0)


def test_per_request_statistics(obs):
    assert readers.ttft_s(obs) == pytest.approx([0.3, 0.1, 0.5])
    assert metric("serve_ttft_upper_quartile_ms")(obs) == pytest.approx(400.0)
    assert metric("serve_ttft_p90_ms")(obs) == pytest.approx(460.0)
    assert readers.tpot_s(obs) == pytest.approx([0.1, 0.3, 0.05])
    assert metric("serve_tpot_request_p90_ms")(obs) == pytest.approx(260.0)


@pytest.mark.parametrize("state,stamps", [("finished", [103.2, 103.3]),
                                          ("timed_out", []),
                                          (None, [])])
def test_a_failed_request_enters_every_statistic_at_the_largest_value(
        obs, state, stamps):
    """Short, timed out or refused: due time to the end of the run, once for
    the first token and once for each gap it asked for."""
    bad = request(4, 103.0, stamps, asked=4, state=state)
    if state is None:
        bad.refused = "BackpressureError"
    obs.requests.append(bad)
    assert not bad.ok
    assert readers.ttft_s(obs)[-1] == pytest.approx(17.0)
    assert readers.tpot_s(obs)[-1] == pytest.approx(17.0)
    gaps = readers.token_gaps_s(obs)
    assert len(gaps) == 9 + 3 and np.sum(np.isclose(gaps, 17.0)) == 3
    assert metric("serve_tpot_upper_quartile_ms")(obs) > 4_000
    assert metric("serve_ttft_upper_quartile_ms")(obs) > 4_000
    # three of twelve gaps at the largest value move the p75, not the median
    assert metric("serve_tpot_p50_ms")(obs) == pytest.approx(100.0)


def test_requests_due_in_the_profilers_stall_are_left_out(obs):
    obs.host_exclude = [(100.9, 101.5)]
    assert len(readers.ttft_s(obs)) == 2
    assert len(readers.token_gaps_s(obs)) == 7


def test_summary_line_holds_every_candidate_statistic(obs):
    summary = readers.latency_summary(obs)
    assert summary["requests"] == 3 and summary["token_gaps"] == 9
    assert summary["ttft_mean"] == pytest.approx(300.0)
    assert summary["gap_p50_p75_p90_p95_p99"][2] == pytest.approx(240.0)
    assert summary["tpot_request_p50_p90"] == pytest.approx([100.0, 260.0])


def test_nothing_to_read_gives_nothing():
    empty = Observations(kind="serve", cell=None, devices=[])
    empty.counters = {"run_end": 1.0}
    for name in ("serve_tpot_p50_ms", "serve_tpot_upper_quartile_ms",
                 "serve_tpot_mean_ms",
                 "serve_ttft_upper_quartile_ms", "serve_ttft_p90_ms",
                 "serve_tpot_request_p90_ms"):
        assert metric(name)(empty) is None
    assert readers.latency_summary(empty) == {}
