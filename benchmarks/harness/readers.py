"""What several metric readers (``metrics/<metric>.py``) share: selecting
requests, ticks, spans and traced operations from a run's observations."""

import numpy as np

MOSAIC = 'custom_call_target="tpu_custom_call"'


# --- requests of an open-loop cell -------------------------------------------
# A request that was refused, failed, timed out or came back short enters at
# the largest value a request of that run could have had: its due time to the
# end of the run.

# In a traced run the requests due while the profiler started or stopped
# are left out (``host_exclude`` is empty otherwise).

def _judged(obs) -> list:
    return [r for r in obs.requests if r.measured and obs.outside_stall(r.due)]


def ttft_s(obs) -> np.ndarray:
    end = obs.counters["run_end"]
    return np.array([(r.stamps[0] - r.due) if r.ok else (end - r.due)
                     for r in _judged(obs)])


def tpot_s(obs) -> np.ndarray:
    """Per request: (last token - first token) / (tokens - 1)."""
    end = obs.counters["run_end"]
    out = []
    for r in _judged(obs):
        if not r.ok:
            out.append(end - r.due)
        elif len(r.stamps) > 1:
            out.append((r.stamps[-1] - r.stamps[0]) / (len(r.stamps) - 1))
    return np.array(out)


def token_gaps_s(obs) -> np.ndarray:
    """Every gap between two consecutive tokens of every judged request: what
    a reader of the stream waits for the next token, some thousands of
    samples where the window holds some tens of requests. A request that did
    not get all its tokens enters with each gap it asked for at the largest
    value (see above)."""
    end = obs.counters["run_end"]
    out = []
    for r in _judged(obs):
        if r.ok:
            out.append(np.diff(r.stamps))
        else:
            out.append(np.full(max(r.asked - 1, 1), end - r.due))
    return np.concatenate(out) if out else np.array([])


def latency_summary(obs) -> dict:
    """The open loop's latencies in one place (milliseconds), printed on a
    line of every run so that a statistic can be judged against its
    neighbours without a new run."""
    ttft, tpot, gaps = ttft_s(obs) * 1e3, tpot_s(obs) * 1e3, token_gaps_s(obs) * 1e3
    if not len(ttft) or not len(gaps):
        return {}

    def pct(values, qs):
        return [round(float(np.percentile(values, q)), 3) for q in qs]
    return {"requests": int(len(ttft)), "token_gaps": int(len(gaps)),
            "ttft_mean": round(float(ttft.mean()), 3),
            "ttft_p50_p75_p90_max": pct(ttft, (50, 75, 90, 100)),
            "tpot_request_mean": round(float(tpot.mean()), 3),
            "tpot_request_p50_p90": pct(tpot, (50, 90)),
            "gap_mean": round(float(gaps.mean()), 3),
            "gap_p50_p75_p90_p95_p99": pct(gaps, (50, 75, 90, 95, 99))}


# --- the program's spans and the benchmark's ticks ---------------------------

def span_median_ms(obs, name: str):
    """Median duration of one of the program's spans inside the measured
    window, outside the profiler's start and stop."""
    t0, t1 = obs.window
    durs = [s.dur for s in obs.spans if s.name == name
            and t0 <= s.end <= t1 and obs.outside_stall(s.end)
            and obs.outside_stall(s.start)]
    return float(np.median(durs) * 1e3) if durs else None


def steady_ticks(obs) -> list:
    """Engine ticks (t0, t1, counters, decode contexts) of a traced serving
    run inside the measured window, outside the profiler's start and stop."""
    t0, t1 = obs.window
    return [t for t in obs.ticks if t0 <= t[0] and t[1] <= t1
            and obs.outside_stall(t[0]) and obs.outside_stall(t[1])]


def traced_ticks(obs) -> list:
    """Ticks that ran wholly inside the profiled sub-window."""
    if obs.trace is None:
        return []
    a, b = obs.trace.window
    return [t for t in obs.ticks if a <= t[0] and t[1] <= b]


def mean_per_tick(obs, counter: str):
    sizes = [t[2][counter] for t in steady_ticks(obs) if t[2][counter] > 0]
    return sum(sizes) / len(sizes) if sizes else None


# --- traced operations --------------------------------------------------------

def compute_ops(obs, program_contains: str = "") -> list:
    """Operations of the device's compute line inside the traced window, in
    programs whose name holds ``program_contains``."""
    if obs.trace is None or not obs.trace.devices:
        return []
    a, b = obs.trace.window
    return [o for o in obs.trace.ops if not o.overlapped
            and a <= o.start and o.end <= b and program_contains in o.program]


def kernels(ops) -> list:
    """The Mosaic (Pallas) calls among ``ops``."""
    return [o for o in ops if o.kind == "custom-call" and MOSAIC in o.detail]


def head_dim(hf: dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def itemsize(hf: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[
        hf.get("torch_dtype", "bfloat16")]
