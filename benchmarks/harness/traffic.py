"""The one general traffic generator. A traffic mix is a data file of
parameters under ``traffic/``; this module turns it, a cell's own parameters
and ``--seed`` into the requests or batches of one run.

Every run of a cell serves the same work. Lengths are not drawn at random:
for ``n`` requests the generator takes the ``n`` prompt lengths and the ``n``
output lengths at evenly spaced quantiles of their distributions and pairs
them by one permutation fixed in the traffic file, so every seed gives the
same multiset of requests and the same token count.

In an open loop ``--seed`` shuffles the order the multiset is sent in, places
the arrivals (sorted uniform draws over the span, i.e. a Poisson process
given its count) and makes the token ids: two seeds are two hours of the same
traffic, and a metric is judged only if it repeats across them (PERF.md,
PR 23: over the chat cell's 51 requests the upper quartile of the token gaps
does, no statistic of the time to first token does). In a closed loop each
block of requests is the whole multiset (so any prefix the callers get
through holds the same mix); ``--seed`` makes the token ids and, unless the
traffic file fixes an ``order_seed``, the order inside every block. With one
order for every seed a quiet host replays one schedule token for token and a
busy one leaves it, so the spread a check reads is 0 or 0.5% by the host,
and neither is the 1% by which two schedules of this loop differ (PERF.md,
PR 27); with the seed's order every run is another deal on any host.
"""

import dataclasses
import statistics

import numpy as np


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` whole lengths at the quantiles (i + 0.5) / n of ``dist``,
    ascending. ``dist`` = {"dist": "lognormal", "median", "sigma", "min",
    "max"} or {"dist": "uniform", "min", "max"}; both are clipped to
    [min, max]."""
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
        values = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        values = dist["min"] + q * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(values), dist["min"], dist["max"]).astype(np.int64)


def length_pairs(traffic: dict, n: int) -> np.ndarray:
    """[n, 2] (prompt, output) lengths: the two quantile ladders paired by the
    traffic file's own permutation, so the pairing is the same in every run."""
    prompts = quantile_lengths(traffic["prompt_tokens"], n)
    outputs = quantile_lengths(traffic["output_tokens"], n)
    pairing = np.random.default_rng(traffic["pairing_seed"]).permutation(n)
    return np.stack([prompts, outputs[pairing]], axis=1)


def arrival_offsets(rng, n: int, seconds: float) -> np.ndarray:
    """``n`` sorted arrival offsets in [0, seconds): uniform draws, i.e. a
    Poisson process given its count."""
    return np.sort(rng.uniform(0.0, seconds, n))


@dataclasses.dataclass
class Schedule:
    """Requests of one serving run, in the order they are sent."""
    due_s: np.ndarray        # seconds from the window's opening (ramp: < 0);
    #                          all zeros in a closed loop (sent when a client is free)
    prompt_len: np.ndarray
    output_len: np.ndarray
    measured: np.ndarray     # open loop: due inside the window
    prompts: list            # int32 token arrays

    def __len__(self):
        return len(self.prompt_len)


def _tokens(rng, lengths, vocab: int) -> list:
    flat = rng.integers(0, vocab, int(np.sum(lengths)), dtype=np.int32)
    return np.split(flat, np.cumsum(lengths)[:-1])


def open_loop_schedule(traffic: dict, rate_rps: float, seconds: float,
                       seed: int, vocab: int) -> Schedule:
    """A ramp of ``traffic["ramp_s"]`` seconds then a window of ``seconds``,
    both at ``rate_rps``; each part holds its own quantile multiset, in an
    order and at arrival offsets drawn from ``seed``."""
    rng = np.random.default_rng([int(seed), 1])
    order = np.random.default_rng([int(seed), 5])
    parts = []
    for span, start, measured in ((traffic["ramp_s"], -traffic["ramp_s"], False),
                                  (seconds, 0.0, True)):
        n = int(round(rate_rps * span))
        pairs = length_pairs(traffic, n)[order.permutation(n)]
        parts.append((start + arrival_offsets(order, n, span), pairs,
                      np.full(n, measured)))
    due = np.concatenate([p[0] for p in parts])
    pairs = np.concatenate([p[1] for p in parts])
    return Schedule(due, pairs[:, 0], pairs[:, 1],
                    np.concatenate([p[2] for p in parts]),
                    _tokens(rng, pairs[:, 0], vocab))


def closed_loop_schedule(traffic: dict, seed: int, vocab: int) -> Schedule:
    """``traffic["blocks"]`` blocks of ``traffic["block_requests"]`` requests.
    Each block is the whole quantile multiset in an order of its own (from
    ``seed``, or from the traffic file's ``order_seed`` where it has one), so
    any prefix the clients get through holds the same mix to within one
    block."""
    rng = np.random.default_rng([int(seed), 2])
    fixed = traffic.get("order_seed")
    order = np.random.default_rng([int(seed), 6] if fixed is None else fixed)
    size = traffic["block_requests"]
    base = length_pairs(traffic, size)
    pairs = np.concatenate([base[order.permutation(size)]
                            for _ in range(traffic["blocks"])])
    n = len(pairs)
    return Schedule(np.zeros(n), pairs[:, 0], pairs[:, 1], np.ones(n, bool),
                    _tokens(rng, pairs[:, 0], vocab))


def train_batches(traffic: dict, seed: int, vocab: int, chips: int) -> list:
    """``traffic["distinct_batches"]`` optimizer-step batches of random token
    rows from the seed, each shaped [gas, chips, seq_len] (one row per chip
    per micro-step) so that ``rows_per_step`` rows are trained whatever the
    chip count."""
    rows, seq = traffic["rows_per_step"], traffic["seq_len"]
    if rows % chips:
        raise ValueError(f"{rows} rows per step do not split over {chips} chips")
    rng = np.random.default_rng([int(seed), 3])
    return [rng.integers(0, vocab, (rows // chips, chips, seq), dtype=np.int32)
            for _ in range(traffic["distinct_batches"])]
