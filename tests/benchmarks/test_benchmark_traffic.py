"""The traffic generator: every seed serves the same multiset of work."""

import json
import pathlib

import numpy as np
import pytest

from benchmarks.harness import traffic

TRAFFIC = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "traffic"


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def pairs(schedule, mask=None):
    mask = np.ones(len(schedule), bool) if mask is None else mask
    return sorted(zip(schedule.prompt_len[mask].tolist(),
                      schedule.output_len[mask].tolist()))


@pytest.mark.parametrize("seed", [1, 7, 2 ** 31 + 5])
def test_open_loop_same_multiset_for_any_seed(seed):
    """The same requests and the same token count, ramp and window each; the
    seed shuffles their order, places the arrivals and makes the token ids."""
    chat = load("chat")
    base = traffic.open_loop_schedule(chat, 3.0, 45, 0, 32000)
    other = traffic.open_loop_schedule(chat, 3.0, 45, seed, 32000)
    assert np.array_equal(base.measured, other.measured)
    for part in (base.measured, ~base.measured):
        assert pairs(base, part) == pairs(other, part)
    assert base.prompt_len.sum() == other.prompt_len.sum()
    assert not np.array_equal(base.prompt_len, other.prompt_len)
    assert not np.array_equal(base.due_s, other.due_s)
    assert not np.array_equal(base.prompts[0][:16], other.prompts[0][:16])
    # the order is a shuffle of the quantile ladder, not the ladder itself
    assert np.any(np.diff(base.prompt_len[base.measured]) < 0)


def test_open_loop_same_seed_same_run():
    chat = load("chat")
    a = traffic.open_loop_schedule(chat, 3.0, 20, 11, 32000)
    b = traffic.open_loop_schedule(chat, 3.0, 20, 11, 32000)
    assert np.array_equal(a.prompt_len, b.prompt_len)
    assert np.array_equal(a.due_s, b.due_s)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


@pytest.mark.parametrize("rate,seconds", [(2.0, 45), (3.5, 45), (4.0, 10)])
def test_open_loop_counts_and_bounds(rate, seconds):
    chat = load("chat")
    s = traffic.open_loop_schedule(chat, rate, seconds, 5, 32000)
    assert s.measured.sum() == round(rate * seconds)
    assert (~s.measured).sum() == round(rate * chat["ramp_s"])
    assert np.all(s.due_s[s.measured] >= 0) and np.all(s.due_s[s.measured] < seconds)
    assert np.all(s.due_s[~s.measured] < 0)
    assert np.all(np.diff(s.due_s) >= 0)
    p, o = chat["prompt_tokens"], chat["output_tokens"]
    assert s.prompt_len.min() >= p["min"] and s.prompt_len.max() <= p["max"]
    assert s.output_len.min() >= o["min"] and s.output_len.max() <= o["max"]
    assert [len(t) for t in s.prompts] == s.prompt_len.tolist()
    assert all(t.dtype == np.int32 and t.max() < 32000 for t in s.prompts)


def test_chat_quantiles_follow_the_stated_distribution():
    chat = load("chat")
    lengths = traffic.quantile_lengths(chat["prompt_tokens"], 1000)
    assert abs(np.median(lengths) - chat["prompt_tokens"]["median"]) <= 2
    assert np.all(np.diff(lengths) >= 0)
    # the context stays inside the engine's 64 x 64-token block table
    assert chat["prompt_tokens"]["max"] + chat["output_tokens"]["max"] <= 4096


@pytest.mark.parametrize("seed", [0, 9, 2 ** 31 + 1])
def test_closed_loop_blocks_hold_the_same_multiset(seed):
    rag = load("batch-rag")
    s = traffic.closed_loop_schedule(rag, seed, 32000)
    size = rag["block_requests"]
    assert len(s) == size * rag["blocks"]
    first = pairs(s, np.arange(len(s)) < size)
    for k in range(1, rag["blocks"]):
        block = (np.arange(len(s)) // size) == k
        assert pairs(s, block) == first
    assert s.prompt_len.mean() == pytest.approx(2048, abs=1)
    assert s.prompt_len.max() + s.output_len.max() <= 4096


DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_closed_loop_fixed_order_seed_changes_only_the_tokens():
    rag = json.loads((DATA / "tiny-rag.json").read_text())
    assert rag["order_seed"] is not None
    a = traffic.closed_loop_schedule(rag, 1, 32000)
    b = traffic.closed_loop_schedule(rag, 2, 32000)
    assert np.array_equal(a.prompt_len, b.prompt_len)
    assert np.array_equal(a.output_len, b.output_len)
    assert not np.array_equal(a.prompts[0], b.prompts[0])
    size = rag["block_requests"]      # each block in an order of its own
    assert not np.array_equal(a.prompt_len[:size], a.prompt_len[size:2 * size])


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2 ** 31 + 11), (0, 3000000019)])
def test_closed_loop_seed_deals_the_order_of_the_same_multiset(seeds):
    """batch-rag has no ``order_seed``: two seeds are two deals of one
    multiset, block by block; one seed is one deal."""
    rag = load("batch-rag")
    assert rag.get("order_seed") is None
    a, b = (traffic.closed_loop_schedule(rag, s, 32000) for s in seeds)
    size = rag["block_requests"]
    for k in (0, 1, rag["blocks"] - 1):
        block = (np.arange(len(a)) // size) == k
        assert pairs(a, block) == pairs(b, block)
        assert not np.array_equal(a.prompt_len[block], b.prompt_len[block])
    assert not np.array_equal(a.prompt_len[:size], a.prompt_len[size:2 * size])
    again = traffic.closed_loop_schedule(rag, seeds[0], 32000)
    assert np.array_equal(a.prompt_len, again.prompt_len)
    assert np.array_equal(a.output_len, again.output_len)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, again.prompts))


@pytest.mark.parametrize("chips,gas", [(1, 4), (4, 1), (2, 2)])
def test_train_batches_same_tokens_a_step_on_any_chip_count(chips, gas):
    mix = load("pretrain-8k")
    batches = traffic.train_batches(mix, 3, 32000, chips)
    assert len(batches) == mix["distinct_batches"]
    assert all(b.shape == (gas, chips, mix["seq_len"]) for b in batches)
    assert batches[0].size == mix["rows_per_step"] * mix["seq_len"] == 32768
    again = traffic.train_batches(mix, 3, 32000, chips)
    assert all(np.array_equal(x, y) for x, y in zip(batches, again))


def test_train_batches_refuse_an_uneven_split():
    with pytest.raises(ValueError):
        traffic.train_batches(load("pretrain-8k"), 0, 32000, 3)


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        traffic.quantile_lengths({"dist": "zipf", "min": 1, "max": 2}, 4)
