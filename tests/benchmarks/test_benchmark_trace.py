"""The reduction from trace events to numbers, on hand-built event lists and
on a small recorded trace."""

import pathlib

import pytest

from benchmarks.harness import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def op(name, start, dur, device=0, program="jit_step", detail=""):
    return tr.Op(name, start, dur, device, program, detail)


def test_merge_clip_total_subtract():
    merged = tr.merge([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert merged == [(0, 3), (5, 6)]
    assert tr.total(merged) == 4
    assert tr.clip(merged, (2, 5.5)) == [(2, 3), (5, 5.5)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1)], [(0, 1)]) == []


def test_leaf_ops_drop_the_enclosing_while():
    ops = [op("while.1", 0.0, 10.0), op("fusion.1", 0.0, 4.0),
           op("fusion.2", 5.0, 5.0), op("copy.3", 11.0, 1.0)]
    assert [o.name for o in tr.leaf_ops(ops)] == ["fusion.1", "fusion.2",
                                                   "copy.3"]


def test_busy_and_idle_share_known_numbers():
    trace = tr.Trace([op("a", 0.0, 1.0), op("b", 1.5, 1.0), op("c", 3.0, 0.5),
                      op("a", 0.0, 2.0, device=1)], [], [0, 1])
    window = (0.0, 4.0)
    assert tr.busy_by_device(trace, window) == {0: 2.5, 1: 2.0}
    assert tr.busy_seconds(trace, window) == 2.25      # mean over devices
    assert tr.idle_gaps(trace, window, 0) == [(1.0, 1.5), (2.5, 3.0),
                                              (3.5, 4.0)]
    # a window that cuts an op counts only the part inside
    assert tr.busy_seconds(trace, (0.5, 2.0)) == (1.0 + 1.5) / 2


def test_a_gap_is_named_by_the_shortest_host_span_it_falls_in():
    spans = [tr.Span("serve/tick", 0.0, 10.0),
             tr.Span("serve/step_decode", 1.0, 2.0),
             tr.Span("serve/step_prefill", 5.0, 3.0)]
    trace = tr.Trace([op("a", 0.0, 1.2), op("b", 2.2, 3.0), op("c", 7.0, 2.0)],
                     spans, [0])
    gaps = tr.longest_gaps(trace, (0.0, 12.0), 0)
    assert gaps[0] == ("(no span)", pytest.approx(3.0))         # 9 .. 12
    assert gaps[1] == ("serve/step_prefill", pytest.approx(1.8))  # 5.2 .. 7
    assert gaps[2] == ("serve/step_decode", pytest.approx(1.0))   # 1.2 .. 2.2
    assert tr.span_at(spans, 9.5) == "serve/tick"


def test_op_seconds_and_top_ops_average_over_devices():
    trace = tr.Trace([op("copy.1", 0, 1.0), op("copy.1", 2, 1.0),
                      op("fusion.2", 1, 0.5), op("copy.1", 0, 3.0, device=1)],
                     [], [0, 1])
    assert tr.op_seconds(trace.ops) == {"jit_step/copy.1": 5.0,
                                        "jit_step/fusion.2": 0.5}
    assert tr.top_ops(trace, (0, 10))[0] == ("jit_step/copy.1", 2.5)


def test_exposed_collective_time():
    # the all-gather runs 2..5; a fusion on another line of the same device
    # covers 3..4, so 2 of its 3 seconds are exposed
    trace = tr.Trace([op("all-gather-start.1", 2.0, 3.0), op("fusion.1", 3.0, 1.0),
                      op("fusion.2", 6.0, 1.0)], [], [0])
    assert tr.exposed_collective_seconds(trace, (0.0, 10.0)) == 2.0
    assert tr.is_collective(op("reduce-scatter.7", 0, 1))
    assert tr.is_collective(tr.Op("ar.9", 0, 1, kind="all-reduce-done"))
    assert not tr.is_collective(op("fusion.9", 0, 1))


def test_no_xplane_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.newest_xplane(str(tmp_path))


# --- the file reader, on a recorded trace ------------------------------------
# data/train-step.xplane.pb: the first optimizer step of the traced
# mistral7b-train-8k run on a v5e (PR 23), cut to the first 2950 device events
# and 40 host events a line so that it stays under a megabyte.

@pytest.fixture(scope="module")
def recorded():
    return tr.read_xplane(str(DATA / "train-step.xplane.pb"))


def test_recorded_trace_is_small_enough_to_keep():
    assert (DATA / "train-step.xplane.pb").stat().st_size < 1 << 20


def test_reader_splits_compute_line_overlapped_line_and_host_spans(recorded):
    assert recorded.devices == [0]
    compute = [o for o in recorded.ops if not o.overlapped]
    overlapped = [o for o in recorded.ops if o.overlapped]
    assert len(compute) == 2924 and len(overlapped) == 2950
    assert {o.kind for o in overlapped} == {"copy-start", "async-start"}
    assert {o.program for o in compute} == {
        "jit__threefry_split", "jit__unstack", "jit_train_batch_step"}
    # the enclosing `while` of the accumulation loop is gone, its body stays
    assert not any(o.kind == "while" for o in compute)
    assert [s.name for s in recorded.spans][:2] == ["clock_sync", "train_batch"]


def test_recorded_step_busy_share_kernel_time_and_largest_op(recorded):
    compute = [o for o in recorded.ops if not o.overlapped]
    window = (min(o.start for o in compute), max(o.end for o in compute))
    busy = tr.busy_seconds(recorded, window)
    assert busy == pytest.approx(1.10122451, rel=1e-6)
    assert 0.98 < busy / (window[1] - window[0]) <= 1.0
    kernels = [o for o in compute if o.kind == "custom-call"
               and 'custom_call_target="tpu_custom_call"' in o.detail]
    assert len(kernels) == 31
    assert sum(o.dur for o in kernels) == pytest.approx(0.191797823, rel=1e-6)
    assert all(o.name.startswith("attn.") for o in kernels)
    name, seconds = tr.top_ops(recorded, window, 1)[0]
    assert name == "jit_train_batch_step/convolution_bitcast_fusion.6"
    assert seconds == pytest.approx(0.048934584, rel=1e-6)
    gap_name, gap = tr.longest_gaps(recorded, window, 0, 1)[0]
    assert gap == pytest.approx(0.004994669, rel=1e-5)


@pytest.mark.parametrize("text,name,kind", [
    ("%copy.168 = bf16[8,2,8,1216,64,128]{5,4,3,2,1,0:T(8,128)(2,1)} copy(bf16[8,2,8,1216,64,128]{5,4,3,2,1,0} %p.1)",
     "copy.168", "copy"),
    ("%attn.71 = (bf16[8,8192,128]{2,1,0:T(8,128)(2,1)}, bf16[8,8192,128]{2,1,0}) custom-call(bf16[32,8192,128]{2,1,0} %x), custom_call_target=\"tpu_custom_call\"",
     "attn.71", "custom-call"),
    ("%all-gather-start.3 = (f32[1024]{0}, f32[4096]{0}) all-gather-start(f32[1024]{0} %p), dimensions={0}",
     "all-gather-start.3", "all-gather-start"),
    ("jit_train_batch_step(8950522425810771810)", "jit_train_batch_step(8950522425810771810)", ""),
])
def test_parse_hlo_instruction_text(text, name, kind):
    assert tr.parse_hlo(text) == (name, kind)
