"""The device's seconds by the work the program names
(``harness/scope_readers.py`` and the thirteen metrics on it): on hand-built
operations and spans, and on a toy latent MoE server's ticks recorded on a
v5e (``record_scoped_trace.py``: every leaf scope in an operation's ``tf_op``,
the leaves tiling their parents, the two counts on the ring)."""

import gzip
import json
import pathlib
import types

import pytest

from benchmarks.harness import program_events as pe
from benchmarks.harness import scope_readers
from benchmarks.harness import trace as tr
from benchmarks.harness import xplane_names as xn
from benchmarks.harness.cells import load_module
from benchmarks.harness.observations import Observations
from deepspeed_tpu.telemetry import names as program_names

REPO = pathlib.Path(__file__).resolve().parents[2]
LOOP = 7
LEAVES = tuple(program_names.SERVED_LEAF_SCOPES)
SHARES = {      # metric -> the scopes it reads
    "moe_dispatch_share": ("moe/experts/sort", "moe/experts/gather"),
    "moe_combine_share": ("moe/experts/combine",),
    "latent_gather_share": ("attn/latent_prefill/gather",),
    "latent_up_proj_share": ("attn/latent_prefill/up_proj",),
    "lm_head_share": ("lm_head", "sample"),
    "attn_proj_share": ("attn/qkv", "attn/out", "attn/gate", "attn/qk_norm",
                        "attn/latent_q"),
    "dense_mlp_share": ("mlp", "moe/shared", "moe/router"),
    "train_lm_head_loss_share": ("lm_head_loss",),
    "train_optimizer_share": ("optimizer",),
    "train_mlp_share": ("mlp",),
}
FILLS = ("moe_gather_fill", "latent_gather_fill")
NEW = (*SHARES, "scoped_busy_share", *FILLS)

# one served chunk program and one trained step, as ``tf_op`` spells them:
# (path, microseconds)
SERVED = [
    ("jit(prefill_chunk_g)/embed/gather", 10),
    ("jit(prefill_chunk_g)/attn/latent_q/dot_general", 40),
    ("jit(prefill_chunk_g)/attn/latent_write/scatter", 5),
    ("jit(prefill_chunk_g)/attn/latent_prefill/gather/gather", 30),
    ("jit(prefill_chunk_g)/attn/latent_prefill/up_proj/sr,rhk->hsk/dot_general", 60),
    ("jit(prefill_chunk_g)/attn/latent_prefill/kernel/jit(_latent_prefill_call)"
     "/latent_prefill_attention", 110),
    ("jit(prefill_chunk_g)/attn/latent_paged/kernel/jit(_latent_paged_call)"
     "/latent_paged_attention", 20),
    ("jit(prefill_chunk_g)/attn/latent_paged/unfold/bhr,rhv->bhv/dot_general", 4),
    ("jit(prefill_chunk_g)/attn/out/thv,hvd->td/dot_general", 25),
    ("jit(prefill_chunk_g)/mlp/dot_general", 35),
    ("jit(prefill_chunk_g)/mlp_norm/reduce_sum", 3),       # no scope of ours
    ("jit(prefill_chunk_g)/moe/router/top_k", 15),
    ("jit(prefill_chunk_g)/jit(_routed_sum)/moe/experts/sort/jit(argsort)/sort", 45),
    ("jit(prefill_chunk_g)/jit(_routed_sum)/moe/experts/gather/gather", 55),
    ("jit(prefill_chunk_g)/jit(_routed_sum)/moe/experts/matmul"
     "/jit(grouped_gate_up)/grouped_matmul_gate_up", 200),
    ("jit(prefill_chunk_g)/jit(_routed_sum)/moe/experts/combine/tk,tkd->td"
     "/dot_general", 70),
    ("jit(prefill_chunk_g)/moe/shared/dot_general", 30),
    ("jit(prefill_chunk_g)/lm_head/dot_general", 50),
    ("jit(sample_tokens)/sample/argmax", 6),
    ("jit(_threefry_split)/threefry2x32", 7),              # under no name
    ("", 12),                                              # a copy between
]
TRAINED = [
    ("jit(train_batch_step)/while/body/closed_call/jvp(LlamaForCausalLM)/model"
     "/layer_0/mlp/w_up/dot_general", 100),
    ("jit(train_batch_step)/while/body/closed_call/transpose(jvp("
     "LlamaForCausalLM))/model/jvp(LlamaForCausalLM)/model/checkpoint"
     "/rematted_computation/layer_1/mlp/w_gate/dot_general", 120),
    ("jit(train_batch_step)/while/body/closed_call/transpose(jvp("
     "LlamaForCausalLM))/model/jvp(LlamaForCausalLM)/model/checkpoint/layer_1"
     "/mlp/w_down/dot_general", 230),
    ("jit(train_batch_step)/while/body/closed_call/transpose(jvp("
     "LlamaForCausalLM))/model/jvp(LlamaForCausalLM)/model/checkpoint/layer_1"
     "/mlp_norm/add_any", 9),
    ("jit(train_batch_step)/while/body/closed_call/jvp(LlamaForCausalLM)/model"
     "/layer_0/attn/pallas_call", 150),
    ("jit(train_batch_step)/while/body/closed_call/jvp(LlamaForCausalLM)"
     "/lm_head_loss/dot_general", 80),
    ("jit(train_batch_step)/while/body/closed_call/transpose(jvp("
     "LlamaForCausalLM))/lm_head_loss/dot_general", 160),
    ("jit(train_batch_step)/optimizer/mul", 90),
    ("jit(train_batch_step)/add", 11),
]


def metric(bench, name):
    return load_module(REPO, bench, "metrics", name)


@pytest.fixture
def run(monkeypatch):
    """A traced run made by hand of (path, microseconds) operations laid end
    to end on one device, with the ``ring`` as the program's spans."""
    def build(paths, ring=()):
        ops, at = [], 0.001
        for i, (path, us) in enumerate(paths):
            ops.append(xn.NamedOp(f"fusion.{i}", at, us * 1e-6, 0, "jit_x", "",
                                  "fusion", False, path and path + ":"))
            at += us * 1e-6 + 1e-6              # a microsecond idle between
        named = xn.Names(ops, [tr.Span("bench/clock_sync", 0.0, 1e-6)], [0])
        trace = tr.Trace([tr.Op(o.name, o.start + 50.0, o.dur) for o in ops],
                         [tr.Span("clock_sync", 50.0, 1e-6)], [0],
                         window=(50.0, 50.0 + at + 0.001))
        obs = Observations(kind="serve", cell=types.SimpleNamespace(
            name="c", root=REPO), devices=[], window=(49.0, 52.0), trace=trace)
        monkeypatch.setattr(xn, "of_run",      # as the real one: no trace,
                            lambda o: o.trace and named)    # no names
        monkeypatch.setattr(pe, "events", lambda: list(ring))
        return obs
    return build


def _us(paths, scopes):
    return sum(us for path, us in paths
               if scope_readers.under_any(path, scopes))


@pytest.mark.parametrize("name", sorted(SHARES))
def test_a_share_is_its_scopes_time_over_busy_time(bench, run, name):
    paths = TRAINED if name.startswith("train_") else SERVED
    busy = sum(us for _, us in paths)
    want = _us(paths, SHARES[name])
    assert want and metric(bench, name).read(run(paths)) == \
        pytest.approx(100.0 * want / busy)


def test_a_name_is_held_as_a_whole_piece_of_the_path():
    under = scope_readers.under
    assert under("jit(f)/mlp/dot_general:", "mlp")
    assert not under("jit(f)/mlp_norm/reduce_sum:", "mlp")
    assert under("jit(f)/jit(_routed_sum)/moe/experts/sort/iota:",
                 "moe/experts")
    assert not under("jit(f)/attn/latent_prefill/gather/gather:",
                     "attn/latent_paged")
    # the path's last piece is the operation's own name: the parent of PR 54
    # gathers ``pool[layer, table]`` under ``attn/latent_prefill`` with a
    # primitive called ``gather``, which is not the leaf of that name
    assert not under("jit(f)/attn/latent_prefill/gather:",
                     "attn/latent_prefill/gather")
    assert under("jit(f)/attn/latent_prefill/gather/gather:",
                 "attn/latent_prefill/gather")
    assert under("jit(sample_tokens)/sample/argmax:", "sample")
    assert not under("", "mlp")
    # the trained model's module, forward, recomputed and backward alike; the
    # served registry's names elsewhere in a path do not make it theirs
    assert _us(TRAINED, ("mlp",)) == 100 + 120 + 230
    assert _us(TRAINED, ("lm_head_loss",)) == 80 + 160
    assert _us(SERVED, ("attn/latent_prefill",)) == 30 + 60 + 110


def test_leaves_sum_to_their_parents_and_the_rest_has_no_name(bench, run):
    obs = run(SERVED)
    by_scope = scope_readers.seconds_by_scope(xn.of_run(obs).ops,
                                              scope_readers.registry())
    for parent in {leaf.rsplit("/", 1)[0] for leaf in LEAVES}:
        mine = [leaf for leaf in LEAVES if leaf.startswith(parent + "/")]
        assert sum(by_scope[leaf] for leaf in mine) == \
            pytest.approx(by_scope[parent])
    busy = sum(us for _, us in SERVED)
    assert metric(bench, "scoped_busy_share").read(obs) == \
        pytest.approx(100.0 * (busy - 3 - 7 - 12) / busy)
    # the two shares of the experts' wrapper and the matmuls are the parent's
    wrapper = metric(bench, "moe_dispatch_share").read(obs) \
        + metric(bench, "moe_combine_share").read(obs)
    assert wrapper == pytest.approx(100.0 * (45 + 55 + 70) / busy)


def test_the_named_share_is_of_the_operations_own_sum(bench, run,
                                                      monkeypatch):
    """Where a window's operations sum to more than the time in which one
    ran, a share over busy time can pass 100; ``scoped_busy_share`` is over
    the operations' own sum and cannot."""
    obs = run(SERVED)
    whole = sum(us for _, us in SERVED)
    monkeypatch.setattr(tr, "busy_seconds",
                        lambda trace, window: 0.9 * whole * 1e-6)
    assert scope_readers.scope_share(obs, scope_readers.registry()) > 100.0
    assert metric(bench, "scoped_busy_share").read(obs) == \
        pytest.approx(100.0 * (whole - 3 - 7 - 12) / whole)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scope_or_the_count_gives_none(bench, run, name):
    """The parent of PR 54 opens the parents alone and stamps neither count;
    an untraced run has no trace to read."""
    older = [(path.replace("/sort", "").replace("/gather/gather", "/gather")
              .replace("/matmul", "").replace("/combine", "")
              .replace("/up_proj", "").replace("/kernel", ""), us)
             for path, us in SERVED if "latent_paged/unfold" not in path]
    assert ("jit(prefill_chunk_g)/attn/latent_prefill/gather", 30) in older
    ring = [pe.Event(pe.PREFILL_CHUNK, 50.1, 0.004, LOOP,
                     {"tokens": 100, "bucket": 128, "start": 0,
                      "latent_panels": 1, "expert_rows": 700})]
    obs = run(older if name in ("moe_dispatch_share", "moe_combine_share",
                                "latent_gather_share", "latent_up_proj_share")
              else [("jit(f)/copy", 5)], ring)
    assert metric(bench, name).read(obs) is None
    obs.trace = None
    if name not in FILLS:                   # the fills read the ring alone
        assert metric(bench, name).read(obs) is None


def chunk(t0, start, tokens, keys=None, rows=None, gathered=None):
    args = {"tokens": tokens, "bucket": 4096, "start": start}
    if keys is not None:
        args.update(latent_panels=8, latent_keys_gathered=keys)
    if rows is not None:
        args["expert_rows"] = rows
    if gathered is not None:
        args["expert_rows_gathered"] = gathered
    return pe.Event(pe.PREFILL_CHUNK, t0, 0.004, LOOP, args)


def decode(t0, rows, gathered=None):
    args = {"batch": 4, "bucket": 8, "expert_rows": rows}
    if gathered is not None:
        args["expert_rows_gathered"] = gathered
    return pe.Event(pe.STEP_DECODE, t0, 0.004, LOOP, args)


def test_the_fills_sum_before_they_divide(bench, run):
    ring = [chunk(50.10, 0, 4096, keys=4096, rows=20000, gathered=32768),
            chunk(50.20, 4096, 2048, keys=8704, rows=9000, gathered=32768),
            decode(50.30, 60, gathered=1024),
            decode(50.40, 1000),                 # a span without the count
            chunk(50.50, 0, 100, keys=512),      # ended no prompt: no rows yet
            chunk(52.50, 0, 9, keys=512, rows=1, gathered=8),   # after
            pe.Event("serve/tick", 50.0, 1.0, LOOP, {"tick": 1})]
    obs = run(SERVED, ring)
    obs.window = (50.0, 52.0)
    assert metric(bench, "moe_gather_fill").read(obs) == pytest.approx(
        100.0 * (20000 + 9000 + 60) / (32768 + 32768 + 1024))
    assert metric(bench, "latent_gather_fill").read(obs) == pytest.approx(
        100.0 * (4096 + 6144 + 100) / (4096 + 8704 + 512))
    # not the mean of the steps' own shares
    assert metric(bench, "latent_gather_fill").read(obs) != pytest.approx(
        100.0 * (1.0 + 6144 / 8704 + 100 / 512) / 3)
    # the profiler's start and stop are left out, as for every span's count
    obs.host_exclude = [(50.19, 50.21)]
    assert metric(bench, "latent_gather_fill").read(obs) == pytest.approx(
        100.0 * (4096 + 100) / (4096 + 512))


def test_the_thirteen_entries_are_listed_where_their_readers_find_something(
        bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    served = [m for m in bench["end_to_end"]
              if m["name"] == "serve_tokens_per_s"]
    for name in NEW:
        entry = by_name[name]
        assert entry["unit"] == "%" and entry["layer"] == (
            "training engine" if name.startswith("train_") else "model step")
        assert entry["source"] == ("program_counter" if name in FILLS
                                   else "device_trace")
        assert entry["better"] == ("higher" if name in (
            *FILLS, "scoped_busy_share") else "lower")
        assert set(entry["workloads"]) <= set(cells)
        for cell in entry["workloads"]:
            if name.startswith("train_"):
                assert entry["moves"] == "train_tokens_per_s_per_chip"
            else:
                assert entry["moves"] == "serve_tokens_per_s"
                assert any(cell in m["workloads"] for m in served)
    assert "joyai-flash-serve-docqa" in by_name["latent_gather_fill"]["workloads"]
    assert "mimo-v2.5-serve-agent-long" in by_name["moe_gather_fill"]["workloads"]
    assert "granite4h-micro-serve-chat-many" in \
        by_name["scoped_busy_share"]["workloads"]
    assert "mistral7b-train-8k" in by_name["train_mlp_share"]["workloads"]


# --- the recorded ticks of a toy latent MoE server ------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(names from the file, the ring's spans) of ``record_scoped_trace.py``'s
    run on a v5e."""
    data = REPO / "tests" / "benchmarks" / "data"
    path = tmp_path_factory.mktemp("scoped") / "scoped-serve.xplane.pb"
    path.write_bytes(gzip.decompress(
        (data / "scoped-serve.xplane.pb.gz").read_bytes()))
    doc = json.loads((data / "scoped-serve.events.json").read_text())
    assert doc["device_kind"] == "TPU v5 lite"
    ring = [pe.Event(name, ts, dur, tid, args)
            for name, ph, ts, dur, tid, args in doc["events"] if ph == "X"]
    return xn.read(str(path)), ring


def test_recorded_trace_holds_every_leaf_through_the_callees(recorded):
    named, _ = recorded
    by_scope = scope_readers.seconds_by_scope(named.ops,
                                              scope_readers.registry())
    assert set(LEAVES) <= set(by_scope)
    # the Pallas calls keep their names under their leaves, through the
    # ``jit`` each is lowered under
    kernels = {}
    for o in named.ops:
        if xn.kernel_of(o):
            kernels.setdefault(xn.kernel_of(o), set()).add(
                next((leaf for leaf in LEAVES
                      if scope_readers.under(o.scope, leaf)), None))
    assert kernels["grouped_matmul_gate_up"] == {"moe/experts/matmul"}
    assert kernels["grouped_matmul"] == {"moe/experts/matmul"}
    assert kernels["latent_prefill_attention"] == {
        "attn/latent_prefill/kernel"}
    assert kernels["latent_paged_attention"] == {"attn/latent_paged/kernel"}
    # every operation under a parent is under exactly one leaf: they tile it
    for parent in {leaf.rsplit("/", 1)[0] for leaf in LEAVES}:
        mine = [leaf for leaf in LEAVES if leaf.startswith(parent + "/")]
        assert sum(by_scope[leaf] for leaf in mine) == \
            pytest.approx(by_scope[parent], rel=1e-9)
    for o in named.ops:
        assert sum(scope_readers.under(o.scope, leaf) for leaf in LEAVES) <= 1


def test_recorded_ring_holds_the_two_counts_beside_what_they_divide(recorded):
    _, ring = recorded
    chunks = [e for e in ring if e.name == pe.PREFILL_CHUNK]
    counted = [e for e in ring if e.name in (pe.PREFILL_CHUNK, pe.STEP_DECODE)
               and e.arg("expert_rows") is not None]
    assert chunks and counted
    for e in chunks:
        assert e.arg("latent_keys_gathered") >= e.arg("start") + e.arg("tokens")
        assert e.arg("latent_panels") >= 1
    for e in counted:
        assert e.arg("expert_rows_gathered") >= e.arg("expert_rows") > 0
