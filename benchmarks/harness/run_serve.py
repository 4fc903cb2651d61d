"""A serving cell: ``InferenceServer.submit`` under an open or a closed loop,
the way a client calls it. Set-up builds the weights on the device from the
seed, checks the engine's tokens against the plain reference's logits, warms
every step program the traffic's bounds can reach (by enumeration, through the
engine's public calls), starts the server and ramps the load; the window then
measures requests that were due inside it.
"""

import dataclasses
import gc
import itertools
import json
import math
import queue
import threading
import time
import typing
from typing import List, Optional

import numpy as np

from benchmarks.harness import cells, device, profile, readers, traffic
from benchmarks.harness.observations import Observations


@dataclasses.dataclass
class Record:
    """One request as its client saw it (monotonic seconds)."""
    index: int
    measured: bool
    prompt_len: int
    asked: int
    due: float = 0.0
    sent: Optional[float] = None
    refused: Optional[str] = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    state: Optional[str] = None
    ended: Optional[float] = None

    @property
    def ok(self) -> bool:
        return self.state == "finished" and len(self.stamps) == self.asked


# --- building the server ----------------------------------------------------

def _dataclass_from(cls, group: dict, where: str):
    """``cls`` with the fields ``group`` names: a field that is a dataclass
    takes an object of its fields, a field declared a tuple takes a JSON
    list, anything else the value as it is."""
    fields = {f.name for f in dataclasses.fields(cls)}
    if not set(group) <= fields:
        raise cells.CellError(f"{where} has {sorted(set(group) - fields)}, "
                              f"which {cls.__name__} lacks; it has "
                              f"{sorted(fields)}")
    hints = typing.get_type_hints(cls)
    given = {}
    for key, value in group.items():
        hint = hints[key]
        if dataclasses.is_dataclass(hint):
            if not isinstance(value, dict):
                raise cells.CellError(f"{where}.{key} is a group of "
                                      f"{hint.__name__}'s fields, not {value!r}")
            value = _dataclass_from(hint, value, f"{where}.{key}")
        elif typing.get_origin(hint) is tuple:
            if not isinstance(value, list):
                raise cells.CellError(f"{where}.{key} is a list, not {value!r}")
            value = tuple(value)
        given[key] = value
    return cls(**given)


def engine_config(group: dict):
    """A configuration file's ``serve.engine`` as the engine's whole
    ``V2EngineConfig``: what the group leaves out stays at its default,
    nested groups (``scheduler``, ``sampling``) become their dataclasses, and
    a key the engine lacks is a ``CellError`` that names it."""
    from deepspeed_tpu.inference.v2.engine_v2 import V2EngineConfig
    return _dataclass_from(V2EngineConfig, group, "serve.engine")


def build_server(cell: cells.Cell, bench: dict, seed: int):
    """(server, family module, program config): weights made on the device in
    one jitted call from the seed, in the type they are served in; every
    serving and engine option at its default except what the configuration
    file's ``serve.engine`` group sets (the KV pool's size, and where a cell
    needs them the bucket ladders and the scheduler's budgets)."""
    import jax

    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.runtime.precision import cast_to_compute
    from deepspeed_tpu.serving.server import InferenceServer, ServingConfig

    hf = cell.config
    family = cells.load_module(cell.root, bench, "families", hf["model_type"])
    cfg, model = family.build(hf, "serve", hf["serve"])
    dtype = family.compute_dtype(cfg)
    example = {"input_ids": np.zeros((1, 8), np.int32)}
    params = jax.jit(lambda key: cast_to_compute(
        model.init(key, example)["params"], dtype))(
            jax.random.PRNGKey(device.device_seed(seed)))
    engine = InferenceEngineV2(params, cfg,
                               engine_config(hf["serve"]["engine"]))
    return InferenceServer(engine, ServingConfig()), family, cfg


# --- correctness ------------------------------------------------------------

def _decode_alone_and_in_a_wave(engine, prompts, new_tokens):
    """The engine's greedy tokens, ``new_tokens`` + 1 a prompt: for
    ``prompts[0]`` alone, then for every prompt admitted together (other
    batch and chunk buckets). Where the step's token budget is smaller than
    the wave's prompts their first tokens come over several steps, and a
    sequence that is ahead decodes on until the last has its tokens."""
    def run(uids, batch):
        got = {u: [] for u in uids}
        out = engine.put(uids, batch)
        while True:
            for u in uids:
                if u in out and len(got[u]) <= new_tokens:
                    got[u].append(out[u])
            if all(len(tokens) > new_tokens for tokens in got.values()):
                break
            out = engine.step()
        for u in uids:
            engine.flush(u)
        return [got[u] for u in uids]
    solo = run([900001], prompts[:1])[0]
    wave = run([900002 + i for i in range(len(prompts))], prompts)
    return solo, wave


def check_against_reference(engine, family, reference, hf, seed, say) -> bool:
    """Seeded prompts of several lengths prefilled and ``new_tokens`` tokens
    decoded through the paged cache: the first prompt alone, then all of them
    in one wave. The engine hands out tokens, not logits, so the comparison
    is teacher-forced: the reference's full float32 forward over prompt +
    generated tokens gives the logits at every generated position, and each
    token the engine chose must be within ``logit_margin_tol`` of the
    reference's largest logit there. With random weights the top logits are
    near ties (gaps from 0.01 up), so an error larger than the tolerance
    anywhere in the computation flips some of the positions checked to a
    token that fails; the tolerance is sized for bfloat16 against float32."""
    import jax.numpy as jnp
    rng = np.random.default_rng([int(seed), 4])
    vocab = int(hf["vocab_size"])
    sizes = hf["serve"].get("check", {})
    prompts = [rng.integers(0, vocab, n).tolist()
               for n in [sizes.get("prompt", 256)]
               + list(sizes.get("others", (100, 300, 700)))]
    solo, wave = _decode_alone_and_in_a_wave(engine, prompts,
                                             sizes.get("new_tokens", 48))
    weights = family.reference_weights(engine.params)
    tol = float(hf["serve"]["logit_margin_tol"])
    worst, positions, gap_lo = 0.0, 0, float("inf")
    for prompt, tokens in [(prompts[0], solo)] + list(zip(prompts, wave)):
        seq = prompt + tokens[:-1]
        logits = reference.logits(weights, hf, seq)[len(prompt) - 1:]
        top2 = jnp.sort(logits, axis=-1)[:, -2:]
        chosen = logits[jnp.arange(len(tokens)), jnp.asarray(tokens)]
        worst = max(worst, float(jnp.max(top2[:, 1] - chosen)))
        gap_lo = min(gap_lo, float(jnp.min(top2[:, 1] - top2[:, 0])))
        positions += len(tokens)
    ok = worst <= tol
    say(f"reference check: {positions} positions of {len(prompts) + 1} "
        f"sequences; largest (top logit - logit of the engine's token) "
        f"{worst:.4f}, tolerance {tol}; the reference's smallest top-1 to "
        f"top-2 gap {gap_lo:.4f}; wave_matches_solo={wave[0] == solo}: "
        f"{'agree' if ok else 'DIFFER'}")
    return ok


# --- warm-up by enumeration -------------------------------------------------

def reachable_shapes(ecfg, mix: dict):
    """The (prefill bucket, context bucket) and (decode batch bucket, context
    bucket) step programs that traffic inside ``mix``'s bounds can reach with
    the engine's bucket ladders: a chunk of ``take`` tokens ending at ``end``
    can have any 1 <= take <= end <= longest prompt (SplitFuse cuts prompts
    wherever the step's budget ends); a decode batch holds 1 ..
    ``max_concurrency`` sequences whose longest context runs from the
    shortest prompt + 1 to the longest prompt + longest output."""
    # the engine's own snapping, so that the enumeration follows the ladder
    from deepspeed_tpu.inference.v2.scheduler import snap_bucket
    bs = ecfg.kv_block_size
    pb, cb, db = (ecfg.scheduler.prefill_buckets, ecfg.ctx_block_buckets,
                  ecfg.decode_batch_buckets)
    pmin, pmax = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    omax = mix["output_tokens"]["max"]

    def ctxb(tokens):
        return snap_bucket(math.ceil(tokens / bs), cb)

    prefill, lo = [], 0
    for b in pb:
        if lo + 1 > pmax:
            break
        prefill += [(b, m) for m in cb if ctxb(lo + 1) <= m <= ctxb(pmax)]
        lo = b
    most = min(int(mix["max_concurrency"]), ecfg.scheduler.max_decode_batch)
    decode = [(d, m) for m in cb if ctxb(pmin + 1) <= m <= ctxb(pmax + omax)
              for d in db if d <= snap_bucket(most, db)]
    return prefill, decode


def warm_engine(engine, mix: dict, say) -> int:
    """Run every reachable step program once, through ``admit`` / ``put`` /
    ``step`` / ``flush`` alone, so that whatever the program does inside a
    step (donation, fusing, renaming) the warm-up still fits it."""
    ecfg = engine.config
    bs = ecfg.kv_block_size
    cb = ecfg.ctx_block_buckets
    pmin, pmax = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    prefill, decode = reachable_shapes(ecfg, mix)
    uid = itertools.count(800001)

    def prefill_all(u):
        while engine.state.get(u).in_prefill:
            engine.step()

    for b, m in prefill:
        take = min(b, pmax)
        end = max(min(m * bs, pmax), take)
        u = next(uid)
        if end > take:
            engine.admit(u, [1] * (end - take))
            prefill_all(u)
            engine.put([u], [[1] * take])      # a continuation: one more chunk
        else:
            engine.admit(u, [1] * take)
        prefill_all(u)
        engine.flush(u)

    for m in sorted({m for _, m in decode}):
        below = max((c for c in cb if c < m), default=0)
        long_uid = next(uid)
        engine.admit(long_uid, [1] * min(max(pmin, below * bs + 1), pmax))
        prefill_all(long_uid)
        live = [long_uid]
        for d in sorted({d for d, mm in decode if mm == m}):
            fresh = [next(uid) for _ in range(d - len(live))]
            for u in fresh:
                engine.admit(u, [1] * 8)
            live += fresh
            if fresh:
                engine.step()      # their prefills (and a warm decode)
            engine.step()          # the decode batch of d sequences
        for u in live:
            engine.flush(u)
    say(f"warmed {len(prefill)} prefill and {len(decode)} decode step "
        f"programs by enumeration")
    return len(prefill) + len(decode)


# --- the load ---------------------------------------------------------------

class Load:
    """Sends the schedule and stamps every token as it arrives. One sender
    thread (open loop) or ``clients`` threads (closed loop) that only sleep
    and call ``submit``; one short-lived thread per in-flight request reads
    its stream."""

    def __init__(self, server, schedule: traffic.Schedule, token_wait_s: float,
                 clients: int = 0):
        from deepspeed_tpu.serving.server import (BackpressureError,
                                                  ServerClosedError)
        self._refusals = (BackpressureError, ServerClosedError, ValueError)
        self.server = server
        self.schedule = schedule
        self.prompts = [p.tolist() for p in schedule.prompts]
        self.records = [Record(i, bool(schedule.measured[i]),
                               int(schedule.prompt_len[i]),
                               int(schedule.output_len[i]))
                        for i in range(len(schedule))]
        self.token_wait_s = token_wait_s
        self.clients = clients          # 0: open loop, one sender
        self._stop = threading.Event()
        self._next = itertools.count()
        self._lock = threading.Lock()
        self._live = {}
        self.threads: List[threading.Thread] = []

    def _submit(self, rec: Record):
        rec.sent = time.monotonic()
        try:
            req = self.server.submit(self.prompts[rec.index],
                                     max_new_tokens=rec.asked)
        except self._refusals as e:
            rec.refused = f"{type(e).__name__}: {str(e)[:60]}"
            rec.ended = time.monotonic()
            return None
        with self._lock:
            self._live[rec.index] = req
        return req

    def _collect(self, rec: Record, req) -> None:
        try:
            for _ in req.stream(timeout=self.token_wait_s):
                rec.stamps.append(time.monotonic())
        except queue.Empty:
            req.cancel()
        rec.ended = time.monotonic()
        rec.state = req.state.value
        with self._lock:
            self._live.pop(rec.index, None)

    def _send_open(self, t_open: float) -> None:
        for rec in self.records:
            rec.due = t_open + float(self.schedule.due_s[rec.index])
            wait = rec.due - time.monotonic()
            if wait > 0 and self._stop.wait(wait):
                return
            req = self._submit(rec)
            if req is not None:
                t = threading.Thread(target=self._collect, args=(rec, req),
                                     daemon=True)
                self.threads.append(t)
                t.start()

    def _client(self) -> None:
        while not self._stop.is_set():
            i = next(self._next)
            if i >= len(self.records):
                return
            rec = self.records[i]
            rec.due = time.monotonic()
            req = self._submit(rec)
            if req is None:
                self._stop.wait(0.05)
            else:
                self._collect(rec, req)

    def start(self, t_open: float) -> None:
        """Open loop: the schedule's offsets are laid from ``t_open`` (ramp
        requests before it). Closed loop: the clients start now."""
        if not self.clients:
            senders = [threading.Thread(target=self._send_open,
                                        args=(t_open,), daemon=True)]
        else:
            senders = [threading.Thread(target=self._client, daemon=True)
                       for _ in range(self.clients)]
        self.threads += senders
        for t in senders:
            t.start()

    def stop_sending(self) -> None:
        self._stop.set()

    def cancel_live(self) -> None:
        with self._lock:
            live = list(self._live.values())
        for req in live:
            req.cancel()

    def join(self, deadline: float) -> bool:
        for t in list(self.threads):
            t.join(timeout=max(deadline - time.monotonic(), 0.0))
        return not any(t.is_alive() for t in self.threads)


def _watch_ticks(engine, ticks: list) -> None:
    """Traced runs only: a span of the benchmark's own around every engine
    tick, the tick's counters, and the context of every sequence it decodes
    (what the paged kernel has to read)."""
    import jax
    inner = engine.step
    most = engine.config.scheduler.max_decode_batch

    def step():
        contexts = [s.total_tokens for s in engine.state.decoding()[:most]]
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench/engine_step"):
            out = inner()
        ticks.append((t0, time.monotonic(), dict(engine.last_step_counters),
                      contexts))
        return out
    engine.step = step


def prepare(cell: cells.Cell, bench: dict, seed: int, trace: bool, say):
    """Set-up up to a started server: weights, the reference check, the
    warm-up, and for a traced run the program's tracer and the tick watch.
    Returns (server, whether the reference check held, the list ticks go to)."""
    hf, mix = cell.config, cell.traffic
    server, family, _ = build_server(cell, bench, seed)
    engine = server.engine
    say(f"server built: {hf['num_hidden_layers']} layers, "
        f"{engine.config.kv_num_blocks} KV blocks of "
        f"{engine.config.kv_block_size} tokens")
    reference = cells.load_module(cell.root, bench, "reference",
                                  hf["model_type"])
    logits_ok = check_against_reference(engine, family, reference, hf, seed,
                                        say)
    warm_engine(engine, mix, say)
    ticks: list = []
    if trace:
        from deepspeed_tpu.telemetry.tracer import get_tracer
        get_tracer().configure(enabled=True, capacity=1 << 20)
        _watch_ticks(engine, ticks)
    server.start()
    gc.collect()
    gc.freeze()
    return server, logits_ok, ticks


def measure(cell: cells.Cell, server, schedule: traffic.Schedule,
            seconds: float, trace: bool, say) -> Observations:
    """Ramp, then one window of ``seconds`` under ``schedule``, then a bounded
    drain. Leaves the server running and idle."""
    from deepspeed_tpu.telemetry.compiles import compiles_total

    mix = cell.traffic
    open_loop = mix["loop"] == "open"
    load = Load(server, schedule, token_wait_s=float(mix["drain_s"]),
                clients=0 if open_loop else int(mix["clients"]))
    obs = Observations(kind="serve", cell=cell, devices=[])
    prof = None
    t_open = time.monotonic() + float(mix["ramp_s"]) + (0.2 if open_loop else 0.0)
    load.start(t_open)
    time.sleep(max(t_open - time.monotonic(), 0.0))
    obs.setup_end = t_open
    t_close = t_open + seconds
    compiles0 = compiles_total()
    m = server.metrics
    edges0 = (m.ladder_transitions, m.requests_shed, m.requests_rejected)
    queue0 = m.queue_depth
    if trace:
        traced_s = float(mix.get("traced_s", 3.0))
        time.sleep(max(t_open + 0.45 * seconds - time.monotonic(), 0.0))
        prof = profile.Profiled(str(cell.root / ".bench_trace" / cell.name))
        prof.start()
        time.sleep(traced_s)
        prof.stop()
        obs.host_exclude = [(prof.stall[0] - 0.25, prof.stall[1] + 1.0)]
    time.sleep(max(t_close - time.monotonic(), 0.0))
    queue1 = m.queue_depth
    if open_loop:
        drained = load.join(t_close + float(mix["drain_s"]))
    else:
        load.stop_sending()
        load.cancel_live()
        drained = load.join(time.monotonic() + float(mix["drain_s"]))
    t_end = time.monotonic()
    load.stop_sending()
    load.cancel_live()
    load.join(time.monotonic() + 10.0)
    compiles = compiles_total() - compiles0
    edges = (m.ladder_transitions - edges0[0], m.requests_shed - edges0[1],
             m.requests_rejected - edges0[2])
    faults = server.health().get("step_faults", 0)

    recs = load.records
    if open_loop:
        counted = [r for r in recs if r.measured]
    else:
        # a closed loop's requests are counted where they ended; the ones the
        # window's close cut short were never due to end inside it
        counted = [r for r in recs if r.ended is not None
                   and t_open <= r.ended <= t_close and r.state != "cancelled"]
    obs.requests = recs
    obs.window = (t_open, t_close)
    obs.attempted = len(counted)
    obs.failed = sum(not r.ok for r in counted)
    obs.counters = {
        "compiles_in_window": compiles, "ladder_transitions": edges[0],
        "requests_shed": edges[1], "requests_rejected": edges[2],
        "queue_depth_at_open": queue0, "queue_depth_at_close": queue1,
        "step_faults": faults, "run_end": t_end, "drained": drained}
    say(f"window closed: {obs.attempted} requests counted, {obs.failed} "
        f"failed; compiles in the window: {compiles}; ladder transitions "
        f"{edges[0]}, shed {edges[1]}, refused {edges[2]}; queue "
        f"{queue0} -> {queue1}; step faults {faults}; drained={drained}")
    if prof is not None:
        from benchmarks.harness.spans import program_spans
        obs.spans = program_spans()
        obs.trace = prof.load(obs.spans)
    return obs


def make_schedule(cell: cells.Cell, seconds: float, seed: int,
                  rate_rps=None) -> traffic.Schedule:
    mix = cell.traffic
    vocab = int(cell.config["vocab_size"])
    if mix["loop"] == "open":
        rate = float(mix["rate_rps"] if rate_rps is None else rate_rps)
        return traffic.open_loop_schedule(mix, rate, seconds, seed, vocab)
    return traffic.closed_loop_schedule(mix, seed, vocab)


def run(cell: cells.Cell, bench: dict, devices: list, seed: int,
        seconds: float, trace: bool, say) -> Observations:
    server, logits_ok, ticks = prepare(cell, bench, seed, trace, say)
    try:
        obs = measure(cell, server, make_schedule(cell, seconds, seed),
                      seconds, trace, say)
    finally:
        server.stop(drain_timeout=10.0)
    obs.devices = devices
    obs.ticks = ticks
    if cell.traffic["loop"] == "open":
        say("latencies (ms) " + json.dumps(readers.latency_summary(obs)))
    obs.correct = bool(logits_ok and obs.counters["compiles_in_window"] == 0
                       and obs.attempted > 0
                       and obs.counters["step_faults"] == 0)
    return obs
