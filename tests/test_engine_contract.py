"""The one engine contract (``inference/v2/engine_contract.py``): the engine
provides every name of it as declared, every engine double of the tests is
one and is served through the server's one admission path, a capability an
engine lacks is refused by name, and ``serving/`` asks nothing of an engine
by ``hasattr`` / ``getattr``.
"""

import ast
import importlib
import inspect
import json
import pathlib
import time
import urllib.error
import urllib.request

import pytest

from deepspeed_tpu.inference.v2.engine_contract import (EngineCapabilityError,
                                                        ServingEngine)
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.serving import (InferenceServer, RequestState,
                                   ServingConfig, ServingFrontend)
from deepspeed_tpu.serving.bench_serve import build_tiny_server
from serving_fakes import ResidentEngine

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "deepspeed_tpu"

#: every public name the contract declares: methods, attributes with a
#: default, and the one attribute declared without (``sched_ledger``)
CONTRACT = sorted({n for n in vars(ServingEngine) if not n.startswith("_")}
                  | set(ServingEngine.__annotations__))


@pytest.fixture(scope="module")
def engine():
    return build_tiny_server(kv_offload=False, prefix_cache=False).engine


def _params(fn):
    return [(p.name, p.kind, p.default)
            for p in inspect.signature(fn).parameters.values()]


@pytest.mark.parametrize("name", CONTRACT)
def test_the_engine_provides_the_name_as_declared(engine, name):
    declared = getattr(ServingEngine, name, None)
    if callable(declared):
        own = getattr(InferenceEngineV2, name)
        assert own is not declared, f"InferenceEngineV2 inherits {name}"
        assert _params(own) == _params(declared)
    else:
        # a report or a switch: the engine keeps its own, from construction
        assert name in vars(engine)


def test_the_contract_is_about_forty_names_and_the_engine_is_one(engine):
    assert 35 <= len(CONTRACT) <= 48
    assert isinstance(engine, ServingEngine)
    assert not InferenceEngineV2.__abstractmethods__


# --- the tests' doubles -------------------------------------------------------

#: (test module, class, what it scripts): the seven doubles that are handed
#: to an ``InferenceServer``
DOUBLES = [
    ("test_serve_siege", "_FakeEngine", "serves"),
    ("test_serve_siege", "_DriftEngine", "serves"),
    ("test_serving", "_ExplodingEngine", "raises"),
    ("test_serve_idle_span", "_Engine", "serves"),
    ("test_telemetry", "_OneTokenPerStepEngine", "serves"),
    ("test_commguard", "_IdleEngine", "idles"),
    ("test_mem", "FakeEngine", "serves"),
]


@pytest.mark.parametrize("module,cls,script", DOUBLES,
                         ids=[f"{m}.{c}" for m, c, _ in DOUBLES])
def test_a_double_is_a_serving_engine_and_is_served(module, cls, script):
    double = getattr(importlib.import_module(module), cls)()
    assert isinstance(double, ServingEngine)
    server = InferenceServer(double, ServingConfig(idle_poll_s=0.001)).start()
    try:
        assert double.depth == 1          # the loop runs it a step ahead
        if script == "idles":
            time.sleep(0.05)
            assert server.health()["status"] == "serving"
            return
        req = server.submit([1, 2, 3], max_new_tokens=4)
        assert req.wait(timeout=10.0)
        # the one admission path: the engine was told the request's budget
        assert double.admitted[req.uid] == 4
        if script == "serves":
            assert req.state == RequestState.FINISHED
            assert req.tokens == [7, 7, 7, 7]
            assert req.sched_attribution["decode_tokens"] == 4
        else:
            assert req.state == RequestState.FAILED
            assert server.health()["status"] == "degraded"
    finally:
        server.stop(drain_timeout=5.0)
    assert double.depth == 0 and not server.running


def test_what_is_no_serving_engine_is_refused_at_construction():
    class Ducky:
        def step(self):
            return {}
    with pytest.raises(TypeError, match="ServingEngine"):
        InferenceServer(Ducky(), ServingConfig())


def test_a_double_that_leaves_the_loop_out_cannot_be_built():
    class NoStep(ResidentEngine):
        step = ServingEngine.step
    with pytest.raises(TypeError, match="abstract"):
        NoStep()


# --- capabilities -------------------------------------------------------------

def _at_construction(**config):
    return lambda tmp_path: InferenceServer(ResidentEngine(),
                                            ServingConfig(**config))


def _at_the_call(method):
    def call(tmp_path):
        server = InferenceServer(ResidentEngine(), ServingConfig())
        getattr(server, method)(str(tmp_path / "handoff.npz"))
    return call


CAPABILITIES = {
    "host-tier": (_at_construction(kv_offload_enabled=True),
                  "host KV offload tier"),
    "prefix-cache": (_at_construction(prefix_cache_enabled=True),
                     "prefix cache"),
    "chunk-cap": (_at_construction(scheduler={"prefill_chunk_tokens": 32}),
                  "prefill chunk cap"),
    "handoff-export": (_at_the_call("export_prefix_handoff"),
                       "prefix handoff"),
    "handoff-import": (_at_the_call("adopt_prefix_handoff"),
                       "prefix handoff"),
}


@pytest.mark.parametrize("capability", sorted(CAPABILITIES))
def test_a_capability_the_engine_lacks_is_refused_by_name(capability,
                                                          tmp_path):
    ask, named = CAPABILITIES[capability]
    with pytest.raises(EngineCapabilityError,
                       match=f"ResidentEngine does not provide .*{named}"):
        ask(tmp_path)


def test_the_front_end_answers_400_where_the_engine_cannot_adopt(tmp_path):
    server = InferenceServer(ResidentEngine(),
                             ServingConfig(idle_poll_s=0.001)).start()
    frontend = ServingFrontend(server).start()
    try:
        request = urllib.request.Request(
            f"{frontend.url}/admin/adopt", method="POST",
            data=json.dumps({"handoff_path": str(tmp_path / "h")}).encode())
        with pytest.raises(urllib.error.HTTPError) as refused:
            urllib.request.urlopen(request, timeout=10)
        assert refused.value.code == 400
        assert "prefix handoff" in refused.value.read().decode()
    finally:
        frontend.stop()
        server.stop(drain_timeout=2.0)


@pytest.mark.parametrize("name", [
    n for n in CONTRACT
    if callable(getattr(ServingEngine, n, None))
    and n not in ServingEngine.__abstractmethods__])
def test_every_capability_method_raises_by_its_own_name(name):
    double = ResidentEngine()
    fn = getattr(double, name)
    args = ["x"] * sum(1 for p in inspect.signature(fn).parameters.values()
                       if p.default is p.empty)
    with pytest.raises(EngineCapabilityError) as refused:
        fn(*args)
    assert "ResidentEngine does not provide" in str(refused.value)
    assert name in str(refused.value) or name == "require_one_page_kind"


# --- what ``serving/`` may ask of an engine ------------------------------------

SERVING = sorted(p.name for p in (PACKAGE / "serving").glob("*.py"))


def _probes(path):
    """``hasattr(x, ...)`` / ``getattr(x, ...)`` calls in ``path`` whose
    ``x`` names an engine."""
    tree = ast.parse(path.read_text())
    return [f"{path.name}:{node.lineno}" for node in ast.walk(tree)
            if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id in ("hasattr", "getattr") and node.args
            and "engine" in ast.unparse(node.args[0])]


@pytest.mark.parametrize("name", SERVING)
def test_serving_probes_no_engine(name):
    assert _probes(PACKAGE / "serving" / name) == []


def test_the_server_reaches_past_the_engine_nowhere_and_admits_in_one_place():
    source = (PACKAGE / "serving" / "server.py").read_text()
    assert "engine.state" not in source and "engine.kv." not in source
    assert "_pipelined" not in source
    assert source.count("engine.admit(") == 1


def test_the_engine_side_imports_nothing_of_serving():
    importers = []
    for path in sorted((PACKAGE / "inference").rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            names = [node.module or ""] if isinstance(node, ast.ImportFrom) \
                else [a.name for a in node.names] \
                if isinstance(node, ast.Import) else []
            if any(n.startswith("deepspeed_tpu.serving") for n in names):
                importers.append(f"{path.name}:{node.lineno}")
    assert importers == []
