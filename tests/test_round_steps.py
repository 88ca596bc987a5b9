"""Round steps: the named scopes of the round body, the device-op → step
map the engines publish, the set-up counters, and spans on the
profiler's clock (docs/observability.md, "Round steps").

The engines compile at toy size here; the map is read from each
program's own optimized HLO (``hlo_steps``), and ``op_steps`` — the
merge over every published program — is read with a registry of the
test's own, since programs of other tests may share instruction names.
"""

import collections
import contextlib
import gc
import glob
import os
import re
import warnings
import weakref

import jax
import numpy as np
import pytest

from repro.core import batched, boost_attempt, sharded_batched, weak
from repro.core.types import BoostConfig
from repro.obs import metrics as M
from repro.obs import trace as T

B, K, MLOC, F = 2, 4, 64, 4
# instructions that run no device op of their own
STRUCTURAL = {"parameter", "get-tuple-element", "tuple", "constant",
              "bitcast"}


def _tree():
    cls = weak.make_class("tree", num_features=F, tree_depth=2,
                          tree_bins=8, tree_comm_mode="histogram")
    cfg = BoostConfig(k=K, coreset_size=16, domain_size=1 << 20,
                      opt_budget=4, deterministic_coreset=False)
    x = (np.random.default_rng(0).integers(0, 8, size=(B, K, MLOC, F))
         / 8).astype(np.float32)
    return cls, cfg, x, np.where(x[..., 0] > 0.5, 1, -1).astype(np.int8)


def _thresholds():
    cls = weak.make_class("thresholds", n=1 << 10)
    cfg = BoostConfig(k=K, coreset_size=16, domain_size=1 << 10,
                      opt_budget=4, deterministic_coreset=True)
    x = np.random.default_rng(0).integers(
        0, 1 << 10, size=(B, K, MLOC)).astype(np.int32)
    return cls, cfg, x, np.where(x > 500, 1, -1).astype(np.int8)


PROBLEMS = {"tree": _tree, "thresholds": _thresholds}


def _lower(problem, engine="batched"):
    cls, cfg, x, y = PROBLEMS[problem]()
    keys = jax.random.split(jax.random.key(1), B)
    alive = np.ones(y.shape, bool)
    if engine == "sharded":
        return sharded_batched.lower_classify_sharded(
            x, y, alive, keys, cfg, cls,
            sharded_batched.make_players_mesh(K))
    return batched.lower_classify(x, y, alive, keys, cfg, cls)


@pytest.fixture(scope="module")
def hlo():
    """The optimized HLO text of each toy engine."""
    return {(p, e): _lower(p, e).as_text()
            for p in PROBLEMS for e in ("batched", "sharded")}


@pytest.fixture
def fresh_traces():
    """Engines traced anew in the test, and again after it: a test that
    swaps a function the trace reads leaves no trace behind."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture
def own_registry(monkeypatch):
    """A registry of published programs of this test alone."""
    monkeypatch.setattr(T, "_PUBLISHED", [])
    monkeypatch.setattr(T, "_DROPPED", collections.deque(maxlen=16))


def _while_body_ops(text):
    """Instructions that run a device op inside any while loop's body."""
    comps = T.parse_hlo(text)
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", text))
    return [name for c in bodies for name, op, *_ in comps[c]
            if op not in STRUCTURAL]


@pytest.mark.parametrize("engine", ["batched", "sharded"])
@pytest.mark.parametrize("problem,missing", [
    ("tree", {"sort_order"}),   # the sampled draw never reads the order
    ("thresholds", set()),      # deterministic coresets sort each slice
])
def test_compiled_engine_maps_its_steps(hlo, problem, missing, engine):
    text = hlo[problem, engine]
    steps = T.hlo_steps(text)
    assert set(T.ROUND_STEPS) - set(steps.values()) == missing
    body = _while_body_ops(text)
    unscoped = [n for n in body if steps[n] is None]
    assert body and len(unscoped) <= 0.10 * len(body), unscoped


def _no_metadata(text):
    """HLO text without metadata or the source-file tables."""
    keep = text.split("\n\nFileNames", 1)[0]
    return re.sub(r",? ?metadata=\{[^}]*\}", "", keep)


def test_named_steps_add_no_instruction(hlo, fresh_traces, monkeypatch):
    """The scopes change metadata only: with every step's scope a
    no-op, the compiled tree engine is the same program."""
    plain = lambda step: contextlib.nullcontext()   # noqa: E731
    for mod in (boost_attempt, batched, sharded_batched):
        monkeypatch.setattr(mod, "step_scope", plain)
    bare = _lower("tree").as_text()
    assert set(T.hlo_steps(bare).values()) == {None}
    assert _no_metadata(bare) == _no_metadata(hlo["tree", "batched"])


def _scoped(compiled):
    return {n: s for n, s in T.hlo_steps(compiled.as_text()).items()
            if s is not None}


def test_two_compiles_of_one_signature_give_one_map(fresh_traces,
                                                    own_registry):
    first = _lower("tree")
    jax.clear_caches()
    second = _lower("tree")
    assert len(T._PUBLISHED) == 2
    steps = T.op_steps()
    assert steps and steps == _scoped(first) == _scoped(second)
    del first, second


def test_published_program_is_held_weakly(own_registry):
    compiled = _lower("tree")
    steps = _scoped(compiled)
    gone = weakref.ref(compiled)
    assert len(T._PUBLISHED) == 1
    del compiled
    gc.collect()
    assert gone() is None
    assert len(T._PUBLISHED) == 0
    assert not any(isinstance(o, T._Program) and o.program is not None
                   for o in gc.get_objects())
    # the map was taken as the program was dropped
    assert T.op_steps() == steps


def test_hlo_steps_rules():
    text = """HloModule m

%fused (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %a = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/vmap(predict)/add"}
  ROOT %b = f32[4]{0} multiply(%a, %a), metadata={op_name="jit(f)/predict/vmap(mw_update)/mul"}
}

ENTRY %main (x: f32[4], z: f32[4]) -> (f32[4], f32[4]) {
  %x = f32[4]{0} parameter(0)
  %z = f32[4]{0} parameter(1)
  %c = f32[4]{0} copy(%x)
  %fusion.1 = f32[4]{0} fusion(%c), kind=kLoop, calls=%fused
  %fusion.2 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/center_erm/coreset_draw/x"}
  %d = f32[4]{0} copy(%fusion.2)
  %e = f32[4]{0} negate(%z), metadata={op_name="jit(f)/neg"}
  ROOT %t = (f32[4]{0}, f32[4]{0}) tuple(%d, %e)
}
"""
    steps = T.hlo_steps(text)
    assert steps["a"] == "predict"             # inside a transform
    assert steps["b"] == "mw_update"           # the innermost step
    assert steps["fusion.2"] == "coreset_draw"
    # a fusion with no op_name: the majority of its fused computation
    # (a tie, to the earlier step)
    assert steps["fusion.1"] == "predict"
    assert steps["c"] == "predict"             # its users
    assert steps["d"] == "coreset_draw"        # its operand
    assert steps["e"] is None                  # no step anywhere
    assert steps["z"] is None


def test_op_steps_leaves_clashing_names_unscoped(own_registry):
    T._DROPPED.extend([{"fusion.1": "predict", "fusion.2": "predict",
                        "copy.3": None},
                       {"fusion.1": "predict", "fusion.2": "center_erm"}])
    assert T.op_steps() == {"fusion.1": "predict"}


def test_lower_classify_counts_its_set_up(fresh_traces):
    reg = M.default_registry()
    before = {n: reg.counter(n).value for n in (
        "compile.lower_s", "compile.backend_s", "compile.programs")}
    _lower("thresholds")
    assert reg.counter("compile.programs").value == \
        before["compile.programs"] + 1
    assert reg.counter("compile.lower_s").value > before["compile.lower_s"]
    assert reg.counter("compile.backend_s").value > \
        before["compile.backend_s"]


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    with warnings.catch_warnings():
        # jaxlib's event-stats type warns that it has no __module__
        warnings.filterwarnings("ignore", "builtin type .* __module__",
                                DeprecationWarning)
        data = ProfileData.from_file(path)
        return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                for plane in data.planes if plane.name.startswith("/host")
                for line in plane.lines for e in line.events]


@pytest.mark.parametrize("recorder", [False, True])
def test_spans_land_on_the_profiler_clock(tmp_path, recorder):
    """Under a capture, ``finalize``'s spans are on the host plane of
    the capture, inside the caller's ``window``, with a recorder or
    without; outside a capture, and with no recorder, they are the
    shared null span."""
    cls, cfg, x, y = _thresholds()
    keys = jax.random.split(jax.random.key(1), B)
    state = batched.run_rounds(batched.init_state(x, y, keys, cfg), x, y,
                               cfg, cls)
    alive = np.ones(y.shape, bool)
    assert not T.enabled()
    assert T.span("finalize.state") is T.span("other")
    rec = T.TraceRecorder() if recorder else None
    with contextlib.ExitStack() as stack:
        if recorder:
            stack.enter_context(T.recording(rec))
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation("window"):
                batched.finalize(state, x, y, alive, cfg, cls)
    assert T.span("finalize.state") is T.span("other")
    if recorder:
        assert [e["name"] for e in rec.events] == [
            "finalize.state", "finalize.inputs"]
    events = _host_events(str(tmp_path))
    (_, w0, w1), = [e for e in events if e[0] == "window"]
    for name in ("finalize.state", "finalize.inputs"):
        (_, s0, s1), = [e for e in events if e[0] == name]
        assert w0 <= s0 <= s1 <= w1, name
