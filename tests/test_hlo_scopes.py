"""Device operations by ``jax.named_scope`` from compiled HLO text
(``analysis.hlo.scope_stats`` / ``scope_map`` / ``op_scopes``) and their
place in the cost ledger (``obs.perf``): the counting rule on hand-written
text and on a toy ``jit(vmap(scan))``, every layer of the tiny
``chunk_step(learn=True)``, the map from each operation to its scope path
that the same walk keeps, and the capture's second compile when a cached
executable carries the names of an older source."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from gsc_tpu.analysis.hlo import (instruction_head, op_scopes, scope_map,
                                  scope_stats)
from gsc_tpu.obs import ListSink, MetricsHub
from gsc_tpu.obs import perf as perf_mod
from gsc_tpu.obs.trace import DEVICE_SCOPES, TORSO_SCOPES
from gsc_tpu.parallel import ParallelDDPG

from tests.test_agent import make_stack

pytestmark = pytest.mark.perf_obs

SCOPES = ("layer_a", "layer_b", "layer_c")

# one loop the program wrote (its body under layer_b) with compiler moves
# that carry no name, one loop the compiler made inside layer_c, a fused
# computation and a reducer that are no operations of their own
TEXT = """HloModule jit_f

%fused (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %inner = f32[8]{0} sine(%p), metadata={op_name="jit(f)/layer_a/sin"}
}

%adder (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %sum = f32[] add(%x, %y), metadata={op_name="jit(f)/layer_a/add"}
}

%made_body (q: (s32[], f32[8])) -> (s32[], f32[8]) {
  %q = (s32[], f32[8]{0}) parameter(0)
  %q0 = s32[] get-tuple-element(%q), index=0
  %q1 = f32[8]{0} get-tuple-element(%q), index=1
  %bump = s32[] add(%q0, %q0)
  %move = f32[8]{0} copy(%q1)
  ROOT %qt = (s32[], f32[8]{0}) tuple(%bump, %move)
}

%made_cond (q: (s32[], f32[8])) -> pred[] {
  %q = (s32[], f32[8]{0}) parameter(0)
  %q0 = s32[] get-tuple-element(%q), index=0
  ROOT %lt = pred[] compare(%q0, %q0), direction=LT
}

%body (c: (s32[], f32[8])) -> (s32[], f32[8]) {
  %c = (s32[], f32[8]{0}) parameter(0)
  %c0 = s32[] get-tuple-element(%c), index=0
  %c1 = f32[8]{0} get-tuple-element(%c), index=1
  %start = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%c1)
  %done = f32[8]{0} copy-done(%start)
  %work = f32[8]{0} fusion(%done), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/while/body/layer_b/layer_a/sin"}
  %red = f32[] reduce(%work, %c0), dimensions={0}, to_apply=%adder, metadata={op_name="jit(f)/while/body/layer_b/reduce_sum"}
  %next = s32[] add(%c0, %c0), metadata={op_name="jit(f)/while/body/add"}
  %loose = f32[8]{0} copy(%c1)
  ROOT %t = (s32[], f32[8]{0}) tuple(%next, %loose)
}

%cond (c: (s32[], f32[8])) -> pred[] {
  %c = (s32[], f32[8]{0}) parameter(0)
  %c0 = s32[] get-tuple-element(%c), index=0
  ROOT %less = pred[] compare(%c0, %c0), direction=LT, metadata={op_name="jit(f)/while/cond/lt"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %zero = s32[] constant(0)
  %named = f32[8]{0} exponential(%a), metadata={op_name="jit(layer_a)/vmap(jit(layer_c))/exp"}
  %staged = f32[8]{0} copy(%a)
  %init = (s32[], f32[8]{0}) tuple(%zero, %staged)
  %loop = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(f)/layer_b/while"}
  %made = (s32[], f32[8]{0}) while(%loop), condition=%made_cond, body=%made_body, metadata={op_name="jit(f)/layer_c/scatter"}
  %out = f32[8]{0} get-tuple-element(%made), index=1
  %view = f32[8]{0} bitcast(%out)
  ROOT %neg = f32[8]{0} negate(%view), metadata={op_name="jit(f)/transpose(jvp(layer_c))/neg"}
}
"""


def test_counting_rule_on_hand_written_text():
    stats = scope_stats(TEXT, SCOPES)
    ops = {k: v["ops"] for k, v in stats.items()}
    # layer_a: the fusion (innermost of layer_b/layer_a), nothing from
    # inside %fused or %adder; the copy-start/copy-done pair feeds it
    assert ops["layer_a"] == 3 and stats["layer_a"]["inherited"] == 2
    assert stats["layer_a"]["fusions"] == 1
    assert stats["layer_a"]["copies"] == 1          # copy-start
    # layer_b: the reduce; %staged feeds the loop that carries the name;
    # %loose, fed by nothing named, falls to the loop that runs its body
    assert ops["layer_b"] == 3 and stats["layer_b"]["inherited"] == 2
    # layer_c: the negate behind autodiff's wrapping, and the whole of
    # the loop the compiler made (add, copy, compare: no name anywhere)
    assert ops["layer_c"] == 4 and stats["layer_c"]["inherited"] == 3
    # named, but under no scope: the program's own loop counter and test,
    # and an operation of a jitted function that is NAMED like a scope
    assert ops["unscoped"] == 3 and stats["unscoped"]["inherited"] == 0
    assert sum(ops.values()) == 13
    # nested: what passes through layer_b at any depth
    assert stats["layer_b"]["ops_incl"] == 6
    assert stats["layer_a"]["ops_incl"] == 3
    assert stats["unscoped"]["ops_incl"] == 3
    # result bytes: f32[8] is 32, the copy-start tuple 32 + 32 + 4
    assert stats["layer_a"]["out_bytes"] == 32 + 68 + 32


def test_op_scopes_names_every_operation():
    scopes = op_scopes(TEXT, SCOPES)
    assert scopes == {
        "start": "layer_a", "done": "layer_a", "work": "layer_a",
        "red": "layer_b", "staged": "layer_b", "loose": "layer_b",
        "next": "unscoped", "less": "unscoped", "named": "unscoped",
        "bump": "layer_c", "move": "layer_c", "lt": "layer_c",
        "neg": "layer_c"}
    # neither control flow, nor values threaded through, nor the inside
    # of a fusion or a reducer
    assert not {"loop", "made", "init", "view", "inner", "sum",
                "a"} & set(scopes)


def toy(x):
    def body(c, _):
        with jax.named_scope("layer_b"):
            c = jnp.sin(c) @ jnp.ones((8, 8)) + 1.0
            with jax.named_scope("layer_c"):
                c = jnp.cumsum(c) * 2.0
        return c, None

    with jax.named_scope("layer_a"):
        x = jnp.tanh(x) @ jnp.ones((8, 8))
    x, _ = jax.lax.scan(body, x, None, length=5)
    return jnp.sort(x)


def test_toy_vmap_scan_with_three_scopes():
    compiled = jax.jit(jax.vmap(toy)).lower(jnp.ones((4, 8))).compile()
    text = compiled.as_text()
    stats = scope_stats(compiled, SCOPES)
    assert stats == scope_stats(text, SCOPES)
    for scope in SCOPES:
        assert stats[scope]["ops"] > 0, scope
    # the scan body once, not once per iteration; the sort and the loop's
    # own counter are the program's, under no scope
    assert stats["unscoped"]["ops"] > 0
    assert stats["layer_b"]["ops_incl"] == \
        stats["layer_b"]["ops"] + stats["layer_c"]["ops"]
    total = sum(v["ops"] for v in stats.values())
    # operations inside fused computations are not counted: the text
    # holds more instructions with a scope's name than there are
    # operations
    mentions = sum(text.count(f"/{s}/") for s in SCOPES)
    assert mentions > total - stats["unscoped"]["ops"]
    assert total < text.count(" = ")
    assert set(op_scopes(compiled, SCOPES).values()) == set(SCOPES) | {
        "unscoped"}
    assert len(op_scopes(compiled, SCOPES)) == total


# ------------------------------------------------ the map from one walk
@pytest.mark.parametrize("source", ["hand_written", "tiny_chunk_step"])
def test_map_from_the_one_walk_counts_as_scope_stats(source, request):
    if source == "hand_written":
        walked, scopes = scope_map(TEXT, SCOPES), SCOPES
        assert walked["stats"] == scope_stats(TEXT, SCOPES)
        # op_scopes is the map's view: each operation's innermost scope
        assert op_scopes(TEXT, SCOPES) == {
            n: path.rsplit("/", 1)[-1]
            for path, ops in walked["paths"].items() for n in ops}
    else:
        walked, scopes = request.getfixturevalue("tiny_walk"), DEVICE_SCOPES
    stats, paths = walked["stats"], walked["paths"]
    # every operation once, under one path
    names = [n for ops in paths.values() for n in ops]
    assert len(names) == len(set(names)) == sum(
        v["ops"] for v in stats.values())
    # the inclusive count of each scope from the map is scope_stats'
    for scope in scopes:
        assert sum(len(ops) for path, ops in paths.items()
                   if scope in path.split("/")) == stats[scope]["ops_incl"]
    assert len(paths.get("unscoped", [])) == stats["unscoped"]["ops_incl"]
    # a signature for every operation, anchors only from the map's paths
    assert set(walked["signatures"]) == set(names)
    assert set(walked["anchors"]) <= set(paths)
    for path, anchors in walked["anchors"].items():
        assert anchors and set(anchors) <= set(paths[path])


def test_anchors_run_once_per_iteration_of_the_layers_loop():
    walked = scope_map(TEXT, SCOPES)
    # layer_b/layer_a: its three operations in the loop body %body;
    # layer_b: of its own, the loop body holds two (the reduce, whose
    # result is a scalar the chip's trace never shows, and %loose), the
    # entry one (%staged); layer_c: the loop the compiler made, whose
    # scalar counter and test do not count
    assert walked["anchors"] == {"layer_b/layer_a": ["start", "done", "work"],
                                 "layer_b": ["loose"], "layer_c": ["move"]}


def test_signatures_are_what_a_trace_event_shows():
    walked = scope_map(TEXT, SCOPES)
    # a chip's trace names an operation by its instruction's text, with
    # the operands' types and without the metadata
    event = ("%work = f32[8]{0} fusion(f32[8]{0} %done), kind=kLoop, "
             "calls=%fused")
    assert instruction_head(event) == ("work", walked["signatures"]["work"])
    assert instruction_head("%start = (f32[8]{0}, f32[8]{0}, u32[]) "
                            "copy-start(f32[8]{0} %c1)") == \
        ("start", walked["signatures"]["start"])
    assert walked["signatures"]["work"] != walked["signatures"]["done"]
    # a CPU trace's bare instruction name carries no signature
    assert instruction_head("work") is None


# ------------------------------------------------- the tiny chunk_step
@pytest.fixture(scope="module")
def tiny():
    env, agent, topo, traffic = make_stack()
    b = 2
    pddpg = ParallelDDPG(env, agent, num_replicas=b, donate=False)
    batch = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                   *[traffic] * b)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, batch)
    _, one = env.reset(jax.random.PRNGKey(1), topo, traffic)
    state = pddpg.init(jax.random.PRNGKey(0), one)
    buffers = pddpg.init_buffers(one)
    args = (pddpg, state, buffers, env_states, obs, topo, batch,
            np.int32(0))
    return type(pddpg).chunk_step, args, {"num_steps": 2, "learn": True}


@pytest.fixture(scope="module")
def tiny_walk(tiny):
    fn, args, kwargs = tiny
    # past the persistent cache: it keys a program on its operations, not
    # its names, so filled by an older source it would hand back that
    # source's scopes and a green run would not be a green source.  The
    # process latches "is the cache used" at its first compile, so the
    # switch alone bypasses nothing: reset the latch on either side of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = fn.lower(*args, **kwargs).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    return scope_map(compiled, DEVICE_SCOPES)


@pytest.fixture(scope="module")
def tiny_stats(tiny_walk):
    return tiny_walk["stats"]


# (a looped torso's scopes stand in its own program: tests/test_torso.py)
@pytest.mark.parametrize("scope", [s for s in DEVICE_SCOPES
                                   if s not in TORSO_SCOPES])
def test_every_scope_of_the_default_path_is_in_chunk_step(tiny_stats,
                                                          scope):
    assert tiny_stats[scope]["ops"] > 0
    assert tiny_stats[scope]["ops_incl"] >= tiny_stats[scope]["ops"]


def test_chunk_step_unscoped_share_and_nesting(tiny_stats):
    total = sum(v["ops"] for v in tiny_stats.values())
    assert tiny_stats["unscoped"]["ops"] <= 0.05 * total
    inner = ("sim_substep", "traffic_arrivals", "policy_forward",
             "env_observe", "replay_write")
    # the control step's scan encloses the rollout's layers
    assert tiny_stats["rollout_step"]["ops_incl"] >= \
        tiny_stats["rollout_step"]["ops"] + sum(
            tiny_stats[s]["ops"] for s in inner)
    # ``traffic_arrivals`` is entered twice: stage 3 under the substep,
    # and the control step's window of the arrival table under
    # ``rollout_step`` alone (PR 31).  Nothing else nests in the substep,
    # so what it holds beyond its own operations is stage 3, and that is
    # some of the scope's operations, not all of them
    stage3 = tiny_stats["sim_substep"]["ops_incl"] - \
        tiny_stats["sim_substep"]["ops"]
    assert 0 < stage3 < tiny_stats["traffic_arrivals"]["ops"]
    assert tiny_stats["traffic_arrivals"]["ops_incl"] == \
        tiny_stats["traffic_arrivals"]["ops"]
    burst = ("replay_sample", "critic_update", "actor_update",
             "target_update")
    assert tiny_stats["learn_burst"]["ops_incl"] >= \
        tiny_stats["learn_burst"]["ops"] + sum(
            tiny_stats[s]["ops"] for s in burst)
    # the graph layers run under the policy and under both updates
    assert tiny_stats["gat_layer"]["ops"] > 0
    assert tiny_stats["policy_forward"]["ops_incl"] > \
        tiny_stats["policy_forward"]["ops"]


# ----------------------------------------------------------- the ledger
def test_capture_puts_scopes_in_entry_event_and_document(tiny):
    fn, args, kwargs = tiny
    hub = MetricsHub()
    sink = ListSink()
    hub.add_sink(sink)
    ledger = perf_mod.CostLedger(hub=hub)
    entry = ledger.capture("chunk_step", fn, args, kwargs)
    assert entry["available"] and set(entry["scopes"]) == \
        set(DEVICE_SCOPES) | {"unscoped"}
    assert sum(v["fusions"] for v in entry["scopes"].values()) \
        <= entry["fusions"]
    event = sink.of_kind("compile_cost")[0]
    assert event["fn"] == "chunk_step" and event["scopes"] == entry["scopes"]
    # the event's counts stay compact; the map beside them is the join's
    assert len(json.dumps({k: v for k, v in event.items()
                           if k != "op_map"})) < 4096
    op_map = entry["op_map"]
    assert event["op_map"] == op_map and op_map["module"] == "jit_chunk_step"
    ops = sum(v["ops"] for v in entry["scopes"].values())
    assert sum(len(v) for v in op_map["paths"].values()) == ops
    assert len(json.dumps(op_map)) < 64 * ops
    doc = ledger.summary()
    assert doc["entries"]["chunk_step"]["scopes"] == entry["scopes"]
    assert doc["entries"]["chunk_step"]["op_map"] == op_map


class Lowered:
    """A lowering whose first compile comes back from a cache that an
    older, scope-less source filled."""

    def __init__(self, stale, fresh, x, calls):
        self.stale, self.fresh, self.x, self.calls = stale, fresh, x, calls

    def compile(self, compiler_options=None):
        self.calls.append(compiler_options)
        fn = self.stale if compiler_options is None else self.fresh
        return jax.jit(fn).lower(self.x).compile()


class Jitted:
    def __init__(self, *a):
        self.a = a

    def lower(self, x):
        return Lowered(*self.a[:2], x, self.a[2])


def test_capture_compiles_again_when_the_cache_hands_back_old_names():
    """The persistent cache keys a program on its operations, so a hit
    carries the names of the source that compiled it first: a program
    that comes back with no scope at all is compiled once more under a
    cache key of its own."""
    def bare(x):
        return jnp.sin(x) * 2.0

    def scoped(x):
        with jax.named_scope("sim_substep"):
            return jnp.sin(x) * 2.0

    calls = []
    ledger = perf_mod.CostLedger()
    entry = ledger.capture("f", Jitted(bare, scoped, calls), (jnp.ones(8),))
    assert calls == [None, perf_mod.OWN_CACHE_KEY]
    assert entry["available"]
    assert entry["scopes"]["sim_substep"]["ops"] > 0
    # names that arrive with the first compile: no second one
    calls.clear()
    entry = ledger.capture("g", Jitted(scoped, scoped, calls),
                           (jnp.ones(8),))
    assert calls == [None]
    assert entry["scopes"]["sim_substep"]["ops"] > 0


def test_own_cache_key_is_a_compile_the_backend_accepts():
    @jax.jit
    def f(x):
        with jax.named_scope("sim_substep"):
            return x + 1.0

    lowered = f.lower(jnp.ones(4))
    compiled = lowered.compile(compiler_options=perf_mod.OWN_CACHE_KEY)
    assert "sim_substep" in compiled.as_text()
    assert float(compiled(jnp.ones(4))[0]) == 2.0
