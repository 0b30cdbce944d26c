"""The set-up ledger (`apex_tpu.monitor.compile.startup`): every
program a process traces, lowers, compiles or reads from the cache, the
kernel bodies it traces, the program's own spans of set-up work, and
`scopes.step_kernels()`, the static side of the kernels.  CPU,
interpret-mode kernels; no number here is a measurement.
"""

import ast
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from apex_tpu.models.gpt import GPT, GPTConfig
from apex_tpu.monitor import scopes
from apex_tpu.monitor.compile import RecompileSentry, startup
from apex_tpu.ops.layer_norm import fused_layer_norm
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import mesh as M
from apex_tpu.transformer.training import (
    init_sharded_optimizer,
    make_tp_dp_train_step,
)

OPS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "apex_tpu", "ops")
STAGES = ("trace_s", "lower_s", "compile_s", "cache_read_s")
TRACE, LOWER, COMPILE = startup._STAGES
CACHE = "/jax/compilation_cache/"


def _listeners():
    from jax._src import monitoring as m   # the getters are not public

    return (m.get_event_listeners(), m.get_event_duration_listeners(),
            m.get_event_time_span_listeners(), m.get_scalar_listeners())


@pytest.fixture
def ledger():
    """The process's ledger, fresh and armed; as found again after."""
    was_armed = startup._LEDGER.armed
    startup.disarm()
    startup._LEDGER.reset()
    startup.arm()
    yield startup._LEDGER
    startup.disarm()
    startup._LEDGER.reset()
    if was_armed:
        startup.arm()


@pytest.fixture
def own():
    """A ledger of the test's own, beside the process's."""
    mine = startup.SetupLedger()
    mine.arm()
    yield mine
    mine.disarm()


def _tiny_step(use_pallas=True):
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:2])
    model = GPT(GPTConfig(vocab_size=64, seq_len=16, hidden=32, num_layers=2,
                          num_heads=4, dropout=0.0))
    params = model.init(jax.random.PRNGKey(8))
    opt = FusedAdam(lr=3e-3, use_pallas=use_pallas)
    state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=False)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    return step, state, tokens, jnp.roll(tokens, -1, axis=1)


def _replay(stage, name, start, seconds, inside=lambda: None):
    """One stage span as JAX's `log_elapsed_time` publishes it."""
    m = jax.monitoring
    m.record_scalar(stage, start, fun_name=name)
    inside()
    m.record_event_duration_secs(stage, seconds, fun_name=name)
    m.record_event_time_span(stage, start, start + seconds, fun_name=name)


# ------------------------- the program records -------------------------

def test_a_step_is_one_record_with_its_three_stages(ledger):
    step, state, tokens, labels = _tiny_step()
    state, loss = step(state, tokens, labels)
    assert np.isfinite(float(loss))
    found = ledger.ledger()
    mine = [r for r in found["programs"]
            if r["fun_name"] == "jit(local_step)"]
    assert len(mine) == 1
    (record,) = mine
    assert record["trace_s"] > 0 and record["lower_s"] > 0
    assert record["compile_s"] > 0 and "cache_read_s" not in record
    assert record["cache"] == "unused" and record["steady"] is False
    assert record["at_s"] > found["armed_at_s"] > 0
    # the helpers of jax.numpy: hundreds of traces, none of them a record
    totals = found["totals"]["setup"]
    assert totals["nested_traces"] > 100
    assert totals["programs"] == len(found["programs"]) < 100
    assert not any(r["fun_name"] in ("jit(_where)", "jit(_reduce_sum)")
                   and r.get("inside") == "local_step"
                   for r in found["programs"])
    # the records' self seconds are the totals
    for stage in STAGES:
        assert sum(r.get(stage, 0.0) for r in found["programs"]) == (
            pytest.approx(totals[stage], rel=1e-9))
    # the Adam kernel's body was traced once, under the step's trace
    assert found["kernels_by_program"]["local_step"]["adam_flat"][
        "calls"] == 1
    assert 0 < found["kernels"]["adam_flat"]["trace_s"] < record["trace_s"]


def test_a_second_call_adds_no_record_and_fires_no_callback(ledger):
    step, state, tokens, labels = _tiny_step()
    state, loss = step(state, tokens, labels)
    float(loss)
    events, programs = ledger.events, ledger.n_programs
    for _ in range(3):
        state, loss = step(state, tokens, labels)
    float(loss)
    assert (ledger.events, ledger.n_programs) == (events, programs)


def test_nothing_fires_in_the_steady_window(ledger):
    """Neither a callback nor a span: the loop of a job, its sentry
    around the step, after `mark_steady`."""
    step, state, tokens, labels = _tiny_step()
    sentry = RecompileSentry(step, warn=False)
    for _ in range(2):
        state, loss = sentry(state, tokens, labels)
    sentry.mark_steady()
    events = ledger.events
    for _ in range(5):
        state, loss = sentry(state, tokens, labels)
        float(loss)
    found = ledger.ledger()
    assert found["events"] == events == found["events_at_steady"]
    assert found["first_after_steady"] is None
    assert found["totals"]["steady"]["programs"] == 0
    assert sentry.steady_recompiles == 0


def test_a_program_compiled_inside_a_trace_is_its_own_record(own):
    """A jnp call that runs while a function is traced (on concrete
    values, under `ensure_compile_time_eval`): recorded, named for
    where it ran, its seconds off that span."""
    def outer(x):
        with jax.ensure_compile_time_eval():
            table = jnp.cumsum(jnp.arange(37.0))  # runs now
        return x * table[5]

    t0 = time.time()
    jax.jit(outer)(jnp.ones(3))
    wall = time.time() - t0
    found = own.ledger()
    inner = [r for r in found["programs"] if r.get("inside") == "outer"]
    assert inner and all(r["fun_name"] != "jit(outer)" for r in inner)
    (outer_record,) = [r for r in found["programs"]
                       if r["fun_name"] == "jit(outer)"]
    assert "inside" not in outer_record
    # self time: together they cannot exceed the wall clock around them
    assert sum(startup.seconds_of(r) for r in [outer_record, *inner]) <= wall


def test_a_lowering_alone_then_its_compile_is_one_record(own):
    def aot(x):
        return jnp.sin(x) + 2

    lowered = jax.jit(aot).lower(jnp.ones(5))
    jax.jit(lambda x: x - 1)(jnp.ones(5))         # another, in between
    lowered.compile()
    mine = [r for r in own.ledger()["programs"]
            if r["fun_name"] == "jit(aot)"]
    assert len(mine) == 1
    assert all(mine[0][k] > 0 for k in ("trace_s", "lower_s", "compile_s"))


def test_a_trace_that_lowers_nothing_is_counted_apart(own):
    jax.eval_shape(jax.jit(lambda x: jnp.cos(x) * 2), jnp.ones(4))
    jax.jit(lambda x: x + 3)(jnp.ones(4))         # flushes what waited
    totals = own.ledger()["totals"]["setup"]
    assert totals["traces_without_program"] >= 1


def test_a_fault_of_the_instrument_costs_a_count_not_the_compile(
        own, monkeypatch):
    def broken(*_):
        raise RuntimeError("the ledger is wrong")

    monkeypatch.setattr(own, "_record", broken)
    out = jax.jit(lambda x: x * 7)(jnp.ones(3))
    assert float(out[0]) == 7.0
    found = own.ledger()
    assert found["faults"] >= 1
    assert "the ledger is wrong" in found["first_fault"]


# ------------------------ the persistent cache ------------------------

@pytest.fixture
def persistent_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update(names[0], str(tmp_path))
    jax.config.update(names[1], 0)
    jax.config.update(names[2], -1)
    cc.reset_cache()
    yield str(tmp_path)
    for n, v in before.items():
        jax.config.update(n, v)
    cc.reset_cache()


def test_a_second_pass_reads_the_cache_and_says_so(own, persistent_cache):
    def cached(x):
        return jnp.tanh(x) * 3 + 1

    x = jnp.ones(7)
    jax.jit(cached)(x)
    jax.clear_caches()
    jax.jit(cached)(x)
    first, second = [r for r in own.ledger()["programs"]
                     if r["fun_name"] == "jit(cached)"]
    assert first["cache"] == "miss" and first["compile_s"] > 0
    if second["cache"] != "hit":
        # this backend serves no entry: the same through JAX's own
        # publishing calls, as compiler.compile_or_get_cached makes them
        m = jax.monitoring

        def the_cache_hits():
            m.record_event(CACHE + "compile_requests_use_cache")
            m.record_event(CACHE + "cache_hits")
            m.record_event_duration_secs(
                CACHE + "compile_time_saved_sec", 4.0)
            m.record_event_duration_secs(
                CACHE + "cache_retrieval_time_sec", 0.25)

        _replay(TRACE, "cached", 100.0, 0.5)
        _replay(LOWER, "jit(cached)", 100.5, 0.25)
        _replay(COMPILE, "jit(cached)", 101.0, 0.5, the_cache_hits)
        second = own.ledger()["programs"][-1]
    assert second["cache"] == "hit" and "compile_s" not in second
    assert second["cache_read_s"] > 0 and "saved_s" in second
    assert second["trace_s"] > 0 and second["lower_s"] > 0
    totals = own.ledger()["totals"]["setup"]
    assert totals["cache_hits"] == 1 and totals["cache_misses"] >= 1
    assert totals["cache_requests"] >= 2


def test_replayed_events_file_the_cache_under_the_open_compile(own):
    m = jax.monitoring

    def missed():
        m.record_event(CACHE + "compile_requests_use_cache")
        m.record_event(CACHE + "cache_misses")

    def hit():
        m.record_event(CACHE + "compile_requests_use_cache")
        m.record_event(CACHE + "cache_hits")
        m.record_event_duration_secs(CACHE + "compile_time_saved_sec", 66.0)
        m.record_event_duration_secs(CACHE + "cache_retrieval_time_sec", 1.0)

    _replay(TRACE, "big", 10.0, 20.0,
            lambda: [_replay(TRACE, "_where", 11.0 + i, 0.5)
                     for i in range(4)])
    _replay(LOWER, "jit(big)", 30.0, 2.0)
    _replay(COMPILE, "jit(big)", 32.0, 60.0, missed)
    _replay(COMPILE, "jit(small)", 92.0, 3.0, hit)
    big, small = own.ledger()["programs"]
    assert (big["trace_s"], big["lower_s"], big["compile_s"]) == (
        20.0, 2.0, 60.0)
    assert big["cache"] == "miss" and "saved_s" not in big
    assert small == {**small, "cache": "hit", "cache_read_s": 3.0,
                     "saved_s": 66.0}
    totals = own.ledger()["totals"]["setup"]
    assert totals["nested_traces"] == 4 and totals["programs"] == 2
    assert (totals["cache_hits"], totals["cache_misses"]) == (1, 1)


# ----------------------- steady state, the sentry -----------------------

def test_a_compile_after_steady_is_marked_and_the_sentry_names_it(ledger):
    step, state, tokens, labels = _tiny_step(use_pallas=False)
    sentry = RecompileSentry(step, name="tiny", warn=False)
    state, loss = sentry(state, tokens, labels)
    first = sentry.events[0]
    assert first["fun_name"] == "jit(local_step)" and first["seconds"] > 0
    sentry.mark_steady()
    assert ledger.ledger()["steady_at_s"] is not None
    # a batch of another shape: the step compiles again
    tokens2 = jnp.concatenate([tokens, tokens])
    state, loss = sentry(state, tokens2, jnp.roll(tokens2, -1, axis=1))
    assert sentry.steady_recompiles == 1
    event = sentry.events[-1]
    assert event["steady_state"] is True
    assert event["fun_name"] == "jit(local_step)"
    found = ledger.ledger()
    late = [r for r in found["programs"] if r["steady"]]
    assert any(r["fun_name"] == "jit(local_step)" for r in late)
    again = max(late, key=startup.seconds_of)
    assert event["seconds"] == pytest.approx(startup.seconds_of(again))
    assert found["totals"]["steady"]["programs"] == len(late)
    assert found["first_after_steady"]["at_s"] >= found["steady_at_s"]
    assert found["events"] > found["events_at_steady"]


# ------------------------------- arming -------------------------------

def test_disarm_leaves_the_listeners_it_found():
    """run.py's kind of listener was there before and fires after;
    never `clear_event_listeners`."""
    seen = []

    def theirs(event, **_):
        seen.append(event)

    jax.monitoring.register_event_listener(theirs)
    try:
        before = _listeners()
        mine = startup.SetupLedger()
        assert mine.arm() is True and mine.arm() is False   # idempotent
        assert [len(a) + 1 for a in before] == [
            len(a) for a in _listeners()]
        mine.disarm()
        mine.disarm()
        assert _listeners() == before
        jax.monitoring.record_event(CACHE + "cache_hits")
        assert seen == [CACHE + "cache_hits"]
        assert mine.ledger()["totals"]["setup"]["cache_hits"] == 0
    finally:
        jax.monitoring.unregister_event_listener(theirs)


def test_the_mesh_and_the_registry_arm_it_and_import_does_not():
    import subprocess
    import sys

    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import apex_tpu\n"
        "from apex_tpu.monitor import scopes\n"
        "from apex_tpu.monitor.compile import startup\n"
        "from apex_tpu.parallel import mesh as M\n"
        "assert not startup._LEDGER.armed\n"
        "from jax._src import monitoring\n"
        "assert not monitoring.get_event_listeners()\n"
        "assert not monitoring.get_scalar_listeners()\n"
        "M.initialize_model_parallel()\n"
        "assert startup._LEDGER.armed\n"
        "startup.disarm(); assert not startup._LEDGER.armed\n"
        "scopes.register('f', jax.jit(lambda x: x), (jax.numpy.ones(2),))\n"
        "assert startup._LEDGER.armed\n"
        "print(startup.ledger()['spans'][0]['name'])\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "initialize_model_parallel"


# ------------------------------- bounded -------------------------------

def test_the_list_is_capped_and_the_totals_stay_exact():
    mine = startup.SetupLedger(max_programs=50, max_spans=8)
    mine.arm()
    try:
        for i in range(20):               # real ones, distinct and tiny
            jax.jit(lambda x, i=i: x + i)(jnp.ones(2))
        real = mine.ledger()["totals"]["setup"]["programs"]
        assert real >= 20
        _replay(TRACE, "the_step", 5.0, 7.0)
        _replay(LOWER, "jit(the_step)", 12.0, 3.0)
        _replay(COMPILE, "jit(the_step)", 15.0, 90.0)
        for i in range(2000):
            _replay(TRACE, f"f{i}", 200.0 + i, 0.25)
            _replay(LOWER, f"jit(f{i})", 200.25 + i, 0.25)
            _replay(COMPILE, f"jit(f{i})", 200.5 + i, 0.5)
        for i in range(20):
            with mine.span(f"s{i}"):
                pass
    finally:
        mine.disarm()
    found = mine.ledger()
    assert len(found["programs"]) == 50
    assert found["programs_dropped"] == real + 1 + 2000 - 50
    totals = found["totals"]["setup"]
    assert totals["programs"] == real + 2001
    assert totals["compile_s"] == pytest.approx(90.0 + 1000.0, abs=5.0)
    # the heaviest stays whenever it came; the numbers say what is gone
    assert max(found["programs"], key=startup.seconds_of)[
        "fun_name"] == "jit(the_step)"
    assert len(found["spans"]) == 8 and found["spans_dropped"] == 12
    assert len(mine._awaiting) == 0


# -------------------------------- spans --------------------------------

def test_the_programs_own_spans_have_a_parent_and_a_program(ledger):
    step, state, tokens, labels = _tiny_step()
    state, _ = step(state, tokens, labels)
    scopes.step_text()
    found = ledger.ledger()
    spans = {s["name"]: s for s in found["spans"]}
    assert {"initialize_model_parallel", "init_sharded_optimizer",
            "make_tp_dp_train_step.build", "scopes.step_text"} <= set(spans)
    assert all(s["s"] >= 0 and s["at_s"] > 0 for s in spans.values())
    (init,) = [r for r in found["programs"]
               if r["fun_name"] == "jit(local_init)"]
    assert init["span"] == "init_sharded_optimizer"
    with startup.span("outer"):
        with startup.span("inner"):
            pass
    inner, outer = ledger.ledger()["spans"][-2:]
    assert (inner["name"], inner["parent"]) == ("inner", "outer")
    assert outer["parent"] is None


def test_the_tuners_first_read_is_a_span(ledger, monkeypatch):
    from apex_tpu import tune
    from apex_tpu.tune import cache

    monkeypatch.setitem(cache._state, "cache", None)
    tune.lookup("no_such_op", {"n": 1})
    tune.lookup("no_such_op", {"n": 2})
    names = [s["name"] for s in ledger.ledger()["spans"]]
    assert names.count("tune.load_tables") == 1


# ------------------------------ the kernels ------------------------------

class _Mixer:
    """What `make_tp_dp_train_step` needs of a model, around one
    checkpointed mixer that is a Pallas kernel pair."""

    hidden = 128

    def partition_specs(self):
        return {"embed": P(), "gain": P()}

    def init(self, key):
        return {"embed": jax.random.normal(key, (64, self.hidden)),
                "gain": jnp.ones((self.hidden,))}

    def loss(self, params, tokens, labels):
        mixer = jax.checkpoint(lambda x, g: fused_layer_norm(
            x, g, None, use_pallas_override=True))
        y = mixer(params["embed"][tokens], params["gain"])
        return jnp.mean(jnp.square(y.sum(-1) - labels))


def test_step_kernels_counts_a_kernel_once_a_call_site(ledger):
    M.destroy_model_parallel()
    mesh = M.initialize_model_parallel(devices=jax.devices()[:1])
    model = _Mixer()
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-3, use_pallas=False)
    state = init_sharded_optimizer(opt, model, params, mesh)
    step = make_tp_dp_train_step(model, opt, mesh, donate=False)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    state, loss = step(state, tokens, tokens.astype(jnp.float32))
    assert np.isfinite(float(loss))

    found = scopes.step_kernels()
    # forward, the forward recomputed in the backward, the backward
    assert {k: v["call_sites"] for k, v in found.items()} == {
        "ln_fwd": 2, "ln_bwd": 1}

    # the bodies traced alone, through the same op at the same shapes
    x = jnp.ones((2, 8, model.hidden))
    alone = jax.make_jaxpr(jax.grad(lambda x, g: fused_layer_norm(
        x, g, None, use_pallas_override=True).sum(), (0, 1)))(
        x, params["gain"]).jaxpr
    bodies = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                bodies[eqn.params["name"]] = eqn.params["jaxpr"]
            for inner in scopes._jaxprs_in(eqn):
                walk(inner)

    walk(alone)
    assert set(bodies) == {"ln_fwd", "ln_bwd"}
    for name, body in bodies.items():
        eqns = scopes.count_eqns(body)
        assert eqns >= len(body.eqns) > 0
        assert found[name]["body_eqns"] == eqns * found[name]["call_sites"]
    # the dynamic twin: the same three bindings, filed under the step
    spans = ledger.ledger()["kernels_by_program"]["local_step"]
    assert {k: v["calls"] for k, v in spans.items()} == {
        "ln_fwd": 2, "ln_bwd": 1}


def test_count_eqns_counts_nested_bodies():
    def f(x):
        return jax.lax.cond(x.sum() > 0, lambda x: jnp.sin(x) + 1,
                            lambda x: x * 2, x)

    jaxpr = jax.make_jaxpr(f)(jnp.ones(3)).jaxpr
    assert scopes.count_eqns(jaxpr) > len(jaxpr.eqns)
    assert scopes.kernels_in(jaxpr) == {}


def test_a_memoised_call_binds_for_nothing(ledger):
    """A span is around the binding, not the construction: the same
    `pallas_call` object at the same shapes is traced once."""
    traced = []

    def body(x_ref, o_ref):
        traced.append(1)
        o_ref[...] = x_ref[...] * 2.0

    call = pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        interpret=True, name="doubler")
    x = jnp.ones((8, 128))

    def twice(x):
        for _ in range(2):
            with startup.kernel_span("doubler"):
                x = call(x)
        return x

    jax.jit(twice)(x)
    found = ledger.ledger()
    cell = found["kernels_by_program"]["twice"]["doubler"]
    assert cell["calls"] == 2
    assert cell["trace_s"] >= cell["max_s"] > cell["trace_s"] / 2
    # two bindings, two spans; the body ran in Python for the first,
    # and the second found its jaxpr in JAX's cache
    assert len(traced) == 1
    assert "jit(twice)" in [r["fun_name"] for r in found["programs"]]


def _inside_a_span(node, parents):
    """The name expression of the `with kernel_span(...)` a node stands
    in, or None."""
    while node in parents:
        node = parents[node]
        if isinstance(node, ast.With):
            for item in node.items:
                call = item.context_expr
                if (isinstance(call, ast.Call)
                        and isinstance(call.func, ast.Name)
                        and call.func.id == "kernel_span"):
                    return call.args[0]
    return None


def _names_in(expr):
    """The string constants of a span's name expression, a dict's keys
    (what it is looked up by) left out."""
    if isinstance(expr, ast.Dict):
        return [n for v in expr.values for n in _names_in(v)]
    if isinstance(expr, ast.Constant):
        return [expr.value] if isinstance(expr.value, str) else []
    return [n for child in ast.iter_child_nodes(expr)
            for n in _names_in(child)]


def _is_pallas_call(node):
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "pallas_call")


def _bindings(tree):
    """(call that binds a kernel, the names its `pl.pallas_call` gives
    or None for a builder's) of one module: `pl.pallas_call(...)(...)`
    on the spot, `builder(...)(...)`, and `call = builder(...)` ...
    `call(...)`, a builder being a function that returns a
    `pl.pallas_call(...)` or another builder's."""
    functions = [n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)]
    builders = set()
    while True:
        more = {
            f.name for f in functions for r in ast.walk(f)
            if isinstance(r, ast.Return) and isinstance(r.value, ast.Call)
            and (_is_pallas_call(r.value)
                 or getattr(r.value.func, "id", None) in builders)}
        if more <= builders:
            break
        builders |= more

    def builds(node):
        return (isinstance(node, ast.Call)
                and getattr(node.func, "id", None) in builders)

    for f in [tree, *functions]:
        held = {t.id for n in ast.walk(f) if isinstance(n, ast.Assign)
                and builds(n.value) for t in n.targets
                if isinstance(t, ast.Name)}
        for node in ast.walk(f):
            if not isinstance(node, ast.Call):
                continue
            if _is_pallas_call(node.func):
                given = {k.arg: k.value for k in node.func.keywords}["name"]
                yield node, given.value
            elif builds(node.func) or (
                    f is not tree and getattr(node.func, "id", None) in held):
                yield node, None
    # a `pl.pallas_call` that is neither bound on the spot nor returned
    # by a builder would be bound where this cannot see
    parents = {c: n for n in ast.walk(tree) for c in ast.iter_child_nodes(n)}
    for node in ast.walk(tree):
        if _is_pallas_call(node):
            parent = parents[node]
            assert (isinstance(parent, ast.Return)
                    or (isinstance(parent, ast.Call)
                        and parent.func is node)), node.lineno


def test_every_binding_of_a_pallas_call_stands_in_a_kernel_span():
    """`with kernel_span("<name>"):` around the statement that calls
    what `pl.pallas_call(..., name="<name>")` returned: a binding
    outside one would trace its body in nobody's account."""
    found, sites = [], 0
    for filename in sorted(os.listdir(OPS)):
        if not filename.endswith(".py"):
            continue
        with open(os.path.join(OPS, filename)) as f:
            tree = ast.parse(f.read())
        parents = {c: n for n in ast.walk(tree)
                   for c in ast.iter_child_nodes(n)}
        sites += sum(_is_pallas_call(n) for n in ast.walk(tree))
        seen = set()
        for call, given in _bindings(tree):
            if call in seen:
                continue
            seen.add(call)
            at = f"{filename}:{call.lineno}"
            span = _inside_a_span(call, parents)
            assert span is not None, f"{at} binds a kernel outside a span"
            names = _names_in(span)
            assert names and set(names) <= set(scopes.KERNELS), at
            if given is not None:
                assert names == [given], at
            found += names
    assert sites >= 30
    # every kernel of the vocabulary is bound somewhere under its name
    assert set(found) == set(scopes.KERNELS)


@pytest.mark.parametrize("source,outside", [
    ("def f(x):\n    return pl.pallas_call(k, name='welford')(x)\n", 1),
    ("def f(x):\n    with kernel_span('welford'):\n"
     "        return pl.pallas_call(k, name='welford')(x)\n", 0),
    ("def _b(n):\n    return pl.pallas_call(k, name='welford')\n"
     "def _c(n):\n    return _b(n)\n"
     "def f(x):\n    call = _c(3)\n    return call(x)\n"
     "def g(x):\n    with kernel_span('welford'):\n"
     "        return _b(3)(x)\n", 1),
])
def test_the_source_check_sees_a_binding_outside_a_span(source, outside):
    tree = ast.parse(source)
    parents = {c: n for n in ast.walk(tree) for c in ast.iter_child_nodes(n)}
    calls = {call for call, _ in _bindings(tree)}
    assert sum(_inside_a_span(c, parents) is None for c in calls) == outside


# ------------------------------ chip_smoke ------------------------------

def test_chip_smoke_prints_the_ledgers_account(ledger):
    import chip_smoke

    earlier = {}
    first = chip_smoke.compile_account(earlier)
    jax.jit(lambda x: x * 5 + 1)(jnp.ones(3))
    second = chip_smoke.compile_account(earlier)
    assert {"persistent_cache_hits", "persistent_cache_misses",
            "compile_account"} == set(first) == set(second)
    assert second["compile_account"]["programs"] >= 1
    assert second["compile_account"]["compile_s"] > 0
    third = chip_smoke.compile_account(earlier)
    assert third["compile_account"]["programs"] == 0
    assert not hasattr(chip_smoke, "CacheEvents")
