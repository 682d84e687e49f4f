"""The library's span recorder (``bluefog_tpu.timeline``): when it records,
what a span holds, where the spans are on the window path, and the names a
profile is read by (the pinned window programs, the step's named scopes)."""

import collections
import glob
import json
import os
import re
import sys
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import timeline as tl
from bluefog_tpu import topology_util as tu
from bluefog_tpu import windows
from bluefog_tpu.core import basics
from bluefog_tpu.optim import CommunicationType
from bluefog_tpu.training import make_decentralized_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = 8
KEEP = 0.5


@pytest.fixture(autouse=True)
def fresh_context(devices):
    bf.init(local_size=2)
    yield
    bf.win_free()
    bf.turn_off_win_ops_with_associated_p()
    bf.shutdown()


def _profile(path):
    """The benchmark's own session: host tracer on, Python tracer off."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return jax.profiler.trace(str(path), profiler_options=options)


def _pushsum_window(width=6):
    """A ring push-sum window like the benchmark's: keep half, send half."""
    bf.set_topology(tu.RingGraph(SIZE, connect_style=1))
    bf.turn_on_win_ops_with_associated_p()
    x = jnp.arange(SIZE * width, dtype=jnp.float32).reshape(SIZE, width)
    bf.win_create(x, "w", zero_init=True)
    return x


def _pushsum_round(x):
    dst = [{(r + 1) % SIZE: 1.0 - KEEP} for r in range(SIZE)]
    ones_prev = [{(r - 1) % SIZE: 1.0} for r in range(SIZE)]
    bf.win_accumulate(x, "w", dst_weights=dst)
    m = bf.win_update("w", self_weight=KEEP, neighbor_weights=ones_prev, reset=True)
    p = bf.win_associated_p("w")
    m = m / p.reshape((SIZE, 1))
    bf.win_set_exposed("w", m, associated_p=1.0)
    return m


def _no_timeline_lookup(monkeypatch):
    """From here on, reading BLUEFOG_TIMELINE from the environment fails."""
    get = os.environ.get

    def guarded(key, *default):
        assert key != "BLUEFOG_TIMELINE", "the environment is read once"
        return get(key, *default)

    monkeypatch.setattr(os.environ, "get", guarded)


def test_off_makes_one_check_and_touches_neither_clock_nor_writer(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("touched while nothing records")

    checks = []
    monkeypatch.setattr(tl, "_profiling", lambda: checks.append(1) and False)
    monkeypatch.setattr(tl, "_profiled", False)
    monkeypatch.setattr(tl, "_writer", False)  # the environment was read: unset
    monkeypatch.setattr(tl, "_get_writer", boom)
    monkeypatch.setattr(tl, "time", types.SimpleNamespace(
        perf_counter=boom, perf_counter_ns=boom))
    monkeypatch.setattr(tl.jax.profiler, "TraceAnnotation", boom)
    _no_timeline_lookup(monkeypatch)
    before = tl.spans()
    checks.clear()
    with tl.timeline_context("win_put") as span:
        assert span is None
    assert len(checks) == 1
    assert tl.spans() == before


def test_window_ops_record_nothing_while_off(monkeypatch):
    monkeypatch.setattr(tl, "_writer", False)
    before = tl.spans()
    _pushsum_round(_pushsum_window())
    assert tl.spans() == before


def test_profiler_session_records_id_parent_name_start_end_nbytes(tmp_path):
    with _profile(tmp_path):
        with tl.timeline_context("outer") as outer:
            outer.nbytes = 96
            with tl.timeline_context("outer/inner") as inner:
                assert inner.nbytes == 0
        with tl.timeline_context("next"):
            pass
    a, b, c = tl.spans()
    assert a._fields == ("id", "parent", "name", "start", "end", "nbytes")
    assert (a.name, b.name, c.name) == ("outer", "outer/inner", "next")
    assert a.parent is None and b.parent == a.id and c.parent is None
    assert a.id < b.id < c.id
    assert a.start <= b.start <= b.end <= a.end <= c.start <= c.end
    assert (a.nbytes, b.nbytes, c.nbytes) == (96, 0, 0)


def test_pushsum_round_yields_the_four_window_ops_with_nested_children(tmp_path):
    x = _pushsum_round(_pushsum_window())  # compile outside the session
    with _profile(tmp_path):
        _pushsum_round(x)
    spans = tl.spans()
    tops = [s for s in spans if s.parent is None]
    assert [s.name for s in tops] == [
        "win_accumulate", "win_update", "win_associated_p", "win_set_exposed"]
    assert all(a.end <= b.start for a, b in zip(tops, tops[1:]))
    by_id = {s.id: s for s in spans}
    children = [s for s in spans if s.parent is not None]
    assert [s.name for s in children] == [
        "win_accumulate/exchange", "win_update/combine", "win_update/reset"]
    for c in children:
        parent = by_id[c.parent]
        assert c.name.startswith(parent.name + "/")
        assert parent.start <= c.start <= c.end <= parent.end
    combine, reset = children[1:]
    assert combine.end <= reset.start


def test_nbytes_is_what_the_op_was_handed(tmp_path):
    x = _pushsum_window(width=6)
    tree = [jnp.ones((SIZE, 3), jnp.float32), jnp.ones((SIZE, 2, 2), jnp.float32)]
    bf.win_create(tree, "fused")
    with _profile(tmp_path):
        _pushsum_round(x)
        bf.win_put(x, "w")
        bf.win_put_update(tree, "fused")
        bf.win_get("w")
    packed = SIZE * 6 * 4  # the packed f32 window
    nbytes = collections.defaultdict(list)
    for s in tl.spans():
        nbytes[s.name].append(s.nbytes)
    assert nbytes["win_accumulate"] == [packed]
    assert nbytes["win_set_exposed"] == [packed]
    assert nbytes["win_put"] == [packed]
    assert nbytes["win_put_update"] == [SIZE * (3 + 4) * 4]
    for name in ("win_update", "win_associated_p", "win_get", "win_put/exchange",
                 "win_get/exchange", "win_put_update/put_update"):
        assert nbytes[name] == [0], name


def test_cast_gets_a_span_only_when_it_converts(tmp_path):
    x = _pushsum_window()
    with _profile(tmp_path):
        bf.win_put(x, "w")
        bf.win_put(x.astype(jnp.bfloat16), "w")
    names = [s.name for s in tl.spans()]
    assert names == ["win_put", "win_put/exchange",
                     "win_put", "win_put/cast", "win_put/exchange"]


def test_a_second_session_starts_from_an_empty_ring(tmp_path):
    with _profile(tmp_path / "a"):
        for _ in range(3):
            with tl.timeline_context("first"):
                pass
    assert [s.name for s in tl.spans()] == ["first"] * 3  # kept after the session
    with tl.timeline_context("between"):  # no session: not recorded
        pass
    with _profile(tmp_path / "b"):
        with tl.timeline_context("second"):
            pass
    assert [s.name for s in tl.spans()] == ["second"]


def test_the_ring_is_bounded(monkeypatch):
    writes = []
    writer = types.SimpleNamespace(
        _t0=0, record=lambda *a, **k: writes.append(a))
    monkeypatch.setattr(tl, "_writer", writer)  # as under BLUEFOG_TIMELINE
    monkeypatch.setattr(tl, "_ring", collections.deque(maxlen=8))
    for i in range(100):
        with tl.timeline_context(f"s{i}"):
            pass
    assert [s.name for s in tl.spans()] == [f"s{i}" for i in range(92, 100)]
    assert len(writes) == 100  # the Chrome file still gets every span
    assert tl._ring.maxlen == 8 and tl.RING >= 1024


def test_a_second_thread_gets_its_own_stack(tmp_path):
    inside, done = threading.Event(), threading.Event()

    def gossip():
        with tl.timeline_context("thread_op"):
            with tl.timeline_context("thread_op/child"):
                inside.set()
                assert done.wait(timeout=30)

    with _profile(tmp_path):
        t = threading.Thread(target=gossip)
        with tl.timeline_context("main_op"):
            t.start()
            assert inside.wait(timeout=30)
            with tl.timeline_context("main_op/child"):
                pass
            done.set()
            t.join(timeout=30)
            assert not t.is_alive()
    by_name = {s.name: s for s in tl.spans()}
    assert len(by_name) == 4
    assert by_name["main_op"].parent is None
    assert by_name["thread_op"].parent is None  # not main_op, open elsewhere
    assert by_name["main_op/child"].parent == by_name["main_op"].id
    assert by_name["thread_op/child"].parent == by_name["thread_op"].id


def test_bluefog_timeline_records_and_still_writes_the_chrome_file(
        tmp_path, monkeypatch):
    path = str(tmp_path / "trace.json")
    monkeypatch.setenv("BLUEFOG_TIMELINE", path)
    monkeypatch.setattr(tl, "_writer", None)  # not read yet
    monkeypatch.setattr(tl, "_ring", collections.deque(maxlen=tl.RING))
    x = _pushsum_window()
    _pushsum_round(x)
    _no_timeline_lookup(monkeypatch)
    _pushsum_round(x)
    names = [s.name for s in tl.spans()]  # recorded with no profiler at all
    assert names.count("win_update/combine") == 2 and len(names) == 14
    tl._get_writer().flush()
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    assert sorted(e["name"] for e in events) == sorted(names)
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in events)


def test_the_spans_are_in_the_profile_the_child_inside_its_parent(tmp_path):
    sys.path.insert(0, REPO)
    from chipbench import trace_reduce

    x = _pushsum_round(_pushsum_window())
    with _profile(tmp_path):
        _pushsum_round(x)
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    wanted = ("bluefog/win_update", "bluefog/win_update/combine")
    # chipbench's loader keeps the `python` line of /host:CPU, where its own
    # spans are too
    host = trace_reduce.load(path, wanted)["host"]
    (parent,) = [e for e in host if e[0] == wanted[0]]
    (child,) = [e for e in host if e[0] == wanted[1]]
    assert parent[1] <= child[1] <= child[2] <= parent[2]


def _module_name(lowered):
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def test_window_programs_keep_the_names_the_benchmark_matches():
    sys.path.insert(0, REPO)
    from chipbench import manifest

    job = manifest.load_module(
        os.path.join(REPO, "chipbench", "jobs", "eager_window_pushsum.py"))
    patterns = [re.compile(p) for p in job.WINDOW_PROGRAMS]
    bf.set_topology(tu.RingGraph(SIZE, connect_style=1))
    plan = basics.context().plan
    x = jnp.zeros((SIZE, 6), jnp.float32)
    mail = jnp.zeros((SIZE, 1, 6), jnp.float32)
    ver = jnp.zeros((SIZE, 1), jnp.int32)
    p, pm = jnp.ones((SIZE,), jnp.float32), jnp.zeros((SIZE, 1), jnp.float32)
    scales = jnp.ones((len(plan.classes), SIZE), jnp.float32)
    w, sw = jnp.ones((SIZE, 1), jnp.float32), jnp.ones((SIZE,), jnp.float32)
    lowered = {
        "exchange": (windows.EXCHANGE_PROGRAM, windows._build_exchange(
            plan, True, True).lower(x, mail, ver, p, pm, scales, scales)),
        "put_update": (windows.EXCHANGE_PROGRAM, windows._build_put_update(
            plan, True, True, jnp.float32).lower(
                x, mail, ver, p, pm, scales, scales, w, sw)),
        "combine": (windows.COMBINE_PROGRAM, jax.jit(
            windows._combine, static_argnames=("wdt", "with_p")).lower(
                x, mail, p, pm, w, sw, wdt=jnp.float32, with_p=True)),
    }
    for role, (pinned, low) in lowered.items():
        name = _module_name(low)
        assert name == pinned, role
        assert any(pat.match(name) for pat in patterns), (role, name)
    assert (windows.EXCHANGE_PROGRAM, windows.COMBINE_PROGRAM) == (
        "jit_spmd", "jit__combine")


@pytest.mark.parametrize("comm,mode,scopes", [
    (CommunicationType.neighbor_allreduce, "atc",
     ("forward_backward", "optimizer_update", "gossip_combine")),
    (CommunicationType.neighbor_allreduce, "awc",
     ("forward_backward", "optimizer_update", "gossip_combine")),
    (CommunicationType.allreduce, "atc",
     ("forward_backward", "gradient_allreduce", "optimizer_update")),
])
def test_the_lowered_step_carries_the_named_scopes(comm, mode, scopes):
    bf.set_topology(tu.ExponentialTwoGraph(SIZE))
    ctx = basics.context()

    def apply_fn(variables, x):
        return x @ variables["params"]["w"]

    gossips = comm == CommunicationType.neighbor_allreduce
    init_fn, step_fn = make_decentralized_train_step(
        apply_fn, optax.sgd(0.1, momentum=0.9), ctx.mesh,
        communication_type=comm, plan=ctx.plan if gossips else None, mode=mode)
    params = {"w": jnp.ones((SIZE, 4, 3), jnp.float32)}
    state = init_fn(params)
    x, y = jnp.ones((SIZE, 2, 4)), jnp.zeros((SIZE, 2), jnp.int32)
    text = jax.jit(step_fn).lower(params, {}, state, x, y).as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope  # e.g. "jit(step)/jvp(forward_backward)/..."
    # metadata only: nothing of a scope is in the program itself
    plain = jax.jit(step_fn).lower(params, {}, state, x, y).as_text()
    assert not any(scope in plain for scope in scopes)
    *_, loss, _ = step_fn(params, {}, state, x, y)
    assert np.isfinite(np.asarray(loss)).all()
