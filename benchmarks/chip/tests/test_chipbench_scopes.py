"""The scope reduction (``scopes.py``): its protobuf reader finds each
device op's name stack in the op's own metadata, its rule gives an op the
innermost named scope of that stack, and on a trace of the FedaGrac round
recorded on one TPU v5e (``data/scopes.xplane.pb``, made by
``record_scopes_trace.py``: granite-moe cut to d 256, 1 layer, 8 experts
top-2, seq 128, one 2-round chunk) it accounts for the round."""
import os

import chipbench_tiny  # noqa: F401  (paths)
import pytest

import harness

DATA = os.path.join(chipbench_tiny.HERE, "data")
KERNELS = os.path.join(DATA, "kernels.xplane.pb")
SCOPED = os.path.join(DATA, "scopes.xplane.pb")
SPANS = ("fed.inputs", "fed.dispatch", "fed.wait", "fed.history")


@pytest.fixture(scope="module")
def sc():
    return harness.load_module(".", "scopes")


@pytest.fixture(scope="module")
def red(sc):
    return sc.reduce(SCOPED)


def test_reader_finds_the_name_stack_in_the_op_metadata(sc):
    ops, = sc.read_planes(KERNELS)["devices"]
    tf_ops = {name.split(" ")[0]: tf_op for _, _, name, tf_op in ops}
    assert tf_ops["%calibrated_update.1"] == (
        "jit(<lambda>)/jit(calibrated_update_2d)/calibrated_update/"
        "pallas_call:")


def test_reader_gives_the_events_profile_data_gives(sc):
    """The same device ops and host spans, at the same nanoseconds, as
    ``jax.profiler.ProfileData`` reads from the file."""
    import jax
    data = jax.profiler.ProfileData.from_file(KERNELS)
    dev, host = [], []
    for plane in data.planes:
        for line in plane.lines:
            evs = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                    e.name) for e in line.events]
            if plane.name.startswith("/device:TPU:") and \
                    line.name == "XLA Ops":
                dev += evs
            elif plane.name.startswith("/host:"):
                host += evs
    got = sc.read_planes(KERNELS)
    assert [e[:3] for e in got["devices"][0]] == dev
    assert sorted(got["host"]) == sorted(host)


@pytest.mark.parametrize("tf_op,scope", [
    # forward, under jvp
    ("jit(chunk_fn)/while/body/fed.client_update/jvp(moe.dispatch)/tanh:",
     "moe.dispatch"),
    # backward: the transpose of the client update, the block under remat
    ("jit(chunk_fn)/transpose(jvp(fed.client_update))/jvp()/checkpoint/"
     "moe.dispatch/mul", "moe.dispatch"),
    # recomputation of the forward inside the backward
    ("jit(chunk_fn)/fed.client_update/checkpoint/rematted_computation/"
     "moe.dispatch/dot_general", "moe.dispatch"),
    # a transform around the scope itself, and a vmap around both
    ("jit(chunk_fn)/while/body/closed_call/fed.client_update/while/body/"
     "closed_call/vmap(transpose(jvp(model.head)))/bsd,vd->bsv/dot_general",
     "model.head"),
    ("jit(chunk_fn)/fed.client_update/vmap(fed.flat_boundary)/"
     "convert_element_type", "fed.flat_boundary"),
    # a parenthesised component that is no scope
    ("jit(f)/fed.client_update/model.attention/jit(searchsorted)/gather",
     "model.attention"),
    ("jit(chunk_fn)/while/body/fed.orientation/fed.orientation/max",
     "fed.orientation"),
    ("jit(chunk_fn)/while/body/add:", None),
    ("jit(<lambda>)/jit(calibrated_update_2d)/calibrated_update/"
     "pallas_call:", None),
])
def test_an_op_belongs_to_its_innermost_scope(sc, tf_op, scope):
    got = sc.scopes_of(tf_op)
    assert (got[-1] if got else None) == scope
    if scope is not None and scope != "fed.client_update" \
            and "fed.client_update" in tf_op:
        assert got[0] == "fed.client_update"


def test_unscoped_trace_has_no_scope(sc):
    """A program without scopes (the kernels' trace) reads all unscoped."""
    red = sc.reduce(KERNELS)
    assert set(red["scopes"]) == {"unscoped"}
    tr = harness.load_module(".", "trace").reduce(KERNELS)
    assert red["scopes"]["unscoped"] == pytest.approx(tr["busy_s"],
                                                      rel=0.2)
    assert red["program_spans"]["seconds"] == {}


def test_every_scope_is_read(red, sc):
    for scope in sc.SCOPES:
        assert red["scopes"].get(scope, 0) > 0, scope


def test_unscoped_share_is_small(red):
    s = red["scopes"]
    total = s["fed.client_update"] + s["fed.aggregate"] + \
        s["fed.orientation"] + s.get("unscoped", 0.0)
    assert s.get("unscoped", 0.0) < 0.03 * total


def test_a_scope_includes_its_sub_scopes(red, sc):
    s = red["scopes"]
    inner = [x for x in sc.SCOPES if x.startswith(("model.", "moe."))]
    inner += ["fed.local_step", "fed.flat_boundary"]
    assert sum(s[x] for x in inner) <= s["fed.client_update"] * (1 + 1e-9)
    assert s["fed.client_update"] > 0.5 * sum(s.values())


def test_scoped_time_is_the_busy_time(red):
    """Round stages and unscoped ops add up to the device's busy time."""
    s = red["scopes"]
    tr = harness.load_module(".", "trace").reduce(SCOPED)
    total = s["fed.client_update"] + s["fed.aggregate"] + \
        s["fed.orientation"] + s.get("unscoped", 0.0)
    assert total == pytest.approx(tr["busy_s"], rel=0.03)


def test_chunk_spans(red):
    spans = red["program_spans"]["seconds"]
    assert len(spans["fed.chunk"]) == 1
    for name in SPANS:
        assert len(spans[name]) == 1, name
    assert sum(spans[n][0] for n in SPANS) <= spans["fed.chunk"][0]
    idle = red["program_spans"]["idle_s"]
    tr = harness.load_module(".", "trace").reduce(SCOPED)
    assert sum(idle.values()) == pytest.approx(
        tr["window_s"] - tr["busy_s"], rel=1e-6, abs=1e-9)
