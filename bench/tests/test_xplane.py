"""The XSpace reader and the per-scope reduction: on a hand-made protobuf
with known answers, on the chip trace recorded before the program had
layer scopes (``fleet_trace.xplane.pb.gz``), and on one recorded with them
(``fleet_trace_scoped.xplane.pb.gz``, ``record_trace.py``)."""
from pathlib import Path

import pytest

from bench import trace_reduce, xplane
from repro.obs.trace import SCOPES

DATA = Path(__file__).parent / "data"
OLD = DATA / "fleet_trace.xplane.pb.gz"
SCOPED = DATA / "fleet_trace_scoped.xplane.pb.gz"
ONE_CHIP_SCOPES = sorted(set(SCOPES) - {"seeker.collectives"})
MS = 10 ** 9   # ps


# --- a hand-made XSpace ------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """Encode (field number, int | str | bytes) pairs."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def _plane(name, lines, events, stats=()):
    """XPlane: lines of (name, [(metadata id, start ms, duration ms)]),
    event metadata {id: (name, [XStat])}, stat metadata [(id, name)]."""
    fields = [(2, name)]
    for lname, evs in lines:
        fields.append((3, _msg((2, lname), (3, 1_000_000), *[
            (4, _msg((1, mid), (2, int(s * MS)), (3, int(d * MS))))
            for mid, s, d in evs])))
    for mid, (ename, st) in events.items():
        fields.append((4, _msg((1, mid), (2, _msg(
            (1, mid), (2, ename), *[(5, x) for x in st])))))
    for sid, sname in stats:
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    return _msg(*fields)


def _space() -> bytes:
    host = _plane("/host:CPU", [("python", [
        (1, 0, 100), (2, 0, 58), (3, 0, 5), (4, 5, 53), (5, 58, 37),
        (6, 0, 100)])], {
        1: ("bench.window", []), 2: ("bench.dispatch", []),
        3: ("fleet.prep", []), 4: ("fleet.run", []), 5: ("bench.block", []),
        6: ("other", [])})
    tf_op = lambda text: _msg((1, 9), (5, text))          # noqa: E731
    body = "jit(run)/while/body/closed_call/"
    dev = _plane("/device:TPU:0", [("XLA Ops", [
        (1, 5, 45), (2, 5, 25), (7, 5, 0), (3, 30, 20), (4, 60, 10),
        (5, 72, 2), (6, 95, 25)])], {
        1: ("%while.1 = (f32[8]{0}) while(%t)", [tf_op("jit(run)/while")]),
        2: ("%fusion.a = f32[8]{0} fusion(%x)", [tf_op(
            body + "vmap(seeker.recover_cluster)/jit(sort)/sort")]),
        # a string stat kept by reference to a stat metadata's name
        3: ("%fusion.b = f32[8]{0} fusion(%y)", [_msg((1, 9), (7, 12))]),
        4: ("%signature_corr_pallas.3 = f32[8,12]{1,0} custom-call(%w)",
            [tf_op(body + "seeker.corr/pallas_call")]),
        5: ("%convert.1 = f32[8]{0} convert(%z)",
            [tf_op(body + "seeker.corrected/convert")]),
        6: ("%fusion.c = s32[4]{0} fusion(%u)", [tf_op("jit(pick)/gather")]),
        7: ("%custom-call.9 = f32[8]{0} custom-call()", [])},
        stats=[(9, "tf_op"),
               (12, body + "seeker.lanes/vmap(seeker.decide)/add")])
    return _msg((1, host), (1, dev))


def test_hand_made_space_known_answers():
    space = xplane.parse(_space())
    ops = {e.name.split(" = ")[0]: e for p in space.planes
           for ln in p.lines for e in ln.events if e.name.startswith("%")}
    assert ops["%fusion.b"].tf_op.endswith("vmap(seeker.decide)/add")
    assert ops["%fusion.a"].start_ns == 1_000_000 + 5e6
    assert ops["%fusion.a"].duration_ns == 25e6
    r = xplane.reduce(space, SCOPES)
    # the innermost scope wins; a name running on into more name
    # characters is no match; [95, 120] ms is clipped to the window; the
    # zero-length op at 5 ms does not make %fusion.a a container
    assert r["scope_s"] == pytest.approx({
        **{s: 0.0 for s in SCOPES}, "seeker.recover_cluster": 0.025,
        "seeker.decide": 0.020, "seeker.corr": 0.010, "unscoped": 0.007})
    # gaps [0, 5] under fleet.prep, [50, 60] under fleet.run (inside
    # bench.dispatch), [70, 72] and [74, 95] under bench.block
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"fleet.prep": 0.005, "fleet.run": 0.010, "bench.block": 0.023})
    # the scopes partition the busy time: [5, 50] + [60, 70] + [72, 74]
    # + [95, 100] ms
    assert sum(r["scope_s"].values()) == pytest.approx(
        trace_reduce.reduce(space)["busy_s"])


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(run)/while/body/closed_call/vmap(seeker.recover_cluster)/"
     "jit(sort)/sort", "seeker.recover_cluster"),
    ("jit(run)/seeker.lanes/vmap(vmap(seeker.decide))/add", "seeker.decide"),
    ("jit(run)/seeker.corrected/x", "unscoped"),
    ("", "unscoped")])
def test_innermost_scope(tf_op, scope):
    assert xplane.innermost_scope(tf_op, xplane.scope_pattern(SCOPES)) \
        == scope


def test_no_window_span_is_an_error():
    space = xplane.parse(_space())
    space.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        xplane.reduce(space, SCOPES)


# --- recorded chip traces ------------------------------------------------------

@pytest.fixture(scope="module")
def old_trace():
    return xplane.load(str(OLD))


def test_reads_what_profile_data_reads(old_trace):
    """Same planes, lines and events, with the same names and times, as
    ``ProfileData``; so every number ``trace_reduce`` gives is unchanged."""
    pd = trace_reduce.load(str(OLD))
    for mine, theirs in zip(old_trace.planes, pd.planes, strict=True):
        assert mine.name == theirs.name
        for a, b in zip(mine.lines, theirs.lines, strict=True):
            assert a.name == b.name
            assert [e[:3] for e in a.events] == [
                (e.name, e.start_ns, e.duration_ns) for e in b.events]
    assert trace_reduce.reduce(old_trace, n_devices=1) \
        == trace_reduce.reduce(pd, n_devices=1)


def _partitions_busy_time(r, old):
    """The scopes and the unscoped rest sum to the device's busy time, up
    to what a ``while`` spends outside its body's ops; they never count
    less than the leaf ops of ``trace_reduce``, which drops an op that a
    zero-length op starts in."""
    total = sum(r["scope_s"].values())
    assert sum(old["op_s"].values()) <= total <= old["busy_s"]
    assert total == pytest.approx(old["busy_s"], rel=0.01)


def test_unscoped_trace_reads_all_unscoped(old_trace):
    old = trace_reduce.reduce(old_trace, n_devices=1)
    r = xplane.reduce(old_trace, SCOPES, n_devices=1)
    assert r["idle_gaps"] == old["idle_gaps"]
    assert set(r["scope_s"]) == set(SCOPES) | {"unscoped"}
    assert not any(r["scope_s"][s] for s in SCOPES)
    _partitions_busy_time(r, old)


@pytest.fixture(scope="module")
def scoped_trace():
    return xplane.load(str(SCOPED))


def test_scoped_trace_names_every_one_chip_layer(scoped_trace):
    r = xplane.reduce(scoped_trace, SCOPES, n_devices=1)
    old = trace_reduce.reduce(scoped_trace, n_devices=1)
    for name in ONE_CHIP_SCOPES:
        assert r["scope_s"][name] > 0.0, name
    assert r["scope_s"]["seeker.collectives"] == 0.0
    _partitions_busy_time(r, old)
    assert any(n.startswith("fleet.") for n, _ in r["idle_gaps"])


@pytest.mark.parametrize("kernel, scope", [
    ("%signature_corr_pallas", "seeker.corr"),
    ("fake_quant_pallas", "seeker.edge_dnn")])
def test_scoped_trace_kernels_under_their_layer(scoped_trace, kernel, scope):
    pattern = xplane.scope_pattern(SCOPES)
    found = {xplane.innermost_scope(e.tf_op, pattern)
             for p in scoped_trace.planes if p.name.startswith("/device:")
             for ln in p.lines if ln.name == "XLA Ops"
             for e in ln.events if kernel in e.name.split(" = ")[0]}
    assert found == {scope}
