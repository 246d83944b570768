"""The stage and idle reduction (``scopes.py``) on the trace recorded on
one TPU v5 lite chip (``testdata/``, recorded before the tick program
named its stages) and on a hand-made XSpace."""
import gzip
import pathlib
import shutil

import pytest

from chipbench import scopes, xplane

TRACE = pathlib.Path(__file__).resolve().parent / "testdata" \
    / "metro_1k.churn.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "metro_1k.churn.xplane.pb"
    with gzip.open(TRACE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


def test_events_are_profile_data_events(recorded):
    """The protobuf parse gives every line's events as
    ``jax.profiler.ProfileData`` does: name, start and duration in ns."""
    from jax.profiler import ProfileData
    space = scopes.load(recorded)
    data = ProfileData.from_file(str(recorded))
    planes = list(data.planes)
    assert [p.name for p in planes] == [p.name for p in space.planes]
    n = 0
    for want_plane, plane in zip(planes, space.planes):
        names = {e.key: e.value.name for e in plane.event_metadata}
        for want_line, line in zip(want_plane.lines, plane.lines):
            want = [(ev.name, ev.start_ns, ev.duration_ns)
                    for ev in want_line.events]
            got = [(names[m], s, e - s) for m, s, e in scopes._events(line)]
            assert got == want, (plane.name, line.name)
            n += len(got)
    assert n > 10_000


def test_recorded_trace_stages_and_idle(recorded):
    sc = scopes.read(recorded)
    summary = xplane.summarize(recorded)
    assert sc.steps == summary.steps
    tick_s, _ = summary.module_s(scopes.PROGRAM)
    assert sc.program_ms == pytest.approx(tick_s * 1e3, rel=1e-12)
    # the program's while loop (the break replay) and the ops around it
    # carry no stage in a trace older than the scopes
    assert set(sc.stage_ms) == {scopes.UNSCOPED}
    assert 0.9 * sc.program_ms < sum(sc.stage_ms.values()) <= sc.program_ms
    idle_ms = (summary.window_s - summary.busy_s) * 1e3
    assert sum(sc.idle_ms.values()) == pytest.approx(idle_ms, rel=1e-6)
    # the longest gap's label (host event at its midpoint) holds most of
    # the idle time in both reductions
    top = max(sc.idle_ms, key=sc.idle_ms.get)
    assert summary.gaps[0][0].endswith(top)


def test_innermost_cuts_by_latest_start():
    events = [("outer", 0, 10), ("inner", 2, 5), ("late", 4, 12)]
    assert scopes._innermost(events, -1, 14) == [
        (-1, 0, None), (0, 2, "outer"), (2, 4, "inner"), (4, 5, "late"),
        (5, 10, "late"), (10, 12, "late"), (12, 14, None)]
    assert scopes._innermost([], 0, 3) == [(0, 3, None)]


def _space(device_ops, host_events, steps):
    """A one-device XSpace.  ``device_ops``: (name, tf_op or None, start,
    end) on ``XLA Ops``, module events named ``jit__...``.  Host events:
    (name, start, end) on one thread, with ``steps`` probe periods."""
    space = scopes.XSpace()
    host = space.planes.add(name="/host:CPU")
    dev = space.planes.add(name="/device:TPU:0")
    ids = {}

    def meta(plane, name, tf_op=None):
        key = (plane.name, name)
        if key not in ids:
            ids[key] = len(ids) + 1
            entry = plane.event_metadata.add(key=ids[key])
            entry.value.name = name
            if tf_op is not None:
                st = entry.value.stats.add(metadata_id=7)
                if tf_op.startswith("ref:"):
                    st.ref_value = 8
                else:
                    st.str_value = tf_op
        return ids[key]

    for key, name in ((7, "tf_op"), (8, "jit(_tick_impl)/fold/mul:")):
        dev.stat_metadata.add(key=key).value.name = name
    mods = dev.lines.add(name=xplane.MODULES_LINE, timestamp_ns=1000)
    ops = dev.lines.add(name=xplane.OPS_LINE, timestamp_ns=1000)
    for name, tf_op, s, e in device_ops:
        line = mods if name.startswith("jit__") else ops
        line.events.add(metadata_id=meta(dev, name, tf_op),
                        offset_ps=s * 1000, duration_ps=(e - s) * 1000)
    thread = host.lines.add(name="python3", timestamp_ns=1000)
    for name, s, e in [(xplane.STEP_NAME, s, e) for s, e in steps] \
            + host_events:
        thread.events.add(metadata_id=meta(host, name),
                          offset_ps=s * 1000, duration_ps=(e - s) * 1000)
    return scopes.XSpace.FromString(space.SerializeToString())


def test_hand_made_space_stages_and_idle():
    tick = "jit(_tick_impl)"
    device_ops = [
        ("jit__tick_impl(1)", None, 100, 200),
        ("%while.1 = while(%a)", f"{tick}/deaths/while", 100, 150),
        ("%fusion.2 = fusion(%b)", f"{tick}/deaths/while/body/gather:",
         105, 125),
        ("%fusion.3 = fusion(%c)", f"{tick}/deaths/while/body/add:",
         130, 140),
        ("%fusion.4 = fusion(%d)", "ref:", 150, 170),          # fold
        ("%fusion.5 = fusion(%e)", f"{tick}/refresh/top_k:", 170, 185),
        ("%copy.6 = copy(%f)", None, 185, 190),                # unscoped
        ("%fusion.7 = fusion(%g)", f"{tick}/switch/select_n:", 190, 200),
        ("jit__traffic_impl(2)", None, 230, 260),
        ("%fusion.8 = fusion(%h)", "jit(_traffic_impl)/mul:", 230, 260),
    ]
    host_events = [("fused_tick", 90, 215), ("fused_tick.wait", 110, 200),
                   ("transport", 215, 232), ("transport.push", 220, 231),
                   ("Captain.fail", 262, 275)]
    space = _space(device_ops, host_events, steps=[(50, 150), (150, 300)])
    sc = scopes.reduce(space)
    assert sc.steps == 2
    assert sc.program_ms == pytest.approx(100e-6)
    assert sc.stage_ms == pytest.approx({
        "deaths": 50e-6, "fold": 20e-6, "refresh": 15e-6,
        "unscoped": 5e-6, "switch": 10e-6})
    # idle on the device: 50-100 (50), 200-230 (30), 260-300 (40)
    assert sc.idle_ms == pytest.approx({
        "unnamed": 40e-6 + 2e-6 + 25e-6, "fused_tick": 10e-6 + 15e-6,
        "transport": 5e-6, "transport.push": 10e-6,
        "Captain.fail": 13e-6})
    assert sum(sc.idle_ms.values()) == pytest.approx(120e-6)
    assert sc.per_step({"a": 3.0}) == {"a": 1.5}


def test_trace_without_steps_or_device_raises():
    with pytest.raises(ValueError, match="step"):
        scopes.reduce(_space([], [], steps=[]))
    space = _space([], [], steps=[(0, 10)])
    del space.planes[1]
    with pytest.raises(ValueError, match="device"):
        scopes.reduce(space)


def test_split_per_period():
    from chipbench import trace_split
    sc = scopes.Scopes(
        steps=2, program_ms=10.0,
        stage_ms={"deaths": 6.0, "fold": 2.0, scopes.UNSCOPED: 1.0},
        idle_ms={scopes.UNNAMED: 1.0, "transport.admit": 3.0})
    out = trace_split.split(sc, {"breaks": 4, "h2d_bytes": 1_000_000},
                            {"breaks": 10, "h2d_bytes": 5_000_000,
                             "d2h_bytes": 2_000_000})
    assert out["periods"] == 2 and out["program_ms"] == 5.0
    assert out["stage_ms"] == {"deaths": 3.0, "fold": 1.0,
                               scopes.UNSCOPED: 0.5}
    assert out["stage_share"] == pytest.approx(0.9)
    assert list(out["idle_ms"]) == ["transport.admit", scopes.UNNAMED]
    assert out["idle_unnamed_share"] == 0.25
    assert out["counts"] == {"breaks": 3.0, "h2d_bytes": 2e6,
                             "d2h_bytes": 1e6}
    assert (out["h2d_mb"], out["d2h_mb"]) == (2.0, 1.0)
    assert out["replay_ms_per_break"] == 1.0
    # a program that counts nothing (an older tree) leaves those out
    bare = trace_split.split(sc, {}, {})
    assert bare["h2d_mb"] is None and bare["replay_ms_per_break"] is None
