"""The trace reduction (``xplane.py``) on a short trace recorded on one
TPU v5 lite chip (``testdata/``: a few probe periods of
``metro_1k.churn``), and on hand-made intervals."""
import gzip
import pathlib
import shutil

import pytest

from chipbench import xplane

TRACE = pathlib.Path(__file__).resolve().parent / "testdata" \
    / "metro_1k.churn.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "metro_1k.churn.xplane.pb"
    with gzip.open(TRACE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return xplane.summarize(path)


def test_union_and_self_times():
    merged = xplane._union([(5, 9), (0, 2), (1, 3), (9, 10), (12, 13)])
    assert merged == [[0, 3], [5, 10], [12, 13]]
    loop = "%while.3 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %t)"
    body = "%fusion.7 = f32[8]{0:T(128)} fusion(f32[8]{0} %p), kind=kLoop"
    assert xplane.op_label(loop) == "%while.3 while"
    assert xplane.self_times([(loop, 0, 10), (body, 2, 5), (body, 6, 7),
                              ("%copy.1 = f32[8] copy(f32[8] %a)", 12, 13)]
                             ) == {"%while.3 while": 6,
                                   "%fusion.7 fusion": 4,
                                   "%copy.1 copy": 1}


def test_window_busy_and_steps(summary):
    assert summary.steps >= 2
    assert 0 < summary.busy_s <= summary.window_s
    assert summary.window_s < 60


def test_programs_run_once_per_period(summary):
    tick_s, tick_n = summary.module_s("jit__tick_impl")
    traffic_s, traffic_n = summary.module_s("jit__traffic_impl")
    assert tick_n == traffic_n == summary.steps
    # every op runs inside one of the two programs
    assert summary.busy_s <= tick_s + traffic_s <= summary.window_s
    assert summary.module_s("no_such_module") is None


def test_top_ops_and_gaps(summary):
    ops = summary.top_ops
    assert 0 < len(ops) <= xplane.TOP
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert sum(t for _, t in ops) <= summary.busy_s * (1 + 1e-9)
    gaps = summary.gaps
    assert 0 < len(gaps) <= xplane.TOP
    assert [t for _, t in gaps] == sorted((t for _, t in gaps),
                                          reverse=True)
    idle = summary.window_s - summary.busy_s
    assert sum(t for _, t in gaps) <= idle * (1 + 1e-9)
    assert all(label for label, _ in gaps)
