"""Host spans and counters (``repro.core.spans``) and the simulator's
per-event profiler spans."""
import functools
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sim import Simulator, _handler_name
from repro.core.spans import Spans


def test_span_accumulates_ms_under_its_name():
    ms, counts = {}, {}
    spans = Spans(ms, counts)
    for _ in range(3):
        with spans.span("outer"):
            with spans.span("outer.inner"):
                pass
    assert set(ms) == {"outer", "outer.inner"}
    assert ms["outer"] >= ms["outer.inner"] > 0
    assert counts == {}


def test_span_records_time_when_the_block_raises():
    spans = Spans({}, {})
    try:
        with spans.span("failing"):
            raise KeyError("x")
    except KeyError:
        pass
    assert spans.ms["failing"] >= 0


def test_counters_count_host_bytes_and_device_reads():
    spans = Spans({}, {})
    host = np.zeros((4, 3), np.float32)
    spans.h2d(host, np.int32(7), 0.4, jnp.ones(1000))
    assert spans.counts["h2d_bytes"] == host.nbytes + 4
    dev = jnp.arange(10, dtype=jnp.int32)
    out = spans.d2h(dev)
    assert isinstance(out, np.ndarray)
    np.testing.assert_array_equal(out, np.arange(10))
    spans.d2h(dev[:2])
    assert spans.counts["d2h_bytes"] == 48
    spans.count("breaks", 3)
    spans.count("breaks", 0)
    assert spans.counts["breaks"] == 3


class _Node:
    def fail(self, log):
        log.append("fail")


def _recover(log):
    log.append("recover")


def test_handler_names():
    assert _handler_name(_Node().fail) == "_Node.fail"
    assert _handler_name(_recover) == "_recover"
    assert _handler_name(functools.partial(_recover)) == "partial"


def test_spans_and_simulator_events_reach_the_profiler_trace(tmp_path):
    """Each simulator event runs inside a profiler span named after its
    handler, and a ``Spans`` span lands in the same trace."""
    log = []
    spans = Spans({}, {})
    sim = Simulator(seed=0)
    sim.at(1.0, _Node().fail, log)
    sim.at(2.0, _recover, log)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("transport.admit"):
            sim.run()
    finally:
        jax.profiler.stop_trace()
    assert log == ["fail", "recover"]
    path, = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    names = {ev.name for plane in data.planes for line in plane.lines
             for ev in line.events}
    assert {"_Node.fail", "_recover", "transport.admit"} <= names
