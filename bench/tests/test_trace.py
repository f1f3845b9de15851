"""The trace reduction, on a trace recorded on one TPU v5e chip by
``record_trace.py`` (three rounds: a 20 ms ``bench.input`` sleep, a
2048 x 2048 matmul, the Pallas Eq. 2 wire kernel on a (2, 2**20) buffer,
two fetches) and on hand-built event lists."""
import os

import pytest

from bench import trace

PATH = os.path.join(os.path.dirname(__file__), "data", "one_chip.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return trace.load(PATH)


def test_spans_and_window(recorded):
    names = [s.name for s in recorded.spans]
    assert names.count("bench.round") == 3
    assert names.count("bench.input") == 3
    assert names.count("bench.fetch") == 3
    lo, hi = trace.window(recorded)
    rounds = [s for s in recorded.spans if s.name == "bench.round"]
    assert (lo, hi) == (rounds[0].start, rounds[-1].end)
    assert list(recorded.devices) == [0]


def test_busy_and_idle(recorded):
    lo, hi = trace.window(recorded)
    busy = trace.busy_seconds(recorded)
    # each round: a ~92 us matmul fusion, a ~64 us kernel, small reductions
    assert 0.3e-3 < busy < 3e-3
    idle = trace.measure(trace.idle_gaps(recorded, 0))
    assert idle == pytest.approx((hi - lo) - busy, rel=1e-9)


def test_breakdown_names_ops_and_gaps(recorded):
    b = trace.breakdown(recorded)
    ops = dict(b["device_ops"])
    assert "convolution_tanh_fusion" in ops
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    # the two longest gaps lie in the first round's fetch (it compiled the
    # reductions); the three 20 ms input sleeps come next
    gaps = b["idle_gaps"]
    assert [n for n, _ in gaps[:5]] == ["bench.fetch"] * 2 + [
        "bench.input"] * 3
    assert gaps[0][1] > 0.09
    assert all(0.018 < s < 0.023 for _, s in gaps[2:5])


def test_kernel_time_per_round(recorded):
    # three calls of the wire kernel, 63.87 us each on the device
    ms = trace.ms_per_round(recorded, r"tpu_custom_call")
    assert ms == pytest.approx(0.063867, rel=1e-3)
    assert trace.ms_per_round(recorded, r"no-such-op") is None


def test_round_gaps_use_the_main_module(recorded):
    assert trace.main_module(recorded).startswith("jit__lambda")
    gaps = trace.round_gaps(recorded)[0]
    assert len(gaps) == 2 and all(g > 0.015 for g in gaps)


def _ev(name, a, b):
    return trace.Event(name, a, b, name)


def test_collectives_exposed_by_hand():
    tr = trace.Trace(
        devices={0: [_ev("fusion.1", 0.0, 1.0),
                     _ev("all-reduce-start.1", 1.0, 1.1),
                     _ev("fusion.2", 1.1, 1.5),
                     _ev("all-reduce-done.1", 1.5, 1.8)],
                 1: [_ev("fusion.1", 0.0, 1.2), _ev("all-reduce.3", 1.2, 1.6)]},
        asyncs={0: [_ev("all-reduce-start.1", 1.0, 1.8)]},
        modules={},
        spans=[trace.Event("bench.round", 0.0, 2.0)])
    total0, exposed0 = trace.collective_seconds(tr, 0)
    assert total0 == pytest.approx(0.8)        # the async span 1.0-1.8
    assert exposed0 == pytest.approx(0.4)      # less fusion.2's 0.4
    assert trace.collective_seconds(tr, 1) == (pytest.approx(0.4),
                                               pytest.approx(0.4))


def test_collectives_by_opcode():
    """A ``shard_map`` psum keeps the name ``psum`` and is an all-reduce by
    its opcode; an operation that only reads an all-reduce's result is
    none."""
    psum = trace.Event("psum.77", 0.0, 0.5,
                       "%psum.77 = f32[1,8]{1,0} all-reduce(f32[1,8]{1,0} "
                       "%get-tuple-element.3), channel_id=1")
    fusion = trace.Event("fusion.9", 0.5, 0.7,
                         "%fusion.9 = f32[8]{0} fusion(f32[8]{0} "
                         "%all-reduce.11), kind=kLoop")
    start = trace.Event("ar.2", 0.7, 0.8, "%ar.2 = (f32[8]{0}, f32[8]{0}) "
                        "all-reduce-start(f32[8]{0} %p), channel_id=2")
    assert trace.is_collective(psum) and trace.is_collective(start)
    assert not trace.is_collective(fusion)
    tr = trace.Trace(devices={0: [psum, fusion, start]}, asyncs={},
                     modules={}, spans=[trace.Event("bench.round", 0.0, 1.0)])
    assert trace.collective_seconds(tr, 0) == (pytest.approx(0.6),
                                               pytest.approx(0.6))


def test_interval_arithmetic():
    assert trace.union([(2, 3), (0, 1), (0.5, 2.5)]) == [(0, 3)]
    assert trace.subtract([(0, 10)], [(1, 2), (5, 6)]) == [(0, 1), (2, 5),
                                                           (6, 10)]
    assert trace.clip([(0, 4), (5, 9)], 2, 6) == [(2, 4), (5, 6)]
    gap = (1.0, 2.0)
    spans = [trace.Event("bench.round", 0.0, 3.0),
             trace.Event("bench.input", 0.9, 2.1)]
    assert trace.attribute(gap, spans) == "bench.input"


def test_loop_seconds_take_the_outer_loops():
    tr = trace.Trace(
        devices={0: [_ev("while.391", 0.0, 1.0), _ev("while.395", 0.2, 0.5),
                     _ev("fusion.7", 1.0, 1.5), _ev("while.12", 1.6, 1.8),
                     _ev("while_body_fusion", 1.8, 1.9)]},
        asyncs={}, modules={},
        spans=[trace.Event("bench.round", 0.0, 2.0)])
    assert trace.loop_seconds(tr, 0) == pytest.approx(1.2)
    assert trace.loop_seconds(tr, 1) == 0
