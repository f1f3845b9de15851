"""The program's names in a trace (``bench.scopes``) and the readers of the
metrics that use them, on hand-built events and on a trace of the
program's own round recorded on one TPU v5e chip by
``record_round_trace.py``."""
import collections
import gzip
import os
import shutil
import statistics

import jax
import pytest

from bench import drive, scopes, spec, trace
from bench.tests import tiny

HERE = os.path.dirname(__file__)
#: ``record_round_trace.py``'s recording, gzip-compressed
GZ = os.path.join(HERE, "data", "colearn_round.xplane.pb.gz")


def _ev(name, a, b):
    return trace.Event(name, a, b, name)


def hand_built(shift=0.0):
    """One device, two rounds of 10 s each: a loop op, which the trace
    gives no op name, with two overlapping scoped ops inside it, the wire,
    the Eq. 4 reduction, one copy without an op name, and the program's
    spans, whose clock runs ``shift`` seconds late."""
    ops, names, mods, prog, bench = [], [], [], [], []
    for r in range(2):
        t = 10.0 * r
        ops += [_ev("while.1", t + 1, t + 5), _ev("fusion.2", t + 1, t + 3),
                _ev("fusion.5", t + 2.5, t + 5),
                _ev("eq2_flat_mean.1", t + 5, t + 6),
                _ev("reduce.3", t + 6, t + 6.5), _ev("copy.4", t + 6.5, t + 7)]
        names += ["",
                  "jit(fn)/local_step/while/body/dot_general:",
                  "jit(fn)/local_step/while/body/add:",
                  "jit(fn)/eq2_wire/eq2_flat_mean/pallas_call:",
                  "jit(fn)/eq4_rel/reduce_sum:", ""]
        mods.append(_ev("jit_fn", t + 1, t + 7))
        bench.append(trace.Event("bench.round", t + 0.5, t + 8))
        s = shift
        prog += [trace.Event("colearn.round", t + 0.6 + s, t + 7.9 + s),
                 trace.Event("colearn.input", t + 0.6 + s, t + 0.9 + s),
                 trace.Event("colearn.dispatch", t + 0.9 + s, t + 1.0 + s),
                 trace.Event("colearn.fetch", t + 1.0 + s, t + 7.0 + s),
                 trace.Event("colearn.finish", t + 7.0 + s, t + 7.9 + s)]
    tr = trace.Trace(devices={0: ops}, asyncs={}, modules={0: mods},
                     spans=bench)
    return scopes.Scoped(tr, prog, {0: names})


def test_in_scope_takes_whole_components():
    assert scopes.in_scope("jit(fn)/local_step/while", "local_step")
    assert scopes.in_scope("jit(fn)/eq2_wire/pallas_call:", "eq2_wire")
    assert not scopes.in_scope("jit(fn)/eq2_wire_x/add:", "eq2_wire")
    assert not scopes.in_scope("jit(fn)/add:local_step", "local_step")
    assert not scopes.in_scope("", "local_step")


def test_scope_seconds_by_hand():
    sc = hand_built()
    # the two body ops overlap: their union, 4 s a round, not 4.5
    assert scopes.scope_seconds(sc, "local_step", 0) == pytest.approx(8.0)
    assert scopes.scope_seconds(sc, "eq2_wire", 0) == pytest.approx(2.0)
    assert scopes.scope_seconds(sc, "eq4_rel", 0) == pytest.approx(1.0)
    assert scopes.scope_ms(sc, "local_step") == pytest.approx(4000.0)
    assert scopes.scope_ms(sc, "no_such_scope") is None
    # the unnamed loop op lies under scoped ops and adds nothing
    assert scopes.unscoped_ops(sc, 0) == [("copy.4", pytest.approx(1.0))]


def test_host_time_is_the_round_less_its_fetch():
    # 7.3 s of round less 6 s of fetch, in both rounds
    assert scopes.host_ms_per_round(hand_built()) == pytest.approx(1300.0)


def test_skew_within_tolerance_is_left_as_recorded():
    sc = hand_built(shift=0.2e-3)
    assert scopes.clock_skew(sc) == [pytest.approx(0.2e-3)] * 2
    before = [(s.start, s.end) for s in sc.program]
    scopes.align(sc)
    assert sc.shift_s == 0.0
    assert [(s.start, s.end) for s in sc.program] == before


def test_skew_beyond_tolerance_shifts_the_program_spans():
    sc = scopes.align(hand_built(shift=3e-3))
    assert sc.shift_s == pytest.approx(3e-3)
    assert scopes.clock_skew(sc) == [pytest.approx(0.0, abs=1e-12)] * 2
    assert sc.program[0].start == pytest.approx(0.6)


def test_gaps_are_named_by_the_innermost_span():
    b = scopes.breakdown(hand_built())
    names = {n for n, _ in b["idle_gaps"]}
    # the device idles in input and dispatch (0.5 s) and in finish (1 s)
    assert "colearn.finish" in names and "colearn.input" in names
    assert "bench.round" not in {n for n, s in b["idle_gaps"] if s > 0.4}
    # the benchmark's own reading names the same gaps by its round span
    assert {n for n, _ in trace.breakdown(hand_built().trace)[
        "idle_gaps"]} == {"bench.round"}


def test_readers_need_the_trace_file():
    for name in ("local_step_ms", "eq2_wire_ms", "eq4_rel_ms"):
        assert spec.metric_reader(name)({"trace_path": None}) is None


def test_round_host_ms_reads_the_programs_rounds_of_the_window():
    """A tiny cell on the CPU, built and driven as the benchmark drives
    it: the reader averages the program's own records of the window's
    rounds, and only those."""
    from repro.analysis import spans
    cell = drive.build(tiny.config(), tiny.traffic(), 5, jax.devices()[:1])
    drive.setup_rounds(cell)
    times, _, _ = drive.window(cell, 0.5)
    own = [r for r in spans.recent()
           if times[0][0] <= r.start and r.end <= times[-1][1]]
    assert len(own) == len(times)
    for r, (t0, t1) in zip(own, times):
        assert t0 <= r.start <= r.end <= t1
        assert r.host_ms["colearn.fetch"] <= r.host_ms["colearn.round"]
    want = sum(r.host_ms["colearn.round"] - r.host_ms["colearn.fetch"]
               for r in own) / len(own)
    got = spec.metric_reader("round_host_ms")({"times": times})
    assert got == pytest.approx(want) and got > 0
    # a window the program ran no round in reads nothing
    assert spec.metric_reader("round_host_ms")(
        {"times": [(times[-1][1] + 1, times[-1][1] + 2)]}) is None


# ---------------------------------------------------------------------------
# the recorded round: three int8 rounds, then three exact ones
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def recorded_path(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / "colearn_round.xplane.pb"
    with gzip.open(GZ, "rb") as f, open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(out)


@pytest.fixture(scope="module")
def recorded(recorded_path):
    return scopes.load(recorded_path)


def test_conversion_keeps_every_operation(recorded, recorded_path):
    """Every device operation ProfileData reads has its twin, by HLO text,
    in the trace_viewer conversion; those the compiler added (copies,
    async halves) and the loop ops carry no op name there."""
    conv = scopes.viewer_ops(recorded_path)[0]
    seen = collections.Counter(e.text for e in recorded.trace.devices[0])
    assert seen == {t: len(v) for t, v in conv.items()}
    named, total = scopes.coverage(recorded)[0]
    assert total == 3918 and named == 3126
    loops = [t for e, t in zip(recorded.trace.devices[0], recorded.tf_ops[0])
             if trace.LOOP.match(e.name)]
    assert loops and not any(loops)


def test_ops_the_conversion_dropped_are_named(recorded, recorded_path,
                                              monkeypatch):
    """The conversion keeps only its latest million events; the operations
    of the rounds it dropped take the names their texts have in the
    rounds it kept."""
    full = scopes.viewer_ops(recorded_path)
    # each text names one instruction, and carries one op name
    assert all(len(set(v)) == 1 for per in full.values()
               for v in per.values())
    late = {d: {t: v[len(v) // 2:] for t, v in per.items()}
            for d, per in full.items()}
    assert late != full
    monkeypatch.setattr(scopes, "viewer_ops", lambda path: late)
    cut = scopes.load(recorded_path)
    assert cut.tf_ops == recorded.tf_ops
    for scope in scopes.SCOPES:
        assert scopes.scope_ms(cut, scope) == scopes.scope_ms(recorded,
                                                               scope)


def test_a_text_with_several_names_is_matched_in_order():
    """Two executables whose instructions share a text: the conversion's
    names go to the operations in order, from the latest back, and the
    operations it dropped stay unnamed; a text with one name names every
    operation it has."""
    def ev(text, t):
        return trace.Event("fusion.1", t, t + 1, text)
    evs = [ev("%fusion.1 = f32[8] add()", float(t)) for t in range(4)]
    evs.insert(2, ev("%copy.2 = f32[8] copy()", 9.0))
    both = ["jit(a)/local_step/add", "jit(b)/eq2_wire/add"] * 2
    names = {"%fusion.1 = f32[8] add()": both,
             "%copy.2 = f32[8] copy()": ["jit(a)/copy"]}
    assert scopes.op_names(evs, names) == [
        both[0], both[1], "jit(a)/copy", both[2], both[3]]
    names["%fusion.1 = f32[8] add()"] = both[1:]
    assert scopes.op_names(evs, names) == [
        "", both[1], "jit(a)/copy", both[2], both[3]]
    assert scopes.op_names(evs, {}) == [""] * 5


def test_each_round_opens_its_phases_in_order(recorded):
    rounds = [s for s in recorded.program if s.name == "colearn.round"]
    benches = [s for s in recorded.trace.spans if s.name == "bench.round"]
    assert len(rounds) == len(benches) == 6
    for r, b in zip(rounds, benches):
        shifted = (r.start + recorded.shift_s, r.end + recorded.shift_s)
        assert b.start <= shifted[0] <= shifted[1] <= b.end
        inner = [s.name for s in recorded.program
                 if s is not r and r.start <= s.start and s.end <= r.end]
        assert inner == ["colearn.input", "colearn.dispatch",
                         "colearn.fetch", "colearn.finish"]


def _by_hand(sc, scope):
    """Seconds under ``scope`` on device 0, merged by a plain sweep."""
    lo, hi = trace.window(sc.trace)
    ivs = sorted((max(e.start, lo), min(e.end, hi))
                 for e, t in zip(sc.trace.devices[0], sc.tf_ops[0])
                 if scope in t.split(":")[0].split("/")
                 and e.end > lo and e.start < hi)
    total, end = 0.0, -1.0
    for s, e in ivs:
        if e > end:
            total += e - max(s, end)
            end = e
    return total


@pytest.mark.parametrize("name,scope", [("local_step_ms", "local_step"),
                                        ("eq2_wire_ms", "eq2_wire"),
                                        ("eq4_rel_ms", "eq4_rel")])
def test_scope_readers_match_a_hand_sum(recorded_path, name, scope):
    ctx = {"trace_path": recorded_path}
    got = spec.metric_reader(name)(ctx)
    want = 1e3 * _by_hand(ctx["scoped"], scope) / 6
    assert want > 0 and got == pytest.approx(want, rel=1e-12)
    assert scopes.scope_seconds(ctx["scoped"], scope, 0) == pytest.approx(
        want * 6e-3, rel=1e-12)


def _run_ctx(path, recorded):
    """What a traced run of the int8 cell hands its readers, for the
    recorded round."""
    from bench import flops
    rounds = [s for s in recorded.trace.spans if s.name == "bench.round"]
    t = tiny.traffic()
    per_round = (t["participants"] * t["steps_per_epoch"]
                 * t["batch_size"] * t["seq_len"])
    return {"trace": trace.load(path), "trace_path": path,
            "times": [(s.start, s.end) for s in rounds],
            "tokens": [per_round] * len(rounds), "setup_s": 1.0,
            "peak_bytes": 0, "compiles": 0, "cfg": tiny.config(),
            "traffic": t, "chips": 1, "peaks": flops.peaks("TPU v5 lite")}


def test_a_traced_run_hands_its_readers_the_trace(recorded_path, recorded):
    from bench import run
    cell = spec.workload("internlm2-k2-int8")
    got = run.trace_metrics(cell, _run_ctx(recorded_path, recorded))
    for name, scope in (("local_step_ms", "local_step"),
                        ("eq2_wire_ms", "eq2_wire"),
                        ("eq4_rel_ms", "eq4_rel")):
        assert got[name] == {"value": pytest.approx(
            scopes.scope_ms(recorded, scope), rel=1e-12), "unit": "ms"}
    # without the file the scope readers read nothing, and the line
    # leaves them out
    bare = run.trace_metrics(cell, dict(_run_ctx(recorded_path, recorded),
                                        trace_path=None))
    assert not {"local_step_ms", "eq2_wire_ms", "eq4_rel_ms"} & set(bare)
    assert set(got) - set(bare) == {"local_step_ms", "eq2_wire_ms",
                                    "eq4_rel_ms"}


def test_scopes_cover_most_of_the_busy_time(recorded):
    lo, hi = trace.window(recorded.trace)
    busy = trace.measure(trace.busy(recorded.trace, 0, lo, hi))
    unscoped = sum(s for _, s in scopes.unscoped_ops(recorded, 0, top=None))
    scoped = trace.measure(trace.clip(trace.union(
        (e.start, e.end) for e, t in zip(recorded.trace.devices[0],
                                         recorded.tf_ops[0])
        if any(scopes.in_scope(t, s) for s in scopes.SCOPES)), lo, hi))
    # what no scope covers, by name; two unnamed operations can overlap
    # (an async copy beside another), so the names may add up to a little
    # more than the remainder
    assert busy - scoped <= unscoped + 1e-12
    assert unscoped == pytest.approx(busy - scoped, rel=0.02)
    assert scoped / busy > 0.8


def test_host_time_matches_a_hand_sum(recorded):
    want = []
    for r in (s for s in recorded.program if s.name == "colearn.round"):
        fetch = sum(s.end - s.start for s in recorded.program
                    if s.name == "colearn.fetch" and r.start <= s.start
                    and s.end <= r.end)
        want.append(r.end - r.start - fetch)
    assert len(want) == 6
    assert scopes.host_ms_per_round(recorded) == pytest.approx(
        1e3 * sum(want) / len(want))


def test_fetches_end_after_their_programs(recorded):
    """Each fetch ends more than the tolerance after its program by the
    device's clock, so load moved the program's spans back by the median;
    each fetch then ends within a third of a millisecond of its program."""
    skews = scopes.clock_skew(recorded)
    assert len(skews) == 6
    assert 1e-3 < recorded.shift_s < 3e-3
    assert statistics.median(skews) == pytest.approx(0.0, abs=1e-12)
    assert all(abs(s) < 0.3e-3 for s in skews)


def test_gaps_are_named_by_the_programs_spans(recorded):
    gaps = scopes.breakdown(recorded)["idle_gaps"]
    assert [n for n, _ in gaps[:6]] == ["colearn.dispatch"] * 6
    # the benchmark's own reading puts every gap under its own spans
    assert all(n.startswith("bench.") for n, _ in
               trace.breakdown(recorded.trace)["idle_gaps"])


def test_wire_kernel_is_named(recorded):
    kernels = [(e, t) for e, t in zip(recorded.trace.devices[0],
                                      recorded.tf_ops[0])
               if "tpu_custom_call" in e.text]
    assert len(kernels) == 3                  # one a round of the int8 cell
    assert {e.name for e, _ in kernels} == {"eq2_flat_mean.1"}
    assert all(scopes.in_scope(t, "eq2_wire") for _, t in kernels)
    # what wire_kernel_ms matches: the same three operations
    assert trace.ms_per_round(recorded.trace, r"tpu_custom_call") == \
        pytest.approx(1e3 * sum(e.end - e.start for e, _ in kernels) / 6)
