"""The per-layer readers of the program's own spans and counters
(`bench/program_spans.py` and the metrics that use it), on synthetic
records, and on a tiny traced run of each cell on the CPU."""
from __future__ import annotations

import pytest

import bench_tiny
from bench.spec import Spec
from repro.runtime import spans

MS = 1_000_000      # ns


def _call(root, children, call=0):
    """Records of one grid call: `root` from 0 to 1 s, and `children`
    given as (name, parent name, start ms, end ms, counts)."""
    recs = [spans.Record(call, root, 0, 1000 * MS, None, call, {})]
    ids = {root: call}
    for i, (name, parent, t0, t1, counts) in enumerate(children, 1):
        ids.setdefault(name, call + i)
        recs.append(spans.Record(call + i, name, t0 * MS, t1 * MS,
                                 ids[parent], call, counts))
    return recs


HALL = _call("repro.mc_sweep", [
    ("repro.mc_sweep.prepare", "repro.mc_sweep", 0, 100,
     {"events": 9600, "rows": 800}),
    ("repro.arrivals.mixed_traces", "repro.mc_sweep.prepare", 0, 20, {}),
    ("repro.arrivals.mixed_traces", "repro.mc_sweep.prepare", 20, 50, {}),
    ("repro.mc_sweep.finalize", "repro.mc_sweep", 970, 1000, {})])
FLEET = _call("repro.sweep", [
    ("repro.sweep.prepare", "repro.sweep", 0, 40,
     {"events": 300, "event_slots": 1200, "rows": 4000}),
    ("repro.sweep.finalize", "repro.sweep", 990, 1000,
     {"rows_built": 1000})])
SECONDS = 2.0       # the traced call's host-clock length


@pytest.mark.parametrize("metric,records,value", [
    ("prepare_share.hall", HALL, 5.0),
    ("trial_synth_share.hall", HALL, 2.5),
    ("finalize_share.hall", HALL, 1.5),
    ("prepare_share.fleet", FLEET, 2.0),
    ("finalize_share.fleet", FLEET, 0.5),
    ("slot_fill.fleet", FLEET, 25.0),
    ("row_fill.fleet", FLEET, 25.0)])
def test_reader_on_synthetic_records(monkeypatch, metric, records, value):
    read = Spec(bench_tiny.REPO).metric(metric).read

    class Ctx:
        traced = {"seconds": SECONDS}

    def with_records(recs):
        monkeypatch.setattr(spans, "records", lambda: tuple(recs))
        return read(Ctx())

    assert with_records(records) == pytest.approx(value)
    root = records[0]
    # no root of the engine, or two grid calls: nothing to read
    assert with_records(records[1:]) is None
    second = [spans.Record(r.id + 100, r.name, r.start_ns, r.end_ns,
                           None if r.parent is None else r.parent + 100,
                           r.call + 100, r.counts) for r in records]
    assert with_records(records + second) is None
    # another engine's root does not count as this one's
    other = "repro.sweep" if root.name == "repro.mc_sweep" \
        else "repro.mc_sweep"
    assert with_records([r if r is not root else spans.Record(
        r.id, other, r.start_ns, r.end_ns, None, r.call, r.counts)
        for r in records]) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.tiny_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("workload,names", [
    ("hall.fig7", ["prepare_share.hall", "trial_synth_share.hall",
                   "finalize_share.hall"]),
    ("fleet.fig13", ["prepare_share.fleet", "finalize_share.fleet",
                     "slot_fill.fleet", "row_fill.fleet"])])
def test_tiny_traced_run_reports_the_span_metrics(root, workload, names):
    spans.clear()
    rc, res, out = bench_tiny.run(root, [
        "--workload", workload, "--seed", str(2 ** 31 + 5),
        "--seconds", "0.2", "--trace", "1"])
    assert rc == 0 and res["correct"] is True, out
    for n in names:
        assert 0 < res["metrics"][n]["value"] <= 100, n
    spans.clear()


def test_untraced_run_records_nothing(root):
    spans.clear()
    rc, res, out = bench_tiny.run(root, [
        "--workload", "hall.fig7", "--seed", "3", "--seconds", "0.2",
        "--trace", "0"])
    assert rc == 0 and res["correct"] is True, out
    assert spans.records() == ()
