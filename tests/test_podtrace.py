"""Pod flight recorder (parallel/podtrace.py): merge parity, round
alignment, torn-dir degradation, heartbeat atomicity, straggler naming.

Everything here runs single-process and fast: rank dirs are either
hand-crafted JSON artifacts (deterministic walls, so the skew and
coverage arithmetic is checked against exact expectations) or produced
by driving the real recorder in-process. The REAL 2-process pods —
where the brackets wrap actual cross-host psums — run in the slow tier
(test_multihost_2proc.py) and the ci.sh pod stage.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from transmogrifai_tpu.parallel import podtrace as P

# -- rank-dir fabrication -----------------------------------------------------


def _span(sid, name, kind, t0, t1, **attrs):
    return {"span_id": sid, "parent_id": None, "name": name,
            "kind": kind, "t_start": t0, "t_end": t1,
            "duration_seconds": round(t1 - t0, 6), "error": False,
            "attrs": attrs}


def _mk_rank(pod_dir, rank, spans, heartbeats=None, meta=None,
             torn=False):
    rd = os.path.join(str(pod_dir), f"rank-{rank}")
    os.makedirs(rd, exist_ok=True)
    mpath = os.path.join(rd, P.METRICS_NAME)
    if torn:
        with open(mpath, "w", encoding="utf-8") as fh:
            fh.write('{"spans": [{"name": "tru')  # killed mid-write
    else:
        with open(mpath, "w", encoding="utf-8") as fh:
            json.dump({"app_name": f"pod-rank{rank}", "spans": spans},
                      fh)
    with open(os.path.join(rd, P.META_NAME), "w",
              encoding="utf-8") as fh:
        json.dump(dict(meta or {}, rank=rank, backend="cpu"), fh)
    if heartbeats:
        with open(os.path.join(rd, P.HEARTBEAT_NAME), "w",
                  encoding="utf-8") as fh:
            for hb in heartbeats:
                fh.write(json.dumps(hb) + "\n")
    return rd


def _rounds_rank(rate, rounds=3, coll_frac=0.4):
    """Spans for one rank: `rounds` pod_rounds of wall `rate` seconds,
    each fully covered by one collective + one compute bracket."""
    spans, sid, t = [], 0, 0.0
    for i in range(rounds):
        t1 = t + rate
        spans.append(_span(sid, f"pod_round[{i}]", "pod_round", t, t1,
                           round=i))
        sid += 1
        tc = t + rate * coll_frac
        spans.append(_span(sid, "pod_collective[glm_round]",
                           "pod_collective", t, tc, site="glm_round",
                           rows=100, feat=8, lanes=4, iters=2))
        sid += 1
        spans.append(_span(sid, "pod_compute[glm_retire]",
                           "pod_compute", tc, t1, site="glm_retire"))
        sid += 1
        t = t1
    return spans


# -- merge parity -------------------------------------------------------------


def test_merge_parity_per_family_histograms(tmp_path):
    """The merged Chrome trace is the UNION of the rank streams: per
    span family (cat), total merged duration == the sum over every
    rank's own spans. Nothing dropped, nothing double-counted."""
    ranks = {0: _rounds_rank(0.10), 1: _rounds_rank(0.12),
             2: _rounds_rank(0.08)}
    for rank, spans in ranks.items():
        _mk_rank(tmp_path, rank, spans)
    rep = P.merge_pod(str(tmp_path))
    assert rep["problems"] == []
    with open(rep["trace_path"], encoding="utf-8") as fh:
        trace = json.load(fh)
    merged = {}
    for ev in trace["traceEvents"]:
        if ev.get("ph") == "X":
            merged[ev["cat"]] = merged.get(ev["cat"], 0.0) \
                + ev["dur"] / 1e6
    expect = {}
    for spans in ranks.values():
        for s in spans:
            expect[s["kind"]] = expect.get(s["kind"], 0.0) \
                + s["duration_seconds"]
    assert set(merged) == set(expect)
    for fam in expect:
        assert merged[fam] == pytest.approx(expect[fam], abs=1e-5), fam


def test_merge_round_alignment_uneven_stripes(tmp_path):
    """Uneven stripes -> different per-round walls per rank. The merged
    timeline aligns every rank's round r at ONE shared start and the
    slowest rank sets the round width, so swimlanes stay comparable on
    unsynchronized clocks."""
    _mk_rank(tmp_path, 0, _rounds_rank(0.10))
    _mk_rank(tmp_path, 1, _rounds_rank(0.30))  # 3x slower stripe
    rep = P.merge_pod(str(tmp_path))
    assert rep["problems"] == []
    assert not rep["synthetic_rounds"]
    assert [r["round"] for r in rep["rounds"]] == [0, 1, 2]
    for row in rep["rounds"]:
        assert row["wall_s"][1] == pytest.approx(0.30, abs=1e-6)
        assert row["wall_s"][0] == pytest.approx(0.10, abs=1e-6)
    with open(rep["trace_path"], encoding="utf-8") as fh:
        evs = [e for e in json.load(fh)["traceEvents"]
               if e.get("ph") == "X"]
    # round r starts at the same merged ts on BOTH lanes: cumulative
    # max-wall boundaries 0, 0.3, 0.6 (slow rank sets the width)
    for i in range(3):
        starts = {e["pid"]: e["ts"] for e in evs
                  if e["name"] == f"pod_round[{i}]"}
        assert starts[0] == pytest.approx(starts[1], abs=1.0)
        assert starts[0] == pytest.approx(i * 0.30 * 1e6, abs=1.0)


def test_merge_flags_broken_round_alignment(tmp_path):
    _mk_rank(tmp_path, 0, _rounds_rank(0.1, rounds=3))
    _mk_rank(tmp_path, 1, _rounds_rank(0.1, rounds=2))  # lost round 2
    rep = P.merge_pod(str(tmp_path))
    assert any("broken round alignment" in p for p in rep["problems"])
    text, rc = P.pod_report_rc(str(tmp_path))
    assert rc == 1
    assert "broken round alignment" in text


def test_merge_torn_rank_degrades_to_partial_report(tmp_path):
    _mk_rank(tmp_path, 0, _rounds_rank(0.1))
    _mk_rank(tmp_path, 1, [], torn=True)
    rep = P.merge_pod(str(tmp_path))
    assert any("torn" in p for p in rep["problems"])
    # the live rank is still fully reported
    assert [r["rank"] for r in rep["ranks"]] == [0, 1]
    live = next(r for r in rep["ranks"] if r["rank"] == 0)
    assert live["rounds"] == 3 and not live["torn"]
    assert next(r for r in rep["ranks"] if r["rank"] == 1)["torn"]
    _, rc = P.pod_report_rc(str(tmp_path))
    assert rc == 1


def test_merge_flags_undercoverage(tmp_path):
    """A round whose instrumented spans cover less than the floor is a
    problem (exit 1): silence must read as a gap, not as health."""
    spans = [_span(0, "pod_round[0]", "pod_round", 0.0, 1.0, round=0),
             _span(1, "pod_compute[x]", "pod_compute", 0.0, 0.5,
                   site="x")]
    _mk_rank(tmp_path, 0, spans)
    rep = P.merge_pod(str(tmp_path))
    assert any("cover" in p for p in rep["problems"])
    assert rep["coverage_min_seen"] == pytest.approx(0.5, abs=1e-6)
    # nested/overlapping brackets must not fake coverage: a second span
    # over the SAME window adds nothing
    spans.append(_span(2, "pod_ingest[y]", "pod_ingest", 0.0, 0.5,
                       site="y"))
    _mk_rank(tmp_path, 0, spans)
    rep2 = P.merge_pod(str(tmp_path))
    assert rep2["coverage_min_seen"] == pytest.approx(0.5, abs=1e-6)


def test_merge_straggler_attribution(tmp_path):
    """The rank with the fat DERIVED compute (round wall minus its
    collective union) is the straggler — victims waiting in the
    barrier show high collective share instead and are never blamed."""
    fast, slow = [], []
    for i in range(3):
        t0, t1 = i * 1.0, (i + 1) * 1.0
        for spans, coll in ((fast, 0.9), (slow, 0.1)):
            sid = len(spans) + 100
            spans.append(_span(sid, f"pod_round[{i}]", "pod_round",
                               t0, t1, round=i))
            spans.append(_span(sid + 1, "pod_collective[glm_round]",
                               "pod_collective", t0, t0 + coll,
                               site="glm_round"))
            spans.append(_span(sid + 2, "pod_compute[work]",
                               "pod_compute", t0 + coll, t1, site="work"))
    _mk_rank(tmp_path, 0, fast)   # 0.9s in the barrier: victim
    _mk_rank(tmp_path, 1, slow)   # 0.9s computing: straggler
    rep = P.merge_pod(str(tmp_path))
    assert rep["skew"]["flagged"]
    assert rep["skew"]["straggler_rank"] == 1
    assert rep["skew"]["flagged_rounds"] == 3
    for row in rep["rounds"]:
        assert row["straggler_rank"] == 1 and row["flagged"]
        assert row["collective_share"][0] > 0.8
    # nested collective brackets union, not sum: duplicating rank 0's
    # barrier bracket must not push its share past 100%
    fast.append(_span(999, "pod_collective[row_layout]",
                      "pod_collective", 0.0, 0.9, site="row_layout"))
    _mk_rank(tmp_path, 0, fast)
    rep2 = P.merge_pod(str(tmp_path))
    assert rep2["rounds"][0]["collective_share"][0] <= 1.0


# -- recorder round trip ------------------------------------------------------


def test_recorder_round_trip(tmp_path, monkeypatch):
    monkeypatch.setenv("TMOG_PODTRACE", "1")
    monkeypatch.setenv("TMOG_PODTRACE_DIR", str(tmp_path))
    monkeypatch.setenv("TMOG_PODTRACE_HEARTBEAT_S", "0")
    P.start(process_id=0, processes=1)
    try:
        for rnd in range(2):
            with P.pod_round(rnd):
                with P.compute("glm_prep", lanes=4):
                    pass
                with P.collective("glm_round", rows=64, feat=4,
                                  lanes=4, iters=2):
                    time.sleep(0.001)
                with P.ingest("glm_land", rows=64, cols=4):
                    pass
                P.note_collective("tile_merge", 0.0005, tile=0, rows=32,
                                  label="stats")
    finally:
        P.finish()
    rd = os.path.join(str(tmp_path), "rank-0")
    assert {P.HEARTBEAT_NAME, P.META_NAME,
            P.METRICS_NAME} <= set(os.listdir(rd))
    hb = P.read_heartbeat(rd)
    assert hb is not None and hb["phase"] == "finish"
    rep = P.merge_pod(str(tmp_path))
    assert rep["problems"] == []
    assert not rep["synthetic_rounds"] and len(rep["rounds"]) == 2
    assert rep["mfu_table"], "MFU table empty on a traced run"
    text, rc = P.pod_report_rc(str(tmp_path))
    assert rc == 0 and "Top sinks" in text


def test_recorder_off_is_inert(tmp_path, monkeypatch):
    monkeypatch.delenv("TMOG_PODTRACE", raising=False)
    monkeypatch.setenv("TMOG_PODTRACE_DIR", str(tmp_path))
    P.start(process_id=0, processes=1)
    try:
        with P.pod_round(0):
            with P.collective("glm_round"):
                pass
    finally:
        P.finish()
    assert P.rank_dirs(str(tmp_path)) == []


# -- heartbeat contract -------------------------------------------------------


def test_heartbeat_atomic_append_under_concurrent_reader(
        tmp_path, monkeypatch):
    """One beat = ONE newline-terminated os.write: a reader polling the
    file mid-run must only ever see complete records, with the round
    index never going backwards."""
    monkeypatch.setenv("TMOG_PODTRACE", "1")
    monkeypatch.setenv("TMOG_PODTRACE_DIR", str(tmp_path))
    monkeypatch.setenv("TMOG_PODTRACE_HEARTBEAT_S", "0")
    P.start(process_id=0, processes=1)
    rd = os.path.join(str(tmp_path), "rank-0")
    stop = threading.Event()
    seen, errors = [], []

    def reader():
        while not stop.is_set():
            try:
                hb = P.read_heartbeat(rd)
            except Exception as e:  # a torn read would surface here
                errors.append(repr(e))
                return
            if hb is not None:
                if not isinstance(hb.get("mono"), float) \
                        or "phase" not in hb:
                    errors.append(f"incomplete record: {hb}")
                    return
                seen.append(hb)

    t = threading.Thread(target=reader)
    t.start()
    try:
        for rnd in range(300):
            P.beat(f"phase{rnd % 7}", rnd=rnd, force=True)
    finally:
        stop.set()
        t.join(timeout=10.0)
        P.finish()
    assert not errors, errors
    rounds = [hb["round"] for hb in seen
              if isinstance(hb.get("round"), int)]
    assert rounds == sorted(rounds), "round index went backwards"
    # and the final file state parses cleanly line by line
    with open(os.path.join(rd, P.HEARTBEAT_NAME),
              encoding="utf-8") as fh:
        for line in fh.read().splitlines():
            json.loads(line)


def test_read_heartbeat_ignores_torn_tail(tmp_path):
    rd = tmp_path / "rank-0"
    rd.mkdir()
    hb = rd / P.HEARTBEAT_NAME
    hb.write_text(json.dumps({"round": 4, "phase": "round",
                              "mono": 1.0, "ts": 2.0}) + "\n"
                  + '{"round": 5, "phase": "tr')  # killed mid-write
    rec = P.read_heartbeat(str(rd))
    assert rec is not None and rec["round"] == 4


def test_straggler_table_names_wedged_rank(tmp_path):
    """The reaper's blame heuristic: a live rank parked in a
    collective:* phase is a VICTIM (it reached the barrier); the live
    rank still in compute with the stalest beat is the straggler."""
    now = time.time()
    _mk_rank(tmp_path, 0, [], heartbeats=[
        {"round": 2, "phase": "collective:glm_round", "mono": 10.0,
         "ts": now - 20.0}])
    _mk_rank(tmp_path, 1, [], heartbeats=[
        {"round": 2, "phase": "compute:wedged", "mono": 10.0,
         "ts": now - 25.0}])
    text, stragglers = P.straggler_table(str(tmp_path),
                                         rcs=[None, None])
    assert stragglers == [1]
    assert "likely straggler: rank 1" in text
    assert "round 2" in text and "compute:wedged" in text
    # an exited rank is never the straggler
    text2, s2 = P.straggler_table(str(tmp_path), rcs=[None, 0])
    assert 1 not in s2


def test_pod_report_rc_usage_error_on_empty_dir(tmp_path):
    text, rc = P.pod_report_rc(str(tmp_path))
    assert rc == 2
    assert "no rank-" in text
