"""Live fleet health plane tests (ISSUE 14, ARCHITECTURE §13): the
bounded telemetry delta stream over the fleet protocol, the streaming
why-slow analyzer (`obs.health`) and its live==replay contract against
`obs.analyze`, health-aware big-job routing (drilled A/B vs locality),
the degraded->flight-bundle contract, per-agent health gauges + the
`dsort top` health pane, and protocol-level clock sync for `dsort report
--merge`."""

import os
import time

import numpy as np
import pytest

from dsort_tpu.fleet import proto
from dsort_tpu.fleet.agent import FleetAgent
from dsort_tpu.fleet.controller import FleetController
from dsort_tpu.obs.analyze import VERDICT_KEYS, analyze_records
from dsort_tpu.obs.health import (
    HEALTH_VERDICT_KEYS,
    SHARED_VERDICT_KEYS,
    HealthAnalyzer,
    HealthDeltaCollector,
    format_health,
)
from dsort_tpu.obs.merge import merge_records
from dsort_tpu.utils.events import COUNTERS, EVENT_TYPES, EventLog
from dsort_tpu.utils.metrics import Metrics, PhaseTimer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.usefixtures("fresh_compile_ledger")


def _slow_runner(data, metrics, job_id=None):
    timer = PhaseTimer(metrics)
    with timer.phase("local_sort"):
        time.sleep(0.25)
    metrics.event("job_done", n_keys=len(data), counters=dict(metrics.counters))
    return np.sort(data)


def _fast_runner(data, metrics, job_id=None):
    timer = PhaseTimer(metrics)
    with timer.phase("local_sort"):
        time.sleep(0.01)
    metrics.event("job_done", n_keys=len(data), counters=dict(metrics.counters))
    return np.sort(data)


def _close_all(ctl, agents):
    try:
        ctl.shutdown(drain=True, timeout=30)
    finally:
        for a in agents:
            a.close()


# -- the delta collector (agent side) ----------------------------------------


def test_collector_accumulates_and_drains():
    """The collector is a Metrics tap accumulating exactly the analyzer's
    inputs; drain() returns the bounded delta and resets, with the
    running sums exact regardless of the sample-window bound."""
    c = HealthDeltaCollector()
    m = Metrics()
    c.attach(m)
    c.attach(m)  # idempotent
    assert m.taps.count(c) == 1
    timer = PhaseTimer(m)
    with timer.phase("local_sort"):
        pass
    with timer.phase("exchange"):
        pass
    for i in range(200):  # overflow the wait window; the sum stays exact
        m.event("job_dequeued", tenant="t", wait_s=0.001)
    m.event("variant_compiled", variant="fused|8|int32", compile_s=0.5)
    m.event("skew_report", max_mean_ratio=2.0, recv_argmax=3)
    m.event("skew_report", max_mean_ratio=1.2, recv_argmax=1)  # not worst
    m.event("hbm_watermark", phase="exchange", edge="end", bytes_in_use=123)
    m.event("job_done", n_keys=10)
    delta = c.drain()
    assert set(delta["phases"]) == {"local_sort", "exchange"}
    assert delta["wait_count"] == 200
    assert delta["wait_s_sum"] == pytest.approx(0.2)
    assert len(delta["waits"]) <= 64  # bounded window, sums exact above
    assert delta["compile_s_sum"] == pytest.approx(0.5)
    assert delta["compiles"][0]["variant"] == "fused|8|int32"
    assert delta["skew"]["max_mean_ratio"] == 2.0
    assert delta["hbm"]["bytes_in_use"] == 123
    assert delta["jobs_done"] == 1
    empty = c.drain()  # reset
    assert empty["phases"] == {} and empty["wait_count"] == 0
    assert empty["seq"] == delta["seq"] + 1


# -- the bounded frame (satellite: heartbeat-plane growth) -------------------


def test_bounded_frame_evicts_oldest_first():
    """A long-running agent cannot inflate the heartbeat plane: an
    oversized telemetry frame is evicted oldest-first down to the byte
    budget, keeping the NEWEST wait/compile samples and preserving the
    per-phase seconds TOTAL (smallest phases fold into 'other')."""
    delta = {
        "seq": 9,
        "phases": {f"phase_{i:03d}": float(i + 1) for i in range(40)},
        "wait_s_sum": 1.0, "wait_count": 500,
        "waits": [float(i) for i in range(500)],
        "compile_s_sum": 2.0, "compile_count": 200,
        "compiles": [
            {"variant": f"fused|{8 * (i + 1)}|int32|auto", "compile_s": 0.1}
            for i in range(200)
        ],
        "skew": None, "hbm": None, "jobs_done": 3, "jobs_failed": 0,
    }
    header = {
        "type": "telemetry", "agent_id": "A", "wall": 1.0, "mono": 2.0,
        "variants": [f"fused|{8 * (i + 1)}|int64|auto" for i in range(300)],
        "delta": delta,
    }
    assert proto.frame_bytes(header) > proto.TELEMETRY_BYTE_BUDGET
    out = proto.bounded_frame(header)
    assert proto.frame_bytes(out) <= proto.TELEMETRY_BYTE_BUDGET
    # The original is never mutated.
    assert len(header["delta"]["waits"]) == 500
    # Eviction is oldest-first: whatever survives is the list TAIL.
    waits = out["delta"].get("waits", [])
    assert waits == [float(i) for i in range(500 - len(waits), 500)]
    # The exact running sums always survive.
    assert out["delta"]["wait_s_sum"] == 1.0
    assert out["delta"]["compile_s_sum"] == 2.0
    # Per-phase TOTAL is preserved even if attribution coarsened.
    assert sum(out["delta"]["phases"].values()) == pytest.approx(
        sum(delta["phases"].values())
    )
    # The dominant phase survives any folding.
    assert max(out["delta"]["phases"], key=out["delta"]["phases"].get) in (
        "phase_039", "other",
    )
    if "other" in out["delta"]["phases"]:
        assert out["delta"]["phases"].get("phase_039") == 40.0
    # A small frame passes through untouched.
    small = {"type": "heartbeat", "variants": ["a"], "queued": 0}
    assert proto.bounded_frame(small) == small


def test_agent_advertises_bounded_recent_variants():
    """The heartbeat's variant advertisement is bounded with eviction
    oldest-first (LRU order): the newest rungs survive."""
    agent = FleetAgent(runner=_fast_runner, agent_id="bnd")
    try:
        vc = agent.service.variants
        for i in range(60):
            vc._insert(("fused", 8 * (i + 1), "int32", "auto"), vc.TOKEN, None)
        labels = agent.variant_labels()
        assert len(labels) <= proto.MAX_ADVERTISED_VARIANTS
        assert f"fused|{8 * 60}|int32|auto" in labels  # newest kept
        assert f"fused|{8 * 1}|int32|auto" not in labels  # oldest evicted
    finally:
        agent.close(drain=False)


# -- the incremental analyzer ------------------------------------------------


def test_health_verdict_schema_shares_analyze_vocabulary():
    """Live and replay verdicts are comparable by construction: the
    shared keys are spelled identically (subset pinned)."""
    assert set(SHARED_VERDICT_KEYS) <= set(VERDICT_KEYS)
    for k in ("straggler", "dominant_phase", "splits", "skew", "hbm"):
        assert k in SHARED_VERDICT_KEYS and k in HEALTH_VERDICT_KEYS


def test_analyzer_scores_straggler_and_degrades():
    h = HealthAnalyzer(degraded_score=1.5, min_busy_s=0.05, slo_ms=100.0)
    h.ingest("A", {"seq": 1, "phases": {"local_sort": 0.9, "merge": 0.1},
                   "wait_s_sum": 0.01, "wait_count": 1, "waits": [0.01],
                   "compile_s_sum": 0.2, "compile_count": 1})
    h.ingest("B", {"seq": 1, "phases": {"local_sort": 0.2},
                   "wait_s_sum": 0.3, "wait_count": 2, "waits": [0.1, 0.2]})
    vs = h.verdicts()
    assert set(vs) == {"A", "B"}
    a, b = vs["A"], vs["B"]
    assert set(a) == set(HEALTH_VERDICT_KEYS)
    assert a["straggler"] and not b["straggler"]
    assert a["score"] == pytest.approx(1.0 / 0.6, abs=1e-3)
    assert a["dominant_phase"] == "local_sort"
    assert a["splits"]["phase_wall_s"] == pytest.approx(1.0)
    assert a["splits"]["compile_s"] == pytest.approx(0.2)
    assert a["splits"]["execute_s"] == pytest.approx(0.8)
    assert a["degraded"]  # straggler at 1.67x >= 1.5 with real busy time
    # B breaches the 100 ms SLO target (p95 wait 200 ms) -> degraded too.
    assert b["slo_risk"]["ratio"] >= 1.0 and b["degraded"]
    assert h.scores()["A"] == (True, a["score"])
    assert h.frames == 2
    # Deltas FOLD: a second ingest doubles A's busy time.
    h.ingest("A", {"seq": 2, "phases": {"local_sort": 1.0}})
    assert h.verdicts()["A"]["splits"]["phase_wall_s"] == pytest.approx(2.0)
    assert "A" in format_health(h.verdicts())
    h.forget("A")
    assert h.agents() == ["B"]


def test_collector_restore_survives_failed_send():
    """A drained-but-undelivered delta folds BACK (the agent's send
    failed): work completed while the controller was detached must not
    vanish from the health history — the exact sums merge."""
    c = HealthDeltaCollector()
    m = Metrics()
    c.attach(m)
    m.event("phase_end", phase="local_sort", seconds=0.4)
    m.event("job_dequeued", tenant="t", wait_s=0.1)
    m.event("skew_report", max_mean_ratio=2.5)
    lost = c.drain()  # shipped into a dead link...
    m.event("phase_end", phase="local_sort", seconds=0.6)
    m.event("job_dequeued", tenant="t", wait_s=0.2)
    c.restore(lost)  # ...and folded back on the send failure
    merged = c.drain()
    assert merged["phases"]["local_sort"] == pytest.approx(1.0)
    assert merged["wait_s_sum"] == pytest.approx(0.3)
    assert merged["wait_count"] == 2
    assert merged["skew"]["max_mean_ratio"] == 2.5
    # The agent path: telemetry enabled, NO controller attached — the
    # sums survive the failed send and ship on the next success.
    agent = FleetAgent(runner=_fast_runner, agent_id="det")
    try:
        agent._enable_telemetry()
        agent._collector.attach(m2 := Metrics())
        m2.event("phase_end", phase="merge", seconds=0.7)
        agent._send_telemetry()  # no conn: drain + restore
        kept = agent._collector.drain()
        assert kept["phases"]["merge"] == pytest.approx(0.7)
    finally:
        agent.close(drain=False)


def test_dead_agent_leaves_fleet_mean_and_straggler_slot():
    """A permanently-down agent's frozen busy time must not make the one
    remaining healthy agent score as the fleet straggler."""
    h = HealthAnalyzer(degraded_score=1.5, min_busy_s=0.05)
    h.ingest("A", {"seq": 1, "phases": {"local_sort": 10.0}})
    h.ingest("B", {"seq": 1, "phases": {"local_sort": 40.0}})
    assert h.verdicts()["B"]["straggler"]
    h.set_active("A", False)  # A died for good; B keeps working alone
    vs = h.verdicts()
    # B is the only live agent: no straggler, no degrade, score 1.0.
    assert not vs["B"]["straggler"] and not vs["B"]["degraded"]
    assert vs["B"]["score"] == pytest.approx(1.0)
    # A's last verdict still renders, but never degraded while down.
    assert not vs["A"]["straggler"] and not vs["A"]["degraded"]
    # A comes back and streams again: it rejoins the computation
    # (busy 210 vs 40 -> score 1.68x >= the 1.5x degrade bar).
    h.ingest("A", {"seq": 2, "phases": {"local_sort": 200.0}})
    vs = h.verdicts()
    assert vs["A"]["straggler"] and vs["A"]["degraded"]


def test_single_agent_is_never_a_straggler():
    h = HealthAnalyzer()
    h.ingest("A", {"seq": 1, "phases": {"local_sort": 5.0}})
    v = h.verdict("A")
    assert not v["straggler"] and not v["degraded"]
    assert v["score"] == pytest.approx(1.0)


# -- live == replay (the scrape==journal discipline, streamed) ---------------


def test_live_verdicts_match_replay_on_drilled_fleet():
    """THE plane's ground-truth drill: on a live fleet with an
    injected-latency agent, the controller's final journaled
    `health_verdict` for each agent matches `obs.analyze` replay of that
    agent's OWN journal (dominant phase, split) and the merged replay
    names the same straggler."""
    ja, jb, jc = EventLog(), EventLog(), EventLog()
    a = FleetAgent(runner=_slow_runner, agent_id="A", journal=ja)
    b = FleetAgent(runner=_fast_runner, agent_id="B", journal=jb)
    ctl = FleetController(
        [a.addr, b.addr], heartbeat_s=0.2, journal=jc,
    )
    try:
        rng = np.random.default_rng(0)
        for _ in range(2):
            d1 = rng.integers(0, 10**6, 900, dtype=np.int32)
            d2 = rng.integers(0, 10**6, 900, dtype=np.int32)
            # Submit BOTH before awaiting: capacity 1 each, so the pair
            # lands one per agent deterministically.
            v1, t1 = ctl.submit(d1, tenant="t")
            v2, t2 = ctl.submit(d2, tenant="t")
            np.testing.assert_array_equal(t1.result(timeout=60), np.sort(d1))
            np.testing.assert_array_equal(t2.result(timeout=60), np.sort(d2))
        replay = {
            aid: analyze_records([e.to_dict() for e in log.events()])
            for aid, log in (("A", ja), ("B", jb))
        }
        for aid in ("A", "B"):
            assert replay[aid]["splits"]["phase_wall_s"] > 0, aid
        # Quiesce: the live verdicts converge onto the replay totals once
        # the agents' final deltas arrive (result-attached, so fast).
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            live = ctl.health_verdicts()
            if len(live) == 2 and all(
                live[aid]["splits"]["phase_wall_s"] == pytest.approx(
                    replay[aid]["splits"]["phase_wall_s"], abs=1e-5
                )
                for aid in ("A", "B")
            ):
                break
            time.sleep(0.05)
        # The FINAL journaled verdict per agent is the live state.
        journaled = {}
        for e in jc.events():
            if e.type == "health_verdict":
                journaled[e.fields["agent"]] = e.fields
        assert set(journaled) == {"A", "B"}
        for aid in ("A", "B"):
            got, want = journaled[aid], replay[aid]
            assert got["dominant_phase"] == want["dominant_phase"], aid
            for key in ("phase_wall_s", "queue_wait_s", "compile_s",
                        "execute_s"):
                assert got["splits"][key] == pytest.approx(
                    want["splits"][key], abs=1e-5
                ), (aid, key)
        # Straggler naming: live says A; the merged replay's straggler is
        # the same agent (src 0 = A's journal).
        assert journaled["A"]["straggler"] is True
        assert journaled["A"]["degraded"] is True
        assert journaled["B"]["straggler"] is False
        merged = merge_records([
            [e.to_dict() for e in log.events()] for log in (ja, jb)
        ])
        straggler = analyze_records(merged)["straggler"]
        assert straggler is not None and straggler["src"] == 0
        assert journaled["A"]["score"] == pytest.approx(
            straggler["score"], abs=1e-2
        )
        # The degraded flip was journaled as the typed event.
        degr = [e for e in jc.events() if e.type == "agent_degraded"]
        assert degr and degr[0].fields["agent"] == "A"
    finally:
        _close_all(ctl, [a, b])


# -- health-aware routing (the drilled A/B of the acceptance criteria) -------


def _prime_and_submit_big(routing: str, journal, flight_dir=None,
                          telemetry=None):
    """One arm of the A/B: slow agent A + fast agent B, one small prime
    job (ties route it to A), wait for verdicts, then one BIG job."""
    a = FleetAgent(runner=_slow_runner, agent_id="A")
    b = FleetAgent(runner=_fast_runner, agent_id="B")
    ctl = FleetController(
        [a.addr, b.addr], heartbeat_s=0.2, journal=journal, routing=routing,
        flight_dir=flight_dir, telemetry=telemetry,
    )
    try:
        d = np.arange(1000, dtype=np.int32)[::-1].copy()
        v, t = ctl.submit(d, tenant="t")
        assert v.admitted
        np.testing.assert_array_equal(t.result(timeout=60), np.sort(d))
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            vs = ctl.health_verdicts()
            if vs.get("A", {}).get("degraded") and "B" in vs:
                break
            time.sleep(0.05)
        assert ctl.health_verdicts()["A"]["degraded"]
        big = np.arange(proto.FLEET_SMALL_JOB_MAX, dtype=np.int32)[::-1].copy()
        v, t = ctl.submit(big, tenant="t")
        assert v.admitted
        np.testing.assert_array_equal(t.result(timeout=120), np.sort(big))
        routed = [
            e.fields for e in journal.events() if e.type == "job_routed"
        ]
        big_routes = [
            r for r in routed if r["n_keys"] >= proto.FLEET_SMALL_JOB_MAX
        ]
        assert len(big_routes) == 1
        return big_routes[0], ctl.stats()
    finally:
        _close_all(ctl, [a, b])


def test_health_routing_routes_big_jobs_around_straggler(tmp_path):
    """The ISSUE 14 acceptance drill: with agent A given an injected
    slowdown, routing="health" places the big job on the CLEAN mesh (B)
    while locality/size routing does not (A wins the load tie) — and the
    degraded flip dumped a flight bundle."""
    from dsort_tpu.obs.flight import FlightRecorder

    flight_dir = str(tmp_path / "flight")
    j_health = EventLog()
    route, stats = _prime_and_submit_big(
        "health", j_health, flight_dir=flight_dir
    )
    assert route["agent"] == "B" and route["reason"] == "health"
    assert stats["agents_degraded"] == 1
    # The degraded->flight-bundle contract: one bundle, typed path.
    bundles = FlightRecorder.read_bundles(flight_dir)
    assert bundles and bundles[0]["recovery_path"] == "agent_degraded"
    assert bundles[0]["detail"]["agent"] == "A"
    # The bundle's state is the fleet view at dump time.
    assert {s["agent"] for s in bundles[0]["state"]} == {"A", "B"}
    # The locality baseline does NOT route around the measured straggler:
    # both agents idle, the load tie breaks on the label and A takes it.
    j_loc = EventLog()
    route, _ = _prime_and_submit_big("locality", j_loc)
    assert route["agent"] == "A" and route["reason"] == "size"


def test_heartbeats_only_controller_streams_no_telemetry():
    """health_telemetry=False (conf FLEET_TELEMETRY=0) is the overhead
    A/B baseline: agents are never opted in, no frames flow, no verdicts
    form — and the opt-in follows the CURRENT controller, so a
    heartbeats-only controller attaching to an agent a previous
    controller enabled stays frame-free too."""
    a = FleetAgent(runner=_fast_runner, agent_id="A")
    ctl = FleetController(
        [a.addr], heartbeat_s=0.2, health_telemetry=False,
    )
    try:
        d = np.arange(500, dtype=np.int32)[::-1].copy()
        v, t = ctl.submit(d, tenant="t")
        np.testing.assert_array_equal(t.result(timeout=60), np.sort(d))
        time.sleep(0.6)  # a few heartbeat rounds
        assert ctl.health_verdicts() == {}
        assert ctl.health.frames == 0
        assert a._collector is None
    finally:
        ctl.shutdown(drain=True, timeout=30)
    # An opted-in controller enables the stream...
    on = FleetController([a.addr], heartbeat_s=0.2)
    try:
        v, t = on.submit(d, tenant="t")
        np.testing.assert_array_equal(t.result(timeout=60), np.sort(d))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and on.health.frames == 0:
            time.sleep(0.05)
        assert on.health.frames > 0 and a._collector is not None
    finally:
        on.shutdown(drain=True, timeout=30)
    # ...and a LATER heartbeats-only controller turns it back off.
    off = FleetController(
        [a.addr], heartbeat_s=0.2, health_telemetry=False,
    )
    try:
        v, t = off.submit(d, tenant="t")
        np.testing.assert_array_equal(t.result(timeout=60), np.sort(d))
        time.sleep(0.6)
        assert off.health.frames == 0
        assert not a._telemetry_on
    finally:
        _close_all(off, [a])


# -- gauges + the dsort top health pane --------------------------------------


def test_health_gauges_scrape_and_top_pane():
    from dsort_tpu.obs import Telemetry
    from dsort_tpu.obs.telemetry import parse_prometheus_text
    from dsort_tpu.obs.top import render_fleet, render_top

    tel = Telemetry()
    journal = EventLog()
    route, _ = _prime_and_submit_big("health", journal, telemetry=tel)
    assert route["agent"] == "B"
    parsed = parse_prometheus_text(tel.render_prometheus())
    score = parsed[("dsort_agent_health_score", (("agent", "A"),))]
    assert score >= 1.5
    assert parsed[("dsort_agent_health_degraded", (("agent", "A"),))] == 1.0
    assert parsed[("dsort_agent_health_degraded", (("agent", "B"),))] == 0.0
    assert parsed[("dsort_fleet_agents_degraded", ())] == 1.0
    info = [
        (dict(labels), v) for (name, labels), v in parsed.items()
        if name == "dsort_agent_health_info" and dict(labels)["agent"] == "A"
    ]
    # Info-style series REPLACE on refresh: exactly one row per agent.
    assert len(info) == 1
    assert info[0][0]["dominant_phase"] == "local_sort"
    assert info[0][0]["straggler"] == "1"
    top = render_top(parsed)
    assert "health:" in top and "A*" in top and "local_sort" in top
    fleet = render_fleet([("http://ctl/metrics", parsed)])
    assert "health:" in fleet
    # The JSON snapshot carries the labeled series too.
    snap = tel.snapshot()
    assert any(k.startswith("agent_health_score{agent=A}")
               for k in snap["series"])


# -- protocol-level clock sync (satellite 1) ---------------------------------


def test_peer_clock_blessing_aligns_skewed_wall_clocks():
    """`dsort report --merge` aligns controller+agent journals on
    MONOTONIC clocks via the peer (wall, mono) pairs the fleet frames
    carry — an agent with a skewed WALL clock still merges correctly."""
    ctl = [
        {"seq": 0, "t": 1000.0, "mono": 50.0, "type": "clock_sync",
         "source": "ctl"},
        # The blessing: the agent's pair journaled next to OUR stamps.
        {"seq": 1, "t": 1000.1, "mono": 50.1, "type": "clock_sync",
         "source": "ctl", "peer": "A", "peer_t": 5000.0, "peer_mono": 7.0},
        {"seq": 2, "t": 1002.0, "mono": 52.0, "type": "job_routed",
         "job_id": "f1", "agent": "A", "reason": "health", "n_keys": 10,
         "tenant": "t"},
    ]
    # The agent's wall clock is ~1.1 h ahead: wall-based alignment would
    # misplace its records by ~4000 s.
    agent = [
        {"seq": 0, "t": 5000.0, "mono": 7.0, "type": "clock_sync",
         "source": "A"},
        {"seq": 1, "t": 5001.0, "mono": 8.0, "type": "job_start",
         "mode": "fleet", "n_keys": 10, "job_id": "f1"},
    ]
    merged = merge_records([ctl, agent])
    start = next(r for r in merged if r["type"] == "job_start")
    # Monotonic blessing places it ~1 s after the hello (mono 50.1 + 1).
    assert start["mono"] == pytest.approx(51.1, abs=1e-6)
    # The trace is ordered: hello blessing < job_start < job_routed.
    types = [r["type"] for r in merged]
    assert types.index("job_start") < types.index("job_routed")
    # WITHOUT the blessing the same journals misalign by the wall skew —
    # the property the protocol pairs exist to remove.
    no_bless = [r for r in ctl if "peer" not in r]
    misaligned = merge_records([no_bless, agent])
    start = next(r for r in misaligned if r["type"] == "job_start")
    assert start["mono"] > 1000  # wall-skew artifact


def test_mutual_blessings_resolve_without_creep():
    """Symmetric controller<->agent blessings form a CYCLE; with a
    non-fleet journal at index 0 the component anchors at its lowest
    member and each shift is applied exactly once — the redundant edge
    (one network round-trip of disagreement) is ignored, never
    accumulated across resolution passes."""
    driver = [
        {"seq": 0, "t": 1000.0, "mono": 0.0, "type": "clock_sync",
         "source": "drv"},
    ]
    ctl = [
        {"seq": 0, "t": 1000.0, "mono": 50.0, "type": "clock_sync",
         "source": "ctl"},
        {"seq": 1, "t": 1000.1, "mono": 50.1, "type": "clock_sync",
         "source": "ctl", "peer": "A", "peer_t": 5000.0, "peer_mono": 7.0},
    ]
    agent = [
        {"seq": 0, "t": 5000.0, "mono": 7.0, "type": "clock_sync",
         "source": "A"},
        # The mutual half: the agent blesses the controller back.
        {"seq": 1, "t": 5000.05, "mono": 7.05, "type": "clock_sync",
         "source": "A", "peer": "ctl", "peer_t": 1000.0, "peer_mono": 50.0},
        {"seq": 2, "t": 5001.0, "mono": 8.0, "type": "job_start",
         "mode": "fleet", "n_keys": 10, "job_id": "f1"},
    ]
    merged = merge_records([driver, ctl, agent])
    start = next(r for r in merged if r["type"] == "job_start")
    # shift_ctl stays wall-anchored (-50); the agent resolves in ONE hop:
    # shift_A = shift_ctl + (50.1 - 7.0) -> job_start at mono 8 - 6.9.
    assert start["mono"] == pytest.approx(1.1, abs=1e-6)


def test_fleet_journals_carry_peer_blessings_live():
    """A real controller+agent pair journals the blessing on BOTH sides
    (welcome -> controller journal, hello -> agent journal)."""
    ja, jc = EventLog(), EventLog()
    a = FleetAgent(runner=_fast_runner, agent_id="A", journal=ja)
    ctl = FleetController([a.addr], heartbeat_s=0.3, journal=jc)
    try:
        d = np.arange(100, dtype=np.int32)[::-1].copy()
        v, t = ctl.submit(d, tenant="t")
        np.testing.assert_array_equal(t.result(timeout=60), np.sort(d))
        ctl_bless = [
            e.fields for e in jc.events()
            if e.type == "clock_sync" and e.fields.get("peer")
        ]
        assert ctl_bless and ctl_bless[0]["peer"] == "A"
        assert isinstance(ctl_bless[0]["peer_mono"], float)
        agent_bless = [
            e.fields for e in ja.events()
            if e.type == "clock_sync" and e.fields.get("peer")
        ]
        assert agent_bless
        assert agent_bless[0]["peer"] == ctl.controller_id
        # The merged trace orders sanely with journal 0 = controller.
        merged = merge_records([
            [e.to_dict() for e in jc.events()],
            [e.to_dict() for e in ja.events()],
        ])
        types = [r["type"] for r in merged]
        assert types.index("job_routed") < types.index("job_done")
    finally:
        _close_all(ctl, [a])


# -- registries + docs -------------------------------------------------------


def test_health_events_counters_and_frames_registered():
    for etype in ("health_verdict", "agent_degraded"):
        assert etype in EVENT_TYPES
    for counter in ("fleet_telemetry_frames", "health_verdicts",
                    "agent_degradations"):
        assert counter in COUNTERS
    assert "telemetry" in proto.FRAME_TYPES
    assert proto.ROUTING_POLICIES == ("locality", "random", "health")


def test_architecture_documents_health_plane():
    """§13's contract is test-enforced like §7-§12: the telemetry frame,
    the verdict schema (every HEALTH_VERDICT_KEYS name verbatim), the
    routing inputs and the degraded->flight-bundle contract."""
    arch = open(os.path.join(REPO, "ARCHITECTURE.md"), encoding="utf-8").read()
    assert "## 13. Health plane" in arch
    assert "`telemetry`" in arch
    for key in HEALTH_VERDICT_KEYS:
        assert f"`{key}`" in arch, f"verdict key {key} undocumented"
    for etype in ("health_verdict", "agent_degraded"):
        assert f"`{etype}`" in arch, f"health event {etype} undocumented"
    for term in ("TELEMETRY_BYTE_BUDGET", "MAX_ADVERTISED_VARIANTS",
                 "oldest-first", "heartbeats-only", "peer_mono",
                 "degraded", "flight bundle", 'routing="health"',
                 "HealthAnalyzer", "HealthDeltaCollector"):
        assert term in arch, f"§13 must explain {term}"


def test_fleet_cli_accepts_health_routing():
    from dsort_tpu import cli
    from dsort_tpu.config import ConfigError, FleetConfig

    # The parser refuses unknown policies; the config accepts "health".
    with pytest.raises(SystemExit):
        cli.main(["fleet", "--routing", "mystery", "--agents", "h:1"])
    assert FleetConfig(routing="health").routing == "health"
    with pytest.raises(ConfigError, match="routing"):
        FleetConfig(routing="mystery")
