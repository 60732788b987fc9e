"""Telemetry-plane tests (`dsort_tpu.obs`, PR 6 tentpole).

Covers the four pillars: journal aggregation (clock-aligned merge,
torn-line tolerance, multi-lane Chrome export), the live metrics endpoint
(Prometheus render + minimal-parser round trip + HTTP scrape), the
per-tenant SLO histograms (live tap == journal replay, exactly), and the
fault flight recorder (bundle schema + one drill per recovery path).  The
serve metrics-endpoint test is the acceptance path: `dsort serve
--metrics-port` scraped mid-session, quantiles asserted against the
journal-derived ground truth.
"""

import json
import urllib.request

import numpy as np
import pytest

from dsort_tpu.config import JobConfig
from dsort_tpu.obs import (
    BUNDLE_SCHEMA_KEYS,
    FlightRecorder,
    LatencyHistogram,
    MetricsServer,
    Telemetry,
    merge_journals,
    merge_records,
    parse_prometheus_text,
    read_journal,
    slo_from_journal,
)
from dsort_tpu.utils.events import EventLog, to_chrome_trace
from dsort_tpu.utils.metrics import Metrics

FAST = JobConfig(settle_delay_s=0.01)


# -- latency histogram -------------------------------------------------------


def test_histogram_quantile_is_upper_bound():
    h = LatencyHistogram()
    rng = np.random.default_rng(0)
    samples = rng.uniform(1e-3, 1.0, 500)
    for s in samples:
        h.observe(float(s))
    assert h.count == 500
    assert h.sum == pytest.approx(float(samples.sum()))
    for q in (0.5, 0.95, 0.99):
        exact = float(np.quantile(samples, q))
        got = h.quantile(q)
        # bucket-resolution contract: a hard upper bound, within one
        # 2^(1/4) bucket of the exact sample quantile
        assert exact <= got <= exact * 2 ** 0.5


def test_histogram_empty_and_determinism():
    assert LatencyHistogram().quantile(0.99) == 0.0
    a, b = LatencyHistogram(), LatencyHistogram()
    for v in (0.01, 0.02, 0.5, 0.5, 3.0):
        a.observe(v)
        b.observe(v)
    for q in (0.5, 0.95, 0.99):
        assert a.quantile(q) == b.quantile(q)


# -- journal merge -----------------------------------------------------------


def _journal_with(events, mono_base, wall_base):
    """Synthetic journal records: (type, dt, fields) at mono_base+dt."""
    out = []
    for seq, (etype, dt, fields) in enumerate(events):
        out.append({
            "seq": seq, "t": wall_base + dt, "mono": mono_base + dt,
            "type": etype, **fields,
        })
    return out


def test_merge_aligns_shifted_mono_bases():
    """Two journals over one wall timeline but wildly different monotonic
    bases must interleave at their true wall positions."""
    wall = 1_700_000_000.0
    a = _journal_with(
        [("job_start", 0.0, {"job": 1}), ("job_done", 0.4, {"job": 1})],
        mono_base=5.0, wall_base=wall,
    )
    b = _journal_with(
        [("clock_sync", 0.1, {"process": 1}),
         ("job_start", 0.2, {"job": 1}), ("job_done", 0.3, {"job": 1})],
        mono_base=9000.0, wall_base=wall,
    )
    merged = merge_records([a, b])
    types = [(r["src"], r["type"]) for r in merged]
    assert types == [
        (0, "job_start"), (1, "clock_sync"), (1, "job_start"),
        (1, "job_done"), (0, "job_done"),
    ]
    monos = [r["mono"] for r in merged]
    assert monos == sorted(monos)
    assert [r["seq"] for r in merged] == list(range(len(merged)))


def test_read_journal_skips_torn_lines(tmp_path):
    log = EventLog()
    log.emit("job_start", mode="spmd", n_keys=3)
    log.emit("job_done", n_keys=3)
    path = tmp_path / "j.jsonl"
    log.write_jsonl(str(path))
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"seq": 99, "t": 1.0, "mono"')  # torn mid-write
        f.write("\nnot json at all\n")
        f.write('{"no_required_keys": true}\n')
        f.write('{"seq": 3, "t": "NaNish", "mono": "x", "type": "probe"}\n')
    records, skipped = read_journal(str(path))
    assert [r["type"] for r in records] == ["job_start", "job_done"]
    assert skipped == 4


def test_merge_journals_files(tmp_path):
    paths = []
    for i in range(2):
        log = EventLog()
        log.emit("job_start", mode="spmd", n_keys=1, process=i)
        log.emit("clock_sync", process=i)
        log.emit("job_done", n_keys=1)
        p = tmp_path / f"j{i}.jsonl"
        log.write_jsonl(str(p))
        paths.append(str(p))
    merged, skipped = merge_journals(paths)
    assert skipped == 0
    assert len(merged) == 6
    assert {r["src"] for r in merged} == {0, 1}
    monos = [r["mono"] for r in merged]
    assert monos == sorted(monos)


# -- chrome trace: one lane per job ------------------------------------------


def test_chrome_trace_distinct_tids_per_concurrent_job():
    """Two jobs interleaved on ONE journal get distinct tids and no
    overlapping phase spans on any one tid (satellite 4)."""
    from dsort_tpu.utils.metrics import PhaseTimer

    journal = EventLog()
    m1, m2 = Metrics(journal=journal), Metrics(journal=journal)
    t1, t2 = PhaseTimer(m1), PhaseTimer(m2)
    m1.event("job_start", mode="spmd", n_keys=10)
    with t1.phase("partition"):
        # job 2 starts and runs a phase INSIDE job 1's phase
        m2.event("job_start", mode="spmd", n_keys=20)
        with t2.phase("partition"):
            pass
        m2.event("job_done", n_keys=20)
    m1.event("job_done", n_keys=10)

    trace = to_chrome_trace([e.to_dict() for e in journal.events()])
    evs = [e for e in trace["traceEvents"] if e["ph"] in ("B", "E", "i")]
    tids = {e["tid"] for e in evs}
    assert len(tids) == 2  # one lane per job
    # per tid: spans nest properly and never interleave with the other job
    for tid in tids:
        depth = 0
        for e in evs:
            if e["tid"] != tid:
                continue
            if e["ph"] == "B":
                depth += 1
            elif e["ph"] == "E":
                depth -= 1
                assert depth >= 0
        assert depth == 0
    # thread_name metadata names each job lane
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert len(meta) == 2


def test_chrome_trace_merged_sources_get_pids(tmp_path):
    logs = []
    for i in range(2):
        log = EventLog()
        m = Metrics(journal=log)
        m.event("job_start", mode="multihost", n_keys=1, process=i)
        m.event("job_done", n_keys=1)
        logs.append([e.to_dict() for e in log.events()])
    merged = merge_records(logs)
    trace = to_chrome_trace(merged)
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert pids == {1, 2}


# -- telemetry registry + endpooint ------------------------------------------


def _run_jobs_with_telemetry(mesh8, tenant="acme", jobs=3):
    """Real SPMD jobs through one journal + one telemetry registry."""
    from dsort_tpu.scheduler import SpmdScheduler

    tel = Telemetry()
    journal = EventLog()
    sched = SpmdScheduler(
        job=JobConfig(settle_delay_s=0.01, tenant=tenant), telemetry=tel
    )
    rng = np.random.default_rng(1)
    for i in range(jobs):
        m = Metrics(journal=journal)
        out = sched.sort(rng.integers(0, 10**6, 20_000).astype(np.int32), m)
        assert (np.diff(out) >= 0).all()
        m.event("result_fetch", n_keys=len(out))
    return tel, journal


def test_telemetry_slo_matches_journal_ground_truth(mesh8):
    """The core SLO contract: the LIVE tap and a post-hoc journal replay
    report byte-identical per-tenant quantiles (same stamps, same
    histogram)."""
    tel, journal = _run_jobs_with_telemetry(mesh8)
    parsed = parse_prometheus_text(tel.render_prometheus())
    truth = slo_from_journal([e.to_dict() for e in journal.events()])
    assert truth, "journal must derive SLO histograms"
    for (tenant, stage), hist in truth.items():
        assert tenant == "acme"
        for q in (0.5, 0.95, 0.99):
            key = (
                "dsort_job_stage_seconds",
                tuple(sorted({
                    "tenant": tenant, "stage": stage, "quantile": str(q),
                }.items())),
            )
            assert parsed[key] == pytest.approx(hist.quantile(q), rel=1e-5), (
                f"scrape vs journal mismatch for {tenant}/{stage} p{q}"
            )
        count_key = (
            "dsort_job_stage_seconds_count",
            tuple(sorted({"tenant": tenant, "stage": stage}.items())),
        )
        assert parsed[count_key] == hist.count
    # all four stages observed (dispatch from attempt_start, fetch from
    # result_fetch)
    stages = {s for (_, s) in truth}
    assert stages == {
        "admit_to_dispatch", "dispatch_to_sorted", "sorted_to_fetched",
        "admit_to_sorted",
    }


def test_telemetry_counters_and_jobs(mesh8):
    tel, journal = _run_jobs_with_telemetry(mesh8, jobs=2)
    parsed = parse_prometheus_text(tel.render_prometheus())
    assert parsed[("dsort_jobs_total",
                   (("outcome", "done"), ("tenant", "acme")))] == 2
    assert parsed[("dsort_jobs_in_flight", ())] == 0
    assert parsed[("dsort_queue_depth", ())] == 0
    # every registered counter renders (zero-valued included)
    from dsort_tpu.utils.events import COUNTERS

    names = {
        dict(labels)["name"]
        for (name, labels) in parsed
        if name == "dsort_counter_total"
    }
    assert set(COUNTERS) <= names
    # phase wall time flowed through phase_end events
    assert any(name == "dsort_phase_seconds_total" for name, _ in parsed)


def test_telemetry_counter_deltas_not_double_counted():
    """job_done carries CUMULATIVE counters; two job_done events on one
    Metrics must absorb deltas, not re-add the running total."""
    tel = Telemetry()
    m = Metrics()
    tel.attach(m)
    tel.attach(m)  # idempotent
    assert len(m.taps) == 1
    m.bump("mesh_reforms")
    m.event("job_start", mode="spmd", n_keys=1)
    m.event("job_done", n_keys=1, counters=dict(m.counters))
    m.bump("mesh_reforms")
    m.event("job_start", mode="spmd", n_keys=1)
    m.event("job_done", n_keys=1, counters=dict(m.counters))
    snap = tel.snapshot()
    assert snap["counters"]["mesh_reforms"] == 2  # not 1 + 2 = 3


def test_metrics_server_scrape_roundtrip():
    tel = Telemetry()
    tel.observe_stage("default", "admit_to_sorted", 0.05)
    tel.set_gauge("queue_depth", 4)
    with MetricsServer(tel, port=0) as srv:
        body = urllib.request.urlopen(srv.url, timeout=10).read().decode()
        parsed = parse_prometheus_text(body)
        assert parsed[("dsort_queue_depth", ())] == 4
        js = json.loads(
            urllib.request.urlopen(
                srv.url.replace("/metrics", "/json"), timeout=10
            ).read().decode()
        )
        assert js["gauges"]["queue_depth"] == 4
        ok = urllib.request.urlopen(
            srv.url.replace("/metrics", "/healthz"), timeout=10
        )
        assert ok.read() == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                srv.url.replace("/metrics", "/nope"), timeout=10
            )


def test_parse_prometheus_rejects_garbage():
    with pytest.raises(ValueError):
        parse_prometheus_text("dsort_counter_total{name=unquoted} 1\n")
    with pytest.raises(ValueError):
        parse_prometheus_text("just words\n")


def test_dsort_top_renders_scrape(capsys):
    from dsort_tpu import cli

    tel = Telemetry()
    tel.observe_stage("acme", "admit_to_sorted", 0.02)
    tel.set_gauge("queue_depth", 1)
    with MetricsServer(tel, port=0) as srv:
        assert cli.main(["top", srv.url]) == 0
    out = capsys.readouterr().out
    assert "jobs in flight" in out and "queue depth: 1" in out
    assert "acme/admit_to_sorted" in out and "p95" in out


def test_dsort_top_unreachable_endpoint_fails_loudly():
    from dsort_tpu import cli

    assert cli.main(["top", "http://127.0.0.1:1/metrics"]) == 1


# -- flight recorder ---------------------------------------------------------


def test_flight_recorder_ring_and_bundle_schema(tmp_path):
    rec = FlightRecorder(
        str(tmp_path), ring_size=4, state_fn=lambda: {"mode": "unit"},
        config=FAST,
    )
    m = Metrics()
    rec.attach(m)
    rec.attach(m)  # idempotent
    assert m.taps.count(rec) == 1
    for i in range(10):
        m.event("probe", worker=i, ok=True)
    assert len(rec.events()) == 4  # bounded ring
    m.event("mesh_reform", survivors=7)
    bundles = FlightRecorder.read_bundles(str(tmp_path))
    assert len(bundles) == 1
    b = bundles[0]
    assert set(BUNDLE_SCHEMA_KEYS) <= set(b)
    assert b["recovery_path"] == "mesh_reform"
    assert b["detail"]["survivors"] == 7
    assert b["state"] == {"mode": "unit"}
    assert b["config"]["settle_delay_s"] == 0.01
    # the ring carries the recent past INCLUDING the trigger
    assert b["ring"][-1]["type"] == "mesh_reform"
    assert any(r["type"] == "probe" for r in b["ring"])
    # the dump itself is journaled + counted
    assert m.counters["flight_dumps"] == 1


def test_flight_bundle_schema_documented():
    """ARCHITECTURE documents the bundle format; the schema keys are the
    contract, so each must appear there verbatim (satellite: test-enforced
    bundle schema)."""
    import os

    arch = open(
        os.path.join(os.path.dirname(os.path.dirname(__file__)),
                     "ARCHITECTURE.md"),
        encoding="utf-8",
    ).read()
    for key in BUNDLE_SCHEMA_KEYS:
        assert f'"{key}"' in arch, (
            f"bundle key {key!r} missing from ARCHITECTURE.md §observability"
        )


# -- flight drills: one bundle per recovery path -----------------------------


def _bundles(d):
    return FlightRecorder.read_bundles(str(d))


def test_flight_drill_mesh_reform(mesh8, tmp_path):
    from dsort_tpu.scheduler import FaultInjector, SpmdScheduler

    inj = FaultInjector()
    inj.fail_once(2, "spmd")
    sched = SpmdScheduler(
        job=JobConfig(settle_delay_s=0.01, flight_recorder_dir=str(tmp_path)),
        injector=inj,
    )
    data = np.random.default_rng(2).integers(0, 10**6, 50_000).astype(np.int32)
    m = Metrics()
    out = sched.sort(data, m)
    np.testing.assert_array_equal(out, np.sort(data))
    paths = [b["recovery_path"] for b in _bundles(tmp_path)]
    assert "mesh_reform" in paths
    b = next(b for b in _bundles(tmp_path) if b["recovery_path"] == "mesh_reform")
    # names the cost: 7 survivors, and the counters snapshot carries the
    # re-form count at dump time
    assert b["detail"]["survivors"] == 7
    assert b["counters"].get("mesh_reforms", 0) >= 1
    assert any(
        r["type"] == "worker_dead" and r.get("worker") == 2 for r in b["ring"]
    )
    assert b["state"]["mode"] == "spmd"


def test_flight_drill_capacity_retry(mesh8, tmp_path):
    from dsort_tpu.scheduler import SpmdScheduler

    sched = SpmdScheduler(
        job=JobConfig(
            settle_delay_s=0.01, capacity_factor=1.0,
            flight_recorder_dir=str(tmp_path),
        ),
    )
    data = np.full(40_000, 7, np.int32)  # one bucket takes everything
    out = sched.sort(data, Metrics())
    np.testing.assert_array_equal(out, data)
    b = next(
        b for b in _bundles(tmp_path)
        if b["recovery_path"] == "capacity_retry"
    )
    assert b["detail"]["observed"] > 0 and b["detail"]["cap_pair"] > 0


def test_flight_drill_taskpool_reassign(tmp_path):
    from dsort_tpu.scheduler import DeviceExecutor, FaultInjector, Scheduler

    inj = FaultInjector()
    inj.fail_once(1, "sort")
    sched = Scheduler(
        DeviceExecutor(injector=inj),
        JobConfig(settle_delay_s=0.01, flight_recorder_dir=str(tmp_path)),
    )
    data = np.random.default_rng(3).integers(0, 10**6, 8_000).astype(np.int32)
    out = sched.run_job(data, Metrics())
    np.testing.assert_array_equal(out, np.sort(data))
    b = next(
        b for b in _bundles(tmp_path) if b["recovery_path"] == "reassign"
    )
    assert b["detail"]["frm"] == 1  # the dead worker the shard moved off
    assert b["state"]["mode"] == "taskpool"
    assert b["counters"].get("reassignments", 0) >= 1


def test_flight_drill_mid_ring_loss(mesh8, tmp_path):
    from dsort_tpu.scheduler import FaultInjector, SpmdScheduler

    inj = FaultInjector()
    inj.fail_once(3, "ring")
    sched = SpmdScheduler(
        job=JobConfig(
            settle_delay_s=0.01, exchange="ring",
            flight_recorder_dir=str(tmp_path),
        ),
        injector=inj,
    )
    data = np.random.default_rng(4).integers(0, 10**6, 50_000).astype(np.int32)
    out = sched.sort(data, Metrics())
    np.testing.assert_array_equal(out, np.sort(data))
    b = next(
        b for b in _bundles(tmp_path) if b["recovery_path"] == "mesh_reform"
    )
    # the ring names WHERE the loss happened: mid-ring, not dispatch
    assert any(
        r["type"] == "worker_dead" and r.get("stage") == "ring"
        for r in b["ring"]
    )


def test_flight_drill_handle_invalidation(mesh8, tmp_path):
    from dsort_tpu.scheduler import FaultInjector, SpmdScheduler

    inj = FaultInjector()
    sched = SpmdScheduler(
        job=JobConfig(settle_delay_s=0.01, flight_recorder_dir=str(tmp_path)),
        injector=inj,
    )
    data = np.random.default_rng(5).integers(0, 10**6, 50_000).astype(np.int32)
    m = Metrics()
    handle = sched.sort(data, m, keep_on_device=True)
    inj.fail_once(2, "spmd")
    sched.sort(data, m)  # second job loses a device -> re-form -> invalidate
    np.testing.assert_array_equal(handle.to_host(), np.sort(data))  # re-runs
    b = next(
        b for b in _bundles(tmp_path)
        if b["recovery_path"] == "device_handle_invalidated"
    )
    assert b["detail"]["reason"] == "mesh_reform"
    assert b["detail"]["n"] == 1


def test_flight_drill_checkpoint_restore(mesh8, tmp_path):
    from dsort_tpu.scheduler import SpmdScheduler

    job = JobConfig(
        settle_delay_s=0.01,
        checkpoint_dir=str(tmp_path / "ck"),
        flight_recorder_dir=str(tmp_path / "flight"),
    )
    data = np.random.default_rng(6).integers(0, 10**6, 30_000).astype(np.int32)
    SpmdScheduler(job=job).sort(data, Metrics(), job_id="j1")
    # a fresh scheduler resumes the persisted job: the restore IS the
    # recovery path the recorder must name
    out = SpmdScheduler(job=job).sort(data, Metrics(), job_id="j1")
    np.testing.assert_array_equal(out, np.sort(data))
    restores = [
        b for b in _bundles(tmp_path / "flight")
        if b["recovery_path"].startswith("checkpoint_restore")
    ]
    assert restores, "restore run must dump a bundle naming the resume path"
    assert any(
        b["recovery_path"] == "checkpoint_restore:shuffle_phase"
        for b in restores
    )


# -- the acceptance path: serve smoke + scrape vs journal --------------------


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_serve_metrics_endpoint_smoke(tmp_path, monkeypatch):
    """Tier-1 gate (satellite 6 + acceptance): `dsort serve` under the
    in-suite smoke exposes a scrape-able endpoint whose Prometheus text
    round-trips the minimal parser and whose per-tenant p50/p95/p99 equal
    the journal-derived ground truth."""
    from dsort_tpu import cli

    rng = np.random.default_rng(7)
    files = []
    for i in range(3):
        p = tmp_path / f"in{i}.txt"
        p.write_text(
            "\n".join(str(x) for x in rng.integers(0, 10**6, 2000 + 500 * i))
        )
        files.append(str(p))
    journal = tmp_path / "serve.jsonl"
    port = _free_port()
    scraped = {}

    feed = iter(files)

    def fake_input(prompt=""):
        try:
            return next(feed)
        except StopIteration:
            # all jobs done, server still up: THE mid-session scrape
            url = f"http://127.0.0.1:{port}/metrics"
            scraped["text"] = urllib.request.urlopen(
                url, timeout=10
            ).read().decode()
            return "exit"

    monkeypatch.setattr("builtins.input", fake_input)
    rc = cli.main([
        "serve", "-o", str(tmp_path / "out.txt"), "--mode", "local",
        "--journal", str(journal), "--tenant", "acme",
        "--metrics-port", str(port),
    ])
    assert rc == 0
    assert scraped, "the scrape must have happened while serve was alive"
    parsed = parse_prometheus_text(scraped["text"])  # round-trips

    records, skipped = read_journal(str(journal))
    assert skipped == 0
    truth = slo_from_journal(records)
    tenants = {t for (t, _) in truth}
    assert tenants == {"acme"}
    for (tenant, stage), hist in truth.items():
        for q in (0.5, 0.95, 0.99):
            key = (
                "dsort_job_stage_seconds",
                tuple(sorted({
                    "tenant": tenant, "stage": stage, "quantile": str(q),
                }.items())),
            )
            assert parsed[key] == pytest.approx(hist.quantile(q), rel=1e-5)
    assert parsed[("dsort_jobs_total",
                   (("outcome", "done"), ("tenant", "acme")))] == 3
    # the serve session's phase wall time reached the endpoint too
    assert any(
        name == "dsort_phase_seconds_total" for (name, _) in parsed
    )


def test_failed_job_closes_on_telemetry(tmp_path):
    """A sorter that raises AFTER job_start must not leave the job open:
    `_run_one` closes it with job_failed, so jobs_in_flight returns to 0
    and the journal records the failure (code-review r6 fix)."""
    from dsort_tpu import cli

    inp = tmp_path / "in.txt"
    inp.write_text("3\n1\n2\n")
    journal = EventLog()
    tel = Telemetry()

    def exploding_sorter(data, metrics, job_id=None):
        metrics.event("job_start", mode="spmd", n_keys=len(data))
        raise OSError("disk full mid-checkpoint")

    with pytest.raises(OSError):
        cli._run_one(
            exploding_sorter, str(inp), str(tmp_path / "out.txt"),
            np.int32, journal=journal, telemetry=tel,
        )
    types = journal.types()
    assert types[0] == "job_start" and types[-1] == "job_failed"
    snap = tel.snapshot()
    assert snap["jobs_in_flight"] == 0
    assert snap["jobs"] == {"default/failed": 1}


def test_histogram_overflow_bucket_reports_observed_max():
    """Durations past the last bucket bound must not silently cap the
    quantile at the bound — the observed max is the only honest answer."""
    from dsort_tpu.obs.histogram import BUCKET_BOUNDS

    h = LatencyHistogram()
    h.observe(BUCKET_BOUNDS[-1] * 10)
    assert h.quantile(0.99) == BUCKET_BOUNDS[-1] * 10
    # a day-long job is within the bounded range (admission-control SLOs)
    assert BUCKET_BOUNDS[-1] > 24 * 3600


def test_read_bundles_orders_by_dump_time(tmp_path):
    """Bundles from several processes in one directory read back in
    wall-clock dump order, not pid-grouped filename order."""
    for name, t in (
        ("flight_900_0001_reassign.json", 3.0),
        ("flight_100_0001_mesh_reform.json", 2.0),
        ("flight_500_0001_capacity_retry.json", 1.0),
    ):
        (tmp_path / name).write_text(json.dumps({"t": t, "recovery_path": "x"}))
    got = [b["t"] for b in FlightRecorder.read_bundles(str(tmp_path))]
    assert got == [1.0, 2.0, 3.0]


def test_report_merge_cli(tmp_path, capsys):
    """`dsort report --merge a b` renders ONE aligned timeline and exports
    a multi-lane chrome trace; torn lines are skipped, not fatal."""
    from dsort_tpu import cli

    paths = []
    for i in range(2):
        log = EventLog()
        m = Metrics(journal=log)
        m.event("job_start", mode="multihost", n_keys=5, process=i)
        m.event("clock_sync", process=i)
        m.event("job_done", n_keys=5)
        p = tmp_path / f"p{i}.jsonl"
        log.write_jsonl(str(p))
        paths.append(str(p))
    with open(paths[1], "a", encoding="utf-8") as f:
        f.write('{"torn line\n')
    trace = tmp_path / "trace.json"
    rc = cli.main(
        ["report", "--merge", *paths, "--chrome-trace", str(trace)]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "timeline:" in out
    assert out.count("job_start") >= 2  # both processes' jobs, one report
    loaded = json.loads(trace.read_text())
    assert {e["pid"] for e in loaded["traceEvents"]} == {1, 2}
