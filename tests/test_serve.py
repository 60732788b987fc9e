"""Serving-layer tests (ISSUE 7, ARCHITECTURE §8): admission control,
deficit-round-robin fairness (asserted from the journal, never from
sleeps), mesh-slice packing bit-identical to serial execution, the
compiled-variant cache, concurrent-job fault drills with per-eviction
flight bundles, graceful shutdown, and the `dsort serve` CLI."""

import os

import numpy as np
import pytest

from dsort_tpu.analysis.spec import assert_conformant
from dsort_tpu.config import ConfigError, JobConfig, ServeConfig, SortConfig
from dsort_tpu.obs import Telemetry
from dsort_tpu.obs.telemetry import parse_prometheus_text
from dsort_tpu.scheduler import FaultInjector
from dsort_tpu.serve import (
    ADMISSION_REASONS,
    Admission,
    AdmissionController,
    DeficitRoundRobin,
    ServiceClosed,
    SortService,
    VariantCache,
    fused_variant_key,
    parse_weights,
)
from dsort_tpu.utils.events import EVENT_TYPES, EventLog

JOB = JobConfig(settle_delay_s=0.01)


def _svc(tmp=None, injector=None, telemetry=None, journal=None, start=True,
         serve=None, job=None):
    job = job or JOB
    if tmp is not None:
        import dataclasses

        job = dataclasses.replace(job, flight_recorder_dir=str(tmp))
    return SortService(
        job=job,
        serve=serve or ServeConfig(small_job_max=1 << 18,
                                   max_tenant_inflight=32,
                                   max_queue_depth=128),
        telemetry=telemetry, journal=journal, injector=injector, start=start,
    )


def _events(journal):
    return [(e.type, e.fields) for e in journal.events()]


# -- admission control -------------------------------------------------------


def test_admission_verdict_vocabulary():
    with pytest.raises(ValueError, match="unknown admission reason"):
        Admission(False, "because", "t", 0, 0)
    ctl = AdmissionController(max_queue_depth=2, max_tenant_inflight=1)
    v1 = ctl.consider("a", shutting_down=False)
    assert v1.admitted and v1.reason == "admitted" and v1.queue_depth == 1
    v2 = ctl.consider("a", shutting_down=False)
    assert not v2.admitted and v2.reason == "tenant_limit"
    ctl.consider("b", shutting_down=False)
    v3 = ctl.consider("c", shutting_down=False)
    assert v3.reason == "queue_full"
    v4 = ctl.consider("a", shutting_down=True)
    assert v4.reason == "shutting_down"
    # release: a finished improves the tenant budget, dequeue the queue
    ctl.dequeued()
    ctl.finished("a")
    v5 = ctl.consider("a", shutting_down=False)
    assert v5.admitted


def test_service_rejects_beyond_queue_depth(devices):
    journal = EventLog()
    tel = Telemetry()
    svc = SortService(
        job=JOB,
        serve=ServeConfig(max_queue_depth=3, max_tenant_inflight=2,
                          small_job_max=1 << 18),
        telemetry=tel, journal=journal, start=False,
    )
    data = np.arange(100, dtype=np.int32)
    verdicts = [svc.submit(data, tenant=f"t{i}")[0] for i in range(5)]
    reasons = [v.reason for v in verdicts]
    assert reasons[:3] == ["admitted"] * 3
    assert set(reasons[3:]) <= {"queue_full", "tenant_limit"}
    # tenant_limit fires independently of global depth
    v_same = svc.submit(data, tenant="t0")[0]
    assert not v_same.admitted
    svc.shutdown(drain=True)
    types = [t for t, _ in _events(journal)]
    assert types.count("job_admitted") == 3
    assert types.count("job_rejected") == 3
    # every verdict reached the per-tenant admission series
    snap = tel.snapshot()
    assert sum(snap["admissions"].values()) == 6
    assert snap["admissions"]["t0/admitted"] == 1


# -- deficit round robin -----------------------------------------------------


def test_parse_weights():
    assert parse_weights("acme=2, blue=1.5") == {"acme": 2.0, "blue": 1.5}
    assert parse_weights(None) == {}
    with pytest.raises(ValueError, match="NAME=WEIGHT"):
        parse_weights("acme")
    with pytest.raises(ValueError, match="> 0"):
        parse_weights("acme=0")


def test_drr_interleaves_tenants():
    drr = DeficitRoundRobin(quantum=10)
    for i in range(6):
        drr.push("heavy", 10, f"h{i}")
    for i in range(2):
        drr.push("light", 10, f"l{i}")
    order = []
    while True:
        nxt = drr.pop()
        if nxt is None:
            break
        order.append(nxt[0])
    # one job per visit at quantum == cost: strict alternation while both
    # queues are non-empty, then the heavy backlog drains
    assert order[:4] == ["heavy", "light", "heavy", "light"]
    assert len(order) == 8 and order.count("light") == 2


def test_drr_weights_give_proportional_share():
    drr = DeficitRoundRobin(quantum=10, weights={"gold": 2.0})
    for i in range(8):
        drr.push("gold", 10, i)
        drr.push("base", 10, i)
    first8 = [drr.pop()[0] for _ in range(8)]
    assert first8.count("gold") >= 5  # ~2/3 share for weight 2


def test_drr_big_job_accumulates_without_starving():
    drr = DeficitRoundRobin(quantum=10)
    drr.push("big", 100, "B")
    for i in range(5):
        drr.push("small", 10, f"s{i}")
    order = [drr.pop()[1] for _ in range(6)]
    assert set(order[:5]) == {"s0", "s1", "s2", "s3", "s4"}
    assert order[5] == "B"  # dispatched once its deficit covers the cost


def test_drr_idle_tenant_banks_no_credit():
    drr = DeficitRoundRobin(quantum=10)
    drr.push("a", 10, "a0")
    assert drr.pop() == ("a", "a0")
    # 'a' drained; many rounds later it must not burst ahead of 'b'
    for i in range(3):
        drr.push("b", 10, f"b{i}")
    drr.push("a", 10, "a1")
    order = [drr.pop()[0] for _ in range(4)]
    assert order.count("a") == 1


# -- compiled-variant cache --------------------------------------------------


def test_variant_cache_lru_and_counters():
    from dsort_tpu.utils.metrics import Metrics

    m = Metrics()
    cache = VariantCache(max_entries=2)
    built = []

    def builder(tag):
        return lambda: built.append(tag) or tag

    assert cache.get_or_build(("k", 1), builder(1), metrics=m) == 1
    assert cache.get_or_build(("k", 1), builder("dup"), metrics=m) == 1
    assert cache.get_or_build(("k", 2), builder(2), metrics=m) == 2
    assert cache.get_or_build(("k", 3), builder(3), metrics=m) == 3  # evicts 1
    assert cache.stats() == {
        "entries": 2, "hits": 1, "misses": 3, "evictions": 1, "prewarmed": 0,
    }
    assert m.counters["variant_cache_hits"] == 1
    assert m.counters["variant_cache_misses"] == 3
    assert m.counters["variant_cache_evictions"] == 1
    # key 1 was evicted: rebuilding is a miss again
    cache.get_or_build(("k", 1), builder("again"), metrics=m)
    assert built == [1, 2, 3, "again"]


def test_variant_cache_prewarm_counts_separately():
    cache = VariantCache(max_entries=8)
    assert cache.prewarm(("k", 1), lambda: "v") == ("v", True)
    assert cache.prewarm(("k", 1), lambda: "v2") == ("v", False)  # present
    st = cache.stats()
    assert st["prewarmed"] == 1 and st["misses"] == 0
    # a later lookup of the prewarmed key is a HIT
    assert cache.get_or_build(("k", 1), lambda: "v3") == "v"
    assert cache.stats()["hits"] == 1


def test_variant_keys_quantize_to_ladder_rungs():
    from dsort_tpu.models.pipelines import pad_rung
    from dsort_tpu.parallel.exchange import ladder_rungs

    # every enumerated rung is its own pad (the ladder is a fixed point)
    rungs = ladder_rungs(1 << 16, lo=8)
    assert all(pad_rung(r) == r for r in rungs)
    assert rungs == sorted(set(rungs))
    # any size maps to a rung on the enumerated ladder
    for n in (1, 7, 9, 100, 5000, 12345, 65535):
        assert pad_rung(n) in rungs
    # nearby sizes share a rung -> shared compiled variant
    k1 = fused_variant_key(5000, "int32", "auto")
    k2 = fused_variant_key(5100, "int32", "auto")
    assert k1 == k2
    assert fused_variant_key(50000, "int32", "auto") != k1


# -- the serving core --------------------------------------------------------


@pytest.mark.parametrize("one_slice", [False, True],
                         ids=["packed_with_large", "one_slice_small_only"])
def test_mixed_workload_bit_identical_and_cached(devices, one_slice):
    """≥8 small jobs across ≥3 tenants, submitted concurrently, beside 1
    large job on the packed mesh or alone on a service whose one slice is
    the whole mesh: every output bit-identical to serial execution
    (np.sort), repeat-size cache hit rate ≥ 50% (acceptance)."""
    journal = EventLog()
    tel = Telemetry()
    serve = None
    if one_slice:
        serve = ServeConfig(small_job_max=1 << 18, max_tenant_inflight=32,
                            max_queue_depth=128, slice_devices=len(devices))
    svc = _svc(telemetry=tel, journal=journal, serve=serve)
    assert svc.stats()["slices"] == (1 if one_slice else len(devices))
    rng = np.random.default_rng(0)
    jobs = []
    for i in range(9):
        d = rng.integers(0, 1 << 30, 8000 + (i % 2) * 500, dtype=np.int32)
        _, t = svc.submit(d, tenant=f"tenant{i % 3}")
        jobs.append((d, t))
    if not one_slice:
        big = rng.integers(0, 1 << 30, 1 << 18, dtype=np.int32)
        v, tbig = svc.submit(big, tenant="tenant0")
        assert v.admitted
        jobs.append((big, tbig))
    for d, t in jobs:
        np.testing.assert_array_equal(t.result(timeout=300), np.sort(d))
    assert svc.variants.hit_rate() >= 0.5
    st = svc.stats()
    assert st["done"] == len(jobs) and st["failed"] == 0
    svc.shutdown(drain=True)
    types = [t for t, _ in _events(journal)]
    assert types.count("job_admitted") == len(jobs)
    assert types.count("job_done") == len(jobs)
    assert types.count("result_fetch") == len(jobs)
    # the big job went to the full mesh, the small ones onto slices
    deq = [f for t, f in _events(journal) if t == "job_dequeued"]
    assert sum(1 for f in deq if f["big"]) == len(jobs) - 9
    assert sum(1 for f in deq if not f["big"]) == 9


def test_fairness_from_journal_no_tenant_starved(devices):
    """Journal-derived fairness (acceptance): with equal weights, a heavy
    tenant's backlog cannot starve light tenants — dequeue order from the
    journal, no sleeps."""
    journal = EventLog()
    svc = SortService(
        job=JOB,
        serve=ServeConfig(small_job_max=1 << 18, max_tenant_inflight=64,
                          max_queue_depth=128, slice_devices=8),
        journal=journal, start=False,
    )  # slice_devices=8 -> ONE slice: strictly serial dispatch order
    rng = np.random.default_rng(1)
    for i in range(8):
        svc.submit(rng.integers(0, 1000, 5000, dtype=np.int32), tenant="heavy")
    for i in range(2):
        svc.submit(rng.integers(0, 1000, 5000, dtype=np.int32), tenant="light")
    svc.start()
    svc.shutdown(drain=True)
    deq = [f for t, f in _events(journal) if t == "job_dequeued"]
    order = [f["tenant"] for f in deq]
    assert len(order) == 10
    # both light jobs dispatch inside the first DRR rotations
    assert order.index("light") < 4, f"light starved: {order}"
    # and the journal's measured queue waits hold the 3x p95 bound
    waits = {}
    for f in deq:
        waits.setdefault(f["tenant"], []).append(f["wait_s"])
    p95 = {t: float(np.percentile(w, 95)) for t, w in waits.items()}
    assert max(p95.values()) <= 3 * max(min(p95.values()), 1e-9) + 0.5


def test_weighted_tenant_gets_proportional_share(devices):
    journal = EventLog()
    svc = SortService(
        job=JOB,
        serve=ServeConfig(small_job_max=1 << 18, max_tenant_inflight=64,
                          max_queue_depth=128, slice_devices=8,
                          tenant_weights={"gold": 2.0}),
        journal=journal, start=False,
    )
    rng = np.random.default_rng(2)
    for i in range(6):
        svc.submit(rng.integers(0, 1000, 8000, dtype=np.int32), tenant="gold")
        svc.submit(rng.integers(0, 1000, 8000, dtype=np.int32), tenant="base")
    svc.start()
    svc.shutdown(drain=True)
    order = [f["tenant"] for t, f in _events(journal) if t == "job_dequeued"]
    assert order[:6].count("gold") >= 4  # ~2x share while both queues full


def test_queue_wait_is_the_admit_to_dispatch_slo(devices):
    """The service emits job_start at ADMISSION, so the existing
    admit_to_dispatch histogram IS the queue wait — live scrape and
    journal replay agree per tenant (PR 6 contract extended to the
    serving layer)."""
    from dsort_tpu.obs.slo import slo_from_journal

    journal = EventLog()
    tel = Telemetry()
    svc = _svc(telemetry=tel, journal=journal)
    rng = np.random.default_rng(3)
    tickets = [
        svc.submit(rng.integers(0, 1000, 4000, dtype=np.int32),
                   tenant="acme")[1]
        for _ in range(3)
    ]
    for t in tickets:
        t.result(timeout=120)
    svc.shutdown(drain=True)
    records = [e.to_dict() for e in journal.events()]
    truth = slo_from_journal(records)
    assert ("acme", "admit_to_dispatch") in truth
    scrape = parse_prometheus_text(tel.render_prometheus())
    for q in (0.5, 0.95, 0.99):
        key = ("dsort_job_stage_seconds", tuple(sorted({
            "tenant": "acme", "stage": "admit_to_dispatch",
            "quantile": str(q),
        }.items())))
        assert scrape[key] == pytest.approx(
            truth[("acme", "admit_to_dispatch")].quantile(q), rel=1e-5
        )


def test_cache_stats_reach_metrics_endpoint(devices):
    tel = Telemetry()
    svc = _svc(telemetry=tel)
    svc.prewarm(sizes=[4000])
    rng = np.random.default_rng(4)
    for _ in range(2):
        svc.submit(rng.integers(0, 1000, 4000, dtype=np.int32))[1].result(120)
    svc.shutdown(drain=True)
    scrape = parse_prometheus_text(tel.render_prometheus())
    assert scrape[("dsort_variant_cache_entries", ())] >= 1
    assert scrape[("dsort_variant_cache_prewarmed", ())] == 1
    assert scrape[("dsort_variant_cache_hits", ())] == 2  # both jobs warm
    assert scrape[("dsort_counter_total", (("name", "variant_cache_prewarms"),))] == 1
    # the journal-side counters flowed through job_done absorption too
    assert scrape[("dsort_counter_total", (("name", "variant_cache_hits"),))] == 2
    assert scrape[("dsort_counter_total", (("name", "jobs_admitted"),))] == 2
    assert scrape[("dsort_counter_total", (("name", "slice_dispatches"),))] == 2


def test_top_renders_cache_and_admissions(capsys):
    tel = Telemetry()
    tel.set_gauge("variant_cache_entries", 3)
    tel.set_gauge("variant_cache_hits", 9)
    tel.set_gauge("variant_cache_misses", 3)
    tel.set_gauge("variant_cache_prewarmed", 2)
    tel.admission_verdict("acme", "admitted")
    tel.admission_verdict("acme", "queue_full")
    from dsort_tpu.obs.top import render_top

    out = render_top(parse_prometheus_text(tel.render_prometheus()))
    assert "variant cache: 3 entries" in out
    assert "hit rate 75.0%" in out
    assert "admissions:" in out and "queue_full" in out


def test_prewarm_ladder_rungs(devices):
    tel = Telemetry()
    svc = _svc(telemetry=tel)
    n = svc.prewarm(sizes=[3000, 3050, 9000])  # two distinct rungs
    assert n == 2
    assert svc.prewarm(sizes=[3000]) == 0  # idempotent
    rng = np.random.default_rng(5)
    d = rng.integers(0, 1000, 3050, dtype=np.int32)
    _, t = svc.submit(d)
    np.testing.assert_array_equal(t.result(120), np.sort(d))
    st = svc.variants.stats()
    assert st["prewarmed"] == 2 and st["hits"] >= 1 and st["misses"] == 0
    svc.shutdown(drain=True)


# -- concurrent-job fault drills --------------------------------------------


def test_fault_drill_concurrent_jobs_two_tenants(devices, tmp_path):
    """Satellite: inject a device loss while ≥3 jobs from 2 tenants are
    queued/in-flight; every job either completes bit-identical or is
    re-admitted and completes — exact journal sequences, one
    flight-recorder bundle per affected job."""
    from dsort_tpu.obs.flight import FlightRecorder

    inj = FaultInjector()
    journal = EventLog()
    svc = _svc(tmp=tmp_path, injector=inj, journal=journal, start=False)
    rng = np.random.default_rng(6)
    inj.fail_once(0, "slice")   # first small dispatch on slice 0 dies
    inj.fail_once(2, "spmd")    # the big job loses device 2 mid-mesh
    jobs = []
    for i in range(4):
        d = rng.integers(0, 1 << 30, 9000, dtype=np.int32)
        v, t = svc.submit(d, tenant=["acme", "blue"][i % 2])
        assert v.admitted
        jobs.append((d, t))
    big = rng.integers(0, 1 << 30, 1 << 18, dtype=np.int32)
    _, tbig = svc.submit(big, tenant="acme")
    svc.start()
    for d, t in jobs:
        np.testing.assert_array_equal(t.result(timeout=300), np.sort(d))
    np.testing.assert_array_equal(tbig.result(timeout=300), np.sort(big))
    svc.shutdown(drain=True)
    evs = [(e.type, e.fields) for e in journal.events()]
    evicted_jobs = {f["job"] for t, f in evs if t == "job_evicted"}
    assert len(evicted_jobs) == 1
    job = next(iter(evicted_jobs))
    # The exact per-job recovery sequence is the declared `job_lifecycle`
    # grammar (ISSUE 17): the contract engine replays every job's trace
    # — one admission, dequeue/attempt rounds with the evict->readmit
    # loop, at most one terminal — instead of a hand-rolled literal.
    report = assert_conformant(journal)
    assert report["contracts"]["job_lifecycle"]["checked"] == 5
    # Behavioral facts the grammar alone cannot pin: the evicted job went
    # around the loop exactly once and completed.
    seq = [t for t, f in evs if f.get("job") == job]
    assert seq.index("job_evicted") < seq.index("job_readmitted")
    assert seq.index("job_readmitted") < seq.index("job_done")
    assert seq.count("job_dequeued") == 2 and seq.count("attempt_start") == 2
    # one flight bundle per eviction, naming the path and the tenant
    bundles = [
        b for b in FlightRecorder.read_bundles(str(tmp_path))
        if b["recovery_path"] == "job_evicted"
    ]
    assert len(bundles) == len(evicted_jobs)
    assert bundles[0]["detail"]["tenant"] in ("acme", "blue")
    assert bundles[0]["state"]["mode"] == "serve"
    # the big job recovered via the SPMD mesh re-form (its own bundle)
    assert any(t == "mesh_reform" for t, _ in evs)
    reform_bundles = [
        b for b in FlightRecorder.read_bundles(str(tmp_path))
        if b["recovery_path"].startswith("mesh_reform")
    ]
    assert len(reform_bundles) == 1


def test_slice_retired_after_dead_probe(devices, monkeypatch):
    """A slice whose lead device fails its probe leaves the packing
    rotation; the evicted job completes on another slice."""
    inj = FaultInjector()
    journal = EventLog()
    svc = _svc(injector=inj, journal=journal, start=False)
    inj.fail_once(0, "slice")
    inj.fail_once(0, "probe")  # the post-eviction probe fails too
    rng = np.random.default_rng(7)
    d = rng.integers(0, 1 << 30, 9000, dtype=np.int32)
    _, t = svc.submit(d, tenant="acme")
    svc.start()
    np.testing.assert_array_equal(t.result(timeout=300), np.sort(d))
    svc.shutdown(drain=True)
    types = [e.type for e in journal.events()]
    assert "slice_retired" in types
    assert svc.stats()["slices"] == 7


def test_fullmesh_reform_retires_dead_slice(devices):
    """A device permanently lost under a FULL-mesh job leaves the slice
    rotation too (the scheduler's reform listener), so later small jobs
    never dispatch onto the corpse."""
    inj = FaultInjector()
    journal = EventLog()
    svc = _svc(injector=inj, journal=journal)
    rng = np.random.default_rng(11)
    inj.kill(5)  # permanent: the re-form probe fails too
    big = rng.integers(0, 1 << 30, 1 << 18, dtype=np.int32)
    _, tbig = svc.submit(big, tenant="acme")
    np.testing.assert_array_equal(tbig.result(timeout=300), np.sort(big))
    assert svc.stats()["slices"] == 7
    retired = [f for t, f in _events(journal) if t == "slice_retired"]
    assert retired and retired[0]["reason"] == "mesh_reform"
    # small jobs keep completing on the surviving slices
    d = rng.integers(0, 1 << 30, 7000, dtype=np.int32)
    _, t = svc.submit(d, tenant="blue")
    np.testing.assert_array_equal(t.result(timeout=300), np.sort(d))
    svc.shutdown(drain=True)


# -- graceful shutdown -------------------------------------------------------


def test_shutdown_drains_queued_jobs(devices):
    journal = EventLog()
    svc = _svc(journal=journal, start=False)
    rng = np.random.default_rng(8)
    jobs = [
        (d := rng.integers(0, 1000, 5000, dtype=np.int32),
         svc.submit(d, tenant="acme")[1])
        for _ in range(4)
    ]
    assert svc.shutdown(drain=True, timeout=120)
    for d, t in jobs:
        assert t.done()
        np.testing.assert_array_equal(t.result(), np.sort(d))
    types = [e.type for e in journal.events()]
    assert "serve_drain" in types
    assert types[-1] == "serve_stop"
    assert types.count("job_done") == 4
    v, none = svc.submit(np.arange(3, dtype=np.int32))
    assert none is None and v.reason == "shutting_down"


def test_shutdown_no_drain_fails_queued_with_verdict(devices):
    journal = EventLog()
    svc = _svc(journal=journal, start=False)
    d = np.arange(1000, dtype=np.int32)
    _, t = svc.submit(d, tenant="acme")
    svc.shutdown(drain=False, timeout=120)
    with pytest.raises(ServiceClosed):
        t.result(timeout=10)
    types = [e.type for e in journal.events()]
    assert "job_failed" in types and types[-1] == "serve_stop"


def test_shutdown_racing_submit_strands_no_ticket(devices):
    """An admitted submission racing shutdown(drain=True) must still
    complete: the dispatcher's drain-exit consults the admission count
    (incremented before the queue push), so an admitted-but-not-yet-
    pushed ticket can never be stranded (code-review fix)."""
    import threading

    svc = _svc(journal=EventLog())
    results = []
    gate = threading.Barrier(5)

    def submitter(i):
        d = np.arange(2000 + i, dtype=np.int32)
        gate.wait()
        v, t = svc.submit(d, tenant="racer")
        if v.admitted:
            results.append((d, t))

    ths = [threading.Thread(target=submitter, args=(i,)) for i in range(4)]
    for th in ths:
        th.start()
    gate.wait()  # release the submitters and shut down immediately
    assert svc.shutdown(drain=True, timeout=120)
    for th in ths:
        th.join()
    for d, t in results:  # every ADMITTED job completed — none stranded
        np.testing.assert_array_equal(t.result(timeout=60), np.sort(d))


def test_cli_serve_unwritable_output_does_not_kill_server(tmp_path, monkeypatch):
    """A failing result write (bad -o path) logs and serves on — the old
    loop's 'a bad job must not kill the server' contract, kept through
    the async core (code-review fix)."""
    from dsort_tpu import cli

    inp = tmp_path / "in.txt"
    _write_keys(inp, np.arange(50, dtype=np.int64))
    lines = iter([str(inp), "exit"])
    monkeypatch.setattr("builtins.input", lambda *_: next(lines))
    # -o points at a DIRECTORY: every write raises OSError
    rc = cli.main(["serve", "-o", str(tmp_path), "--mode", "local"])
    assert rc == 0


def test_serve_events_registered():
    for etype in (
        "job_admitted", "job_rejected", "job_dequeued", "job_evicted",
        "job_readmitted", "slice_retired", "variant_prewarm",
        "serve_drain", "serve_stop",
    ):
        assert etype in EVENT_TYPES


# -- CLI: dsort serve on the async core --------------------------------------


def _write_keys(path, data):
    path.write_text("\n".join(str(int(x)) for x in data))


def test_cli_serve_sigint_graceful_shutdown(tmp_path, monkeypatch):
    """Ctrl-C (and SIGTERM via `_sigterm_to_interrupt`) drains in-flight
    jobs, flushes the journal with a serve_stop close event, and exits 0
    — today's satellite over the old mid-job teardown."""
    from dsort_tpu import cli

    rng = np.random.default_rng(9)
    d = rng.integers(0, 10**6, 2000, dtype=np.int64)
    inp = tmp_path / "in.txt"
    _write_keys(inp, d)
    journal = tmp_path / "serve.jsonl"
    lines = iter([str(inp)])

    def fake_input(prompt=""):
        try:
            return next(lines)
        except StopIteration:
            raise KeyboardInterrupt  # the SIGINT path

    monkeypatch.setattr("builtins.input", fake_input)
    rc = cli.main([
        "serve", "-o", str(tmp_path / "out.txt"), "--mode", "local",
        "--journal", str(journal), "--tenant", "acme",
    ])
    assert rc == 0
    records = EventLog.read_jsonl(str(journal))
    types = [r["type"] for r in records]
    assert types.count("job_done") == 1
    assert "serve_drain" in types and types[-1] == "serve_stop"
    out = np.loadtxt(tmp_path / "out.txt", dtype=np.int64)
    np.testing.assert_array_equal(out, np.sort(d))


def test_sigterm_handler_routes_to_interrupt():
    from dsort_tpu import cli

    with pytest.raises(KeyboardInterrupt):
        cli._sigterm_to_interrupt(15, None)


def test_cli_serve_async_two_tenants(tmp_path, monkeypatch):
    """README's two-tenant quick-start shape: async REPL (--max-in-flight)
    with per-line tenant labels; both tenants' jobs complete and the
    journal carries their admission records."""
    from dsort_tpu import cli

    rng = np.random.default_rng(10)
    files, datas = [], []
    for i in range(4):
        d = rng.integers(0, 10**6, 1500 + 100 * i, dtype=np.int64)
        p = tmp_path / f"in{i}.txt"
        _write_keys(p, d)
        files.append(p)
        datas.append(d)
    journal = tmp_path / "serve.jsonl"
    lines = iter(
        [f"tenant=acme {files[0]}", f"tenant=blue {files[1]}",
         f"tenant=acme {files[2]}", f"tenant=blue {files[3]}", "exit"]
    )
    monkeypatch.setattr("builtins.input", lambda *_: next(lines))
    rc = cli.main([
        "serve", "-o", str(tmp_path / "out.txt"), "--mode", "spmd",
        "--journal", str(journal), "--max-in-flight", "4",
    ])
    assert rc == 0
    records = EventLog.read_jsonl(str(journal))
    admitted = [r for r in records if r["type"] == "job_admitted"]
    assert {r["tenant"] for r in admitted} == {"acme", "blue"}
    assert len(admitted) == 4
    done = [r for r in records if r["type"] == "job_done"]
    assert len(done) >= 4


def test_parse_serve_line():
    from dsort_tpu.cli import _parse_serve_line

    assert _parse_serve_line("a.txt", "default") == ("default", "a.txt")
    assert _parse_serve_line("tenant=acme  b.txt ", "d") == ("acme", "b.txt")
    assert _parse_serve_line("  exit ", "d") == ("d", "exit")


def test_serve_config_validation_and_conf_keys():
    with pytest.raises(ConfigError):
        ServeConfig(max_queue_depth=0)
    with pytest.raises(ConfigError):
        ServeConfig(slice_devices=0)
    with pytest.raises(ConfigError):
        ServeConfig(tenant_weights={"a": -1})
    cfg = SortConfig.from_mapping({
        "SERVE_QUEUE_DEPTH": "9", "SERVE_TENANT_INFLIGHT": "3",
        "SERVE_SLICE_DEVICES": "2", "SERVE_WEIGHTS": "acme=2",
        "SERVE_PREWARM": "1",
    })
    assert cfg.serve.max_queue_depth == 9
    assert cfg.serve.max_tenant_inflight == 3
    assert cfg.serve.slice_devices == 2
    assert cfg.serve.tenant_weights == {"acme": 2.0}
    assert cfg.serve.prewarm


# -- ARCHITECTURE §8 schema enforcement --------------------------------------


def test_architecture_documents_serving_layer():
    """§8's contract is test-enforced like §7's bundle schema: every
    admission verdict and every serving-layer event type appears
    verbatim."""
    arch = open(
        os.path.join(os.path.dirname(os.path.dirname(__file__)),
                     "ARCHITECTURE.md"),
        encoding="utf-8",
    ).read()
    assert "## 8. Serving layer" in arch
    for reason in ADMISSION_REASONS:
        assert f"`{reason}`" in arch, f"admission reason {reason} undocumented"
    for etype in (
        "job_admitted", "job_rejected", "job_dequeued", "job_evicted",
        "job_readmitted", "serve_drain", "serve_stop",
    ):
        assert f"`{etype}`" in arch, f"serve event {etype} undocumented"
    for term in ("deficit", "capacity ladder", "prewarm", "shutdown"):
        assert term in arch
