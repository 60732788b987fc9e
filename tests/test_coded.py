"""Coded redundancy plane (`parallel.coded`, ARCHITECTURE §14).

The acceptance bar (ISSUE 15): one injected device loss at redundancy=2
recovers with ZERO re-sorted keys and ZERO re-dispatches — counter-
asserted across the SPMD scheduler, the wave pipeline and serve's
eviction path — with bit-identical output; losses over the budget
degrade cleanly to the re-run path (journaled `coded_budget_exceeded`,
still bit-identical).
"""

import json
import os

import numpy as np
import pytest

from dsort_tpu.analysis.spec import assert_conformant
from dsort_tpu.config import ConfigError, JobConfig, SortConfig
from dsort_tpu.data.ingest import gen_uniform, gen_zipf
from dsort_tpu.parallel.coded import (
    CodedBudgetExceeded,
    dead_positions,
)
from dsort_tpu.parallel.exchange import (
    replica_wire_bytes,
    resolve_redundancy,
    ring_wire_bytes,
)
from dsort_tpu.parallel.sample_sort import SampleSort
from dsort_tpu.scheduler.fault import FaultInjector, WorkerFailure
from dsort_tpu.utils.events import COUNTERS, EVENT_TYPES, EventLog
from dsort_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _metered():
    return Metrics(journal=EventLog())


def _sweep_hook(injector, p, stage="ring"):
    """The scheduler's aggregating ring-hook shape for bare SampleSort /
    wave drills: sweep every position, raise ONE failure carrying all."""

    def hook():
        failed = []
        for i in range(p):
            try:
                injector.check(i, stage)
            except WorkerFailure as f:
                failed.append(f.worker)
        if failed:
            e = WorkerFailure(failed[0], stage)
            e.workers = failed
            raise e

    return hook


# ---- knob resolution + config ---------------------------------------------


def test_resolve_redundancy_vocabulary():
    assert resolve_redundancy(None, 1, 8) == 1
    assert resolve_redundancy(None, 3, 8) == 3
    assert resolve_redundancy(2, 1, 8) == 2      # override > config
    assert resolve_redundancy(16, 1, 8) == 8     # clamped to the mesh
    assert resolve_redundancy(4, 1, 1) == 1      # no replica holder on P=1
    with pytest.raises(ValueError):
        resolve_redundancy(0, 1, 8)
    with pytest.raises(ValueError):
        resolve_redundancy(None, -1, 8)


def test_job_config_redundancy_validated():
    assert JobConfig(redundancy=2).redundancy == 2
    with pytest.raises(ConfigError):
        JobConfig(redundancy=0)


def test_conf_key_and_cli_flag_thread_redundancy(tmp_path):
    conf = tmp_path / "job.conf"
    conf.write_text("REDUNDANCY=2\nEXCHANGE=ring\n")
    cfg = SortConfig.from_conf_file(str(conf))
    assert cfg.job.redundancy == 2 and cfg.job.exchange == "ring"

    from dsort_tpu import cli

    class A:
        conf = None
        redundancy = 3

    assert cli._load_config(A()).job.redundancy == 3


def test_replica_wire_bytes_model():
    caps = (16, 8, 8, 24)
    p, bps = 4, 4
    # r=2: each device re-ships caps[k] at shift k+1; k=3 lands on itself.
    assert replica_wire_bytes(caps, bps, p, 2) == (16 + 8 + 8) * bps * p
    # r=1 is uncoded: no replica traffic.
    assert replica_wire_bytes(caps, bps, p, 1) == 0
    # r=p: every off-self slot of every shift ships.
    full = sum(
        sum(caps[k] for k in range(p) if (k + j) % p != 0)
        for j in range(1, p)
    ) * bps * p
    assert replica_wire_bytes(caps, bps, p, p) == full
    # uniform caps: the r=2 premium is exactly one extra ring's worth
    u = (32, 32, 32, 32)
    assert replica_wire_bytes(u, bps, p, 2) == ring_wire_bytes(u, bps, p)


def test_dead_positions_mapping():
    e = WorkerFailure(5, "ring")
    assert dead_positions(e) == [5]
    assert dead_positions(e, live=[0, 2, 5, 7]) == [2]
    e.workers = [5, 7]
    assert dead_positions(e, live=[0, 2, 5, 7]) == [2, 3]


# ---- exchange-level: healthy bit-identical + reconstruction ---------------


@pytest.mark.parametrize("dist", ["uniform_int32", "zipf_int64"])
@pytest.mark.parametrize("red", [2, 3])
def test_coded_healthy_bit_identical(mesh8, red, dist):
    ss = SampleSort(mesh8, JobConfig(exchange="ring", redundancy=red))
    data = (gen_uniform(100_003, seed=1) if dist == "uniform_int32"
            else gen_zipf(1 << 16, a=1.3, seed=5))
    m = _metered()
    np.testing.assert_array_equal(ss.sort(data, metrics=m), np.sort(data))
    assert m.counters["coded_replica_bytes"] > 0
    types = m.journal.types()
    assert "coded_replica_ship" in types and "skew_report" in types
    ship = next(
        e for e in m.journal.events() if e.type == "coded_replica_ship"
    )
    assert ship.fields["redundancy"] == red
    assert ship.fields["bytes"] == m.counters["coded_replica_bytes"]


def test_coded_zipf_per_call_override(mesh8):
    """Per-call redundancy= override on an uncoded JobConfig, skewed keys."""
    ss = SampleSort(mesh8, JobConfig(exchange="ring", key_dtype=np.int64))
    z = gen_zipf(1 << 16, a=1.3, seed=4)
    np.testing.assert_array_equal(
        ss.sort(z, redundancy=2), np.sort(z)
    )


def test_coded_float_keys_ride_mapped(mesh8):
    ss = SampleSort(mesh8, JobConfig(exchange="ring", redundancy=2))
    rng = np.random.default_rng(3)
    f = rng.standard_normal(20_000).astype(np.float32)
    f[:7] = [np.nan, -np.nan, 0.0, -0.0, np.inf, -np.inf, 1.5]
    np.testing.assert_array_equal(ss.sort(f), np.sort(f))


def test_coded_forces_ring_from_alltoall_and_fused(mesh8, caplog):
    data = gen_uniform(50_000, seed=2)
    for exch in ("alltoall", "fused"):
        ss = SampleSort(mesh8, JobConfig(exchange=exch, redundancy=2))
        m = _metered()
        np.testing.assert_array_equal(ss.sort(data, metrics=m), np.sort(data))
        # The coded run took the lax ring: replica plane journaled, and no
        # fused launch happened.
        assert m.counters["coded_replica_bytes"] > 0
        assert m.counters.get("fused_exchange_launches", 0) == 0


def test_coded_kv_runs_coded(mesh8):
    """v2 (§18) retired the kv warn-and-run-uncoded downgrade: payload
    rows ride the replica plane and the premium is priced."""
    from dsort_tpu.data.ingest import gen_terasort

    tk, tv = gen_terasort(4096, seed=3)
    ss = SampleSort(
        mesh8,
        JobConfig(
            exchange="ring", redundancy=2, key_dtype=np.uint64,
            payload_bytes=tv.shape[1],
        ),
    )
    m = _metered()
    out_k, out_v = ss.sort_kv(tk, tv, metrics=m)
    order = np.argsort(tk, kind="stable")
    np.testing.assert_array_equal(out_k, tk[order])
    np.testing.assert_array_equal(out_v, tv[order])
    assert m.counters["coded_replica_bytes"] > 0  # kv premium is priced
    assert "coded_replica_ship" in m.journal.types()


def test_fault_snapshot_reconstructs_every_loss_shape(mesh8):
    """The `CodedExchangeState` contract: single loss, non-adjacent double
    loss at r=2, budget exceeded on an adjacent pair at r=2, adjacent
    pair covered at r=3."""
    data = gen_uniform(80_000, seed=5)
    ss = SampleSort(mesh8, JobConfig(exchange="ring", redundancy=2))
    ss.fault_hook = lambda: (_ for _ in ()).throw(WorkerFailure(3, "ring"))
    with pytest.raises(WorkerFailure) as ei:
        ss.sort(data)
    st = ei.value.coded_state
    assert st.num_workers == 8 and st.redundancy == 2
    expect = np.sort(data)
    out, info = st.assemble([3])
    np.testing.assert_array_equal(out, expect)
    assert info["holders"] == {3: 4}
    assert info["recovered_keys"] == len(st.ranges[3])
    assert info["replica_bytes"] > 0
    # non-adjacent double loss is covered at r=2
    out2, info2 = st.assemble([2, 5])
    np.testing.assert_array_equal(out2, expect)
    assert info2["holders"] == {2: 3, 5: 6}
    # an adjacent pair exceeds the r=2 budget
    with pytest.raises(CodedBudgetExceeded):
        st.assemble([3, 4])
    # ... and r=3 covers it (both ranges rebuilt from the j=2 holder)
    ss3 = SampleSort(mesh8, JobConfig(exchange="ring", redundancy=3))
    e3 = WorkerFailure(3, "ring")
    e3.workers = [3, 4]
    ss3.fault_hook = lambda: (_ for _ in ()).throw(e3)
    with pytest.raises(WorkerFailure) as ei3:
        ss3.sort(data)
    out3, info3 = ei3.value.coded_state.assemble([3, 4])
    np.testing.assert_array_equal(out3, expect)
    assert info3["holders"] == {3: 5, 4: 5}


# ---- FaultInjector multi-trip sequences -----------------------------------


def test_fail_sequence_trips_in_order():
    inj = FaultInjector()
    inj.fail_sequence([(3, "ring"), (4, "ring"), (2, "spmd")])
    # out-of-order checks don't trip until the head matches
    inj.check(4, "ring")
    inj.check(2, "spmd")
    with pytest.raises(WorkerFailure):
        inj.check(3, "ring")
    # the next entry armed immediately: one sweep can trip both
    with pytest.raises(WorkerFailure):
        inj.check(4, "ring")
    # a later attempt's sweep continues the remainder
    inj.check(4, "ring")
    with pytest.raises(WorkerFailure):
        inj.check(2, "spmd")
    inj.check(2, "spmd")  # consumed: the sequence is exhausted
    assert inj.trips == 3


# ---- the SPMD scheduler drill (acceptance) --------------------------------


@pytest.mark.parametrize("dtype", [np.int64, np.int32])
def test_scheduler_coded_recovery_zero_rerun(tmp_path, dtype):
    """THE acceptance drill: one injected mid-ring loss at redundancy=2
    recovers with zero re-sorted keys and zero re-dispatches —
    counter-asserted (`coded_recoveries`=1, `device_handle_reruns`=0,
    exactly ONE attempt_start), output bit-identical, one
    `coded_reconstruct` flight bundle."""
    from dsort_tpu.obs.flight import FlightRecorder
    from dsort_tpu.scheduler import SpmdScheduler

    inj = FaultInjector()
    sched = SpmdScheduler(
        job=JobConfig(
            settle_delay_s=0.01, exchange="ring", redundancy=2,
            flight_recorder_dir=str(tmp_path),
        ),
        injector=inj,
    )
    z = gen_zipf(1 << 17, a=1.3, dtype=dtype, seed=5)
    np.testing.assert_array_equal(sched.sort(z), np.sort(z))  # warm
    inj.fail_once(3, "ring")
    m = _metered()
    out = sched.sort(z, metrics=m)
    np.testing.assert_array_equal(out, np.sort(z))
    assert m.counters["coded_recoveries"] == 1
    assert m.counters["coded_recovered_keys"] > 0
    assert m.counters.get("device_handle_reruns", 0) == 0
    assert m.counters.get("wave_runs_resorted", 0) == 0
    assert m.counters.get("shuffle_resort_keys", 0) == 0
    assert m.counters["mesh_reforms"] == 1
    types = m.journal.types()
    assert types.count("attempt_start") == 1  # zero re-dispatch
    # full fault contract order: death -> re-form -> coded reconstruct.
    assert (
        types.index("worker_dead")
        < types.index("mesh_reform")
        < types.index("coded_recover")
    )
    assert types[-1] == "job_done"
    rec = next(e for e in m.journal.events() if e.type == "coded_recover")
    assert rec.fields["dead"] == [3] and rec.fields["holders"] == {3: 4}
    assert rec.fields["recovered_keys"] == m.counters["coded_recovered_keys"]
    assert rec.fields["wall_s"] >= 0
    bundles = [
        b for b in FlightRecorder.read_bundles(str(tmp_path))
        if b["recovery_path"] == "coded_reconstruct"
    ]
    assert len(bundles) == 1
    assert bundles[0]["detail"]["dead"] == [3]
    # the scheduler still re-formed: the dead device left the mesh
    assert sorted(sched.table.live_workers()) == [0, 1, 2, 4, 5, 6, 7]


def test_scheduler_over_budget_degrades_to_rerun():
    """Two losses at redundancy=2 (a range's owner AND its replica
    holder, via the multi-trip injector) exceed the budget: journaled
    `coded_budget_exceeded`, clean degrade to the re-run path,
    bit-identical output."""
    from dsort_tpu.scheduler import SpmdScheduler

    inj = FaultInjector()
    sched = SpmdScheduler(
        job=JobConfig(settle_delay_s=0.01, exchange="ring", redundancy=2),
        injector=inj,
    )
    z = gen_zipf(1 << 17, a=1.3, seed=5)
    np.testing.assert_array_equal(sched.sort(z), np.sort(z))  # warm
    inj.fail_sequence([(3, "ring"), (4, "ring")])
    m = _metered()
    out = sched.sort(z, metrics=m)
    np.testing.assert_array_equal(out, np.sort(z))
    types = m.journal.types()
    assert "coded_budget_exceeded" in types
    assert m.counters.get("coded_recoveries", 0) == 0
    assert types.count("attempt_start") == 2  # the re-run happened
    assert m.counters["mesh_reforms"] == 1
    b = next(
        e for e in m.journal.events() if e.type == "coded_budget_exceeded"
    )
    assert b.fields["dead"] == [3, 4] and b.fields["redundancy"] == 2
    # both devices actually left the mesh in ONE re-form
    assert sorted(sched.table.live_workers()) == [0, 1, 2, 5, 6, 7]


@pytest.mark.parametrize("dtype", [np.int64, np.int32])
def test_scheduler_uncoded_rerun_contract_unchanged(dtype):
    """redundancy=1 keeps today's re-run path byte-for-byte: the mid-ring
    drill's contract (PR 4) still holds with the new hook plumbing."""
    from dsort_tpu.scheduler import SpmdScheduler

    inj = FaultInjector()
    sched = SpmdScheduler(
        job=JobConfig(settle_delay_s=0.01, exchange="ring"), injector=inj
    )
    z = gen_zipf(1 << 16, a=1.3, dtype=dtype, seed=5)
    np.testing.assert_array_equal(sched.sort(z), np.sort(z))
    inj.fail_once(3, "ring")
    m = _metered()
    np.testing.assert_array_equal(sched.sort(z, metrics=m), np.sort(z))
    types = m.journal.types()
    assert types.count("attempt_start") == 2
    assert "coded_recover" not in types and "coded_replica_ship" not in types


def test_scheduler_coded_loss_in_resume_subset_keeps_restored_ranges(
    tmp_path,
):
    """A coded loss inside a checkpoint-resume's SUBSET re-sort must not
    complete from the subset-only snapshot (it covers only the lost
    interval — assembling it as the job output would drop every restored
    range): the partial snapshot degrades to the re-run loop, whose next
    attempt resumes correctly.  Output bit-identical, restored ranges
    intact."""
    from dsort_tpu.scheduler import SpmdScheduler

    inj = FaultInjector()
    job = JobConfig(
        settle_delay_s=0.01, checkpoint_dir=str(tmp_path),
        heartbeat_timeout_s=5.0, exchange="ring", redundancy=2,
    )
    sched = SpmdScheduler(job=job, injector=inj)
    data = gen_uniform(40_000, seed=60)
    # Loss 1 (uncoded stage): range 7 dies while read back — ranges 0..6
    # persist, the retry resumes by re-sorting only the lost interval.
    # Loss 2 (coded stage): the SUBSET re-sort's ring trips — its coded
    # snapshot covers only the subset and must NOT short-circuit the job.
    # Ordered via fail_sequence so the ring trip cannot fire before the
    # assemble-stage loss has produced a resume.
    inj.fail_sequence([(7, "assemble"), (6, "ring")])
    m = _metered()
    out = sched.sort(data, metrics=m, job_id="codedresume")
    np.testing.assert_array_equal(out, np.sort(data))
    assert m.counters["shuffle_ranges_restored"] >= 7
    assert 0 < m.counters["shuffle_resort_keys"] < len(data)
    # the partial snapshot was refused: no coded completion happened
    assert m.counters.get("coded_recoveries", 0) == 0


# ---- the wave pipeline drill ----------------------------------------------


def test_wave_coded_repair_no_host_resort(tmp_path):
    """A coded wave repairs a mid-ring loss from replica slots: zero
    `wave_runs_resorted`, zero `wave_resort_keys`, no `wave_resume`,
    bit-identical output, and the pipeline continues on the mesh."""
    from dsort_tpu.models.wave_sort import ExternalWaveSort

    data = gen_uniform(1 << 18, seed=7)
    ws = ExternalWaveSort(
        wave_elems=1 << 16, spill_dir=str(tmp_path), job_id="codedwave",
        job=JobConfig(exchange="ring"), redundancy=2, resume=False,
    )
    inj = FaultInjector()
    inj.fail_once(3, "ring")
    sweep = _sweep_hook(inj, ws.num_workers)
    calls = {"n": 0}

    def hook():
        calls["n"] += 1
        if calls["n"] == 2:  # the second wave's exchange
            sweep()

    ws.fault_hook = hook
    m = _metered()
    out = ws.sort(data, metrics=m)
    np.testing.assert_array_equal(out, np.sort(data))
    assert m.counters["coded_recoveries"] == 1
    assert m.counters.get("wave_runs_resorted", 0) == 0
    assert m.counters.get("wave_resort_keys", 0) == 0
    assert m.counters["waves_sorted"] == 4
    types = m.journal.types()
    assert "coded_recover" in types and "wave_resume" not in types
    rec = next(e for e in m.journal.events() if e.type == "coded_recover")
    assert rec.fields["wave"] == 1 and rec.fields["dead"] == [3]


def test_wave_coded_over_budget_degrades_to_host_resort(tmp_path):
    from dsort_tpu.models.wave_sort import ExternalWaveSort

    data = gen_uniform(1 << 17, seed=9)
    ws = ExternalWaveSort(
        wave_elems=1 << 16, spill_dir=str(tmp_path), job_id="codedwave2",
        job=JobConfig(exchange="ring"), redundancy=2, resume=False,
    )
    inj = FaultInjector()
    inj.fail_sequence([(3, "ring"), (4, "ring")])
    ws.fault_hook = _sweep_hook(inj, ws.num_workers)
    m = _metered()
    out = ws.sort(data, metrics=m)
    np.testing.assert_array_equal(out, np.sort(data))
    types = m.journal.types()
    assert "coded_budget_exceeded" in types and "wave_resume" in types
    assert m.counters.get("coded_recoveries", 0) == 0
    assert m.counters["wave_runs_resorted"] == ws.num_workers


def test_wave_coded_composes_with_restart_resume(tmp_path):
    """Coded runs are ordinary durable (wave, run) entries: a second run
    of the same job restores them for free (`runs_resumed`)."""
    from dsort_tpu.models.wave_sort import ExternalWaveSort

    data = gen_uniform(1 << 17, seed=11)
    kw = dict(
        wave_elems=1 << 16, spill_dir=str(tmp_path), job_id="codedresume",
        job=JobConfig(exchange="ring"), redundancy=2,
    )
    ws = ExternalWaveSort(**kw)
    inj = FaultInjector()
    inj.fail_once(3, "ring")
    ws.fault_hook = _sweep_hook(inj, ws.num_workers)
    m = _metered()
    np.testing.assert_array_equal(ws.sort(data, metrics=m), np.sort(data))
    assert m.counters["coded_recoveries"] == 1
    ws2 = ExternalWaveSort(**kw)
    m2 = _metered()
    np.testing.assert_array_equal(ws2.sort(data, metrics=m2), np.sort(data))
    assert m2.counters["runs_resumed"] == 2 * ws2.num_workers
    assert m2.counters.get("waves_sorted", 0) == 0


def test_wave_fused_overrides_to_ring_when_coded(tmp_path):
    from dsort_tpu.models.wave_sort import ExternalWaveSort

    ws = ExternalWaveSort(
        wave_elems=1 << 15, spill_dir=str(tmp_path), job_id="codedfused",
        job=JobConfig(exchange="fused"), redundancy=2, resume=False,
    )
    assert ws.exchange == "ring" and ws.redundancy == 2
    data = gen_uniform(1 << 16, seed=13)
    np.testing.assert_array_equal(ws.sort(data), np.sort(data))


# ---- serve: eviction completes from replicas ------------------------------


def _coded_runner_service(tmp_path, journal):
    """A runner-mode service whose sorter is a coded SampleSort with an
    injected mid-ring loss on its FIRST run — the eviction drill rig."""
    from dsort_tpu.parallel.mesh import local_device_mesh
    from dsort_tpu.serve.service import SortService

    mesh = local_device_mesh()
    job = JobConfig(
        exchange="ring", redundancy=2, settle_delay_s=0.01,
        flight_recorder_dir=str(tmp_path),
    )
    ss = SampleSort(mesh, job)
    inj = FaultInjector()
    ss.fault_hook = _sweep_hook(inj, mesh.shape["w"])
    calls = []

    def runner(data, metrics, job_id=None):
        calls.append(1)
        return ss.sort(data, metrics)

    svc = SortService(job=job, journal=journal, runner=runner, start=False)
    return svc, ss, inj, calls


def test_serve_evicted_coded_job_completes_from_replicas(tmp_path):
    """`job_evicted` on a coded job re-admits and completes from replicas
    instead of re-running: the runner executes ONCE, the re-dispatch is
    a local merge (`coded_recover`), output bit-identical, one eviction
    bundle + one `coded_reconstruct` bundle."""
    from dsort_tpu.obs.flight import FlightRecorder

    journal = EventLog()
    svc, ss, inj, calls = _coded_runner_service(tmp_path, journal)
    data = gen_uniform(60_000, seed=1)
    ss.sort(data)  # warm OUTSIDE the service (not a runner call)
    inj.fail_once(3, "ring")
    v, t = svc.submit(data, tenant="acme")
    assert v.admitted
    svc.start()
    np.testing.assert_array_equal(t.result(timeout=300), np.sort(data))
    svc.shutdown(drain=True)
    assert len(calls) == 1  # the sort ran once; completion came from replicas
    types = journal.types()
    # Sequencing rides the declared contracts (ISSUE 17): the job's
    # evict->readmit->terminal cycle is the `job_lifecycle` grammar.
    report = assert_conformant(journal)
    assert report["contracts"]["job_lifecycle"]["checked"] == 1
    # Behavioral facts the grammar cannot pin: the completion came from
    # replicas — a reconstruct between readmission and the terminal, and
    # a second dequeue for the local merge.
    assert types.index("job_readmitted") < types.index("coded_recover")
    assert types.index("coded_recover") < types.index("job_done")
    assert types.count("job_dequeued") == 2
    paths = [
        b["recovery_path"]
        for b in FlightRecorder.read_bundles(str(tmp_path))
    ]
    assert paths.count("job_evicted") == 1
    assert paths.count("coded_reconstruct") == 1


def test_serve_over_budget_coded_job_reruns(tmp_path):
    """An over-budget snapshot on the ticket degrades to the ordinary
    re-dispatch: the runner executes twice, `coded_budget_exceeded`
    journaled, output still bit-identical."""
    journal = EventLog()
    svc, ss, inj, calls = _coded_runner_service(tmp_path, journal)
    data = gen_uniform(60_000, seed=2)
    ss.sort(data)  # warm
    inj.fail_sequence([(3, "ring"), (4, "ring")])
    _, t = svc.submit(data, tenant="acme")
    svc.start()
    np.testing.assert_array_equal(t.result(timeout=300), np.sort(data))
    svc.shutdown(drain=True)
    assert len(calls) == 2  # evicted, then genuinely re-run
    types = journal.types()
    assert "coded_budget_exceeded" in types
    assert "coded_recover" not in types


# ---- analyzer: the recovery verdict ---------------------------------------


def test_analyze_recovery_verdict_coded_vs_rerun():
    """`dsort report --analyze`'s `recovery` key splits re-run vs
    coded-local recovery, asserted against journal ground truth on an
    injected coded drill AND the existing re-run drill."""
    from dsort_tpu.obs.analyze import VERDICT_KEYS, analyze_records
    from dsort_tpu.scheduler import SpmdScheduler

    assert "recovery" in VERDICT_KEYS
    z = gen_zipf(1 << 16, a=1.3, seed=5)

    def drill(red, seq):
        inj = FaultInjector()
        sched = SpmdScheduler(
            job=JobConfig(
                settle_delay_s=0.01, exchange="ring", redundancy=red
            ),
            injector=inj,
        )
        sched.sort(z)
        inj.fail_sequence(seq)
        m = _metered()
        np.testing.assert_array_equal(sched.sort(z, metrics=m), np.sort(z))
        return m, [e.to_dict() for e in m.journal.events()]

    # coded drill: path = coded_reconstruct, figures == journal ground truth
    m, recs = drill(2, [(3, "ring")])
    v = analyze_records(recs)["recovery"]
    rec_ev = next(r for r in recs if r["type"] == "coded_recover")
    assert v["path"] == "coded_reconstruct"
    assert v["coded"]["recoveries"] == 1
    assert v["coded"]["recovered_keys"] == rec_ev["recovered_keys"]
    assert v["coded"]["replica_bytes"] == rec_ev["replica_bytes"]
    assert v["coded"]["wall_s"] == pytest.approx(rec_ev["wall_s"])
    assert v["rerun"]["mesh_reforms"] == 1
    assert v["rerun"]["resorted_keys"] == 0
    # re-run drill: path = rerun, no coded side
    m2, recs2 = drill(1, [(3, "ring")])
    v2 = analyze_records(recs2)["recovery"]
    assert v2["path"] == "rerun"
    assert v2["coded"]["recoveries"] == 0
    assert v2["rerun"]["mesh_reforms"] == 1
    # healthy journal: no recovery section at all
    from dsort_tpu.parallel.mesh import local_device_mesh

    m3 = _metered()
    SampleSort(local_device_mesh(), JobConfig(exchange="ring")).sort(
        z, metrics=m3
    )
    v3 = analyze_records([e.to_dict() for e in m3.journal.events()])
    assert v3["recovery"] is None
    # the human table renders the split
    from dsort_tpu.obs.analyze import format_analysis

    txt = format_analysis(analyze_records(recs))
    assert "recovery" in txt and "coded_reconstruct" in txt


# ---- CLI -----------------------------------------------------------------


def test_cli_run_small_coded_job_reaches_exchange(tmp_path):
    """`dsort run --redundancy 2` must reach the exchange plane even for
    a small input: the fused single-device shortcut has no replica plane,
    so an explicit availability posture skips it (the checkpointing
    rule)."""
    from dsort_tpu import cli

    rng = np.random.default_rng(17)
    inp = tmp_path / "in.txt"
    np.savetxt(
        inp, rng.integers(0, 1 << 30, 20_000, dtype=np.int32), fmt="%d"
    )
    out = tmp_path / "out.txt"
    jpath = tmp_path / "run.jsonl"
    rc = cli.main([
        "run", str(inp), "--exchange", "ring", "--redundancy", "2",
        "--journal", str(jpath), "-o", str(out),
    ])
    assert rc == 0
    got = np.loadtxt(out, dtype=np.int64)
    want = np.sort(np.loadtxt(inp, dtype=np.int64))
    np.testing.assert_array_equal(got, want)
    types = [json.loads(ln)["type"] for ln in open(jpath)]
    assert "coded_replica_ship" in types  # not the fused shortcut


# ---- registries + docs schema ---------------------------------------------


def test_coded_registries():
    for etype in (
        "coded_replica_ship", "coded_recover", "coded_budget_exceeded"
    ):
        assert etype in EVENT_TYPES
    for counter in (
        "coded_recoveries", "coded_replica_bytes", "coded_recovered_keys"
    ):
        assert counter in COUNTERS
    from dsort_tpu.obs.flight import RECOVERY_EVENTS, recovery_path_name

    assert "coded_recover" in RECOVERY_EVENTS
    assert recovery_path_name("coded_recover", {}) == "coded_reconstruct"


def test_architecture_documents_coded_plane():
    """§14's contract is test-enforced like §7–§13: replica placement,
    the reconstruction contract, the budget/fallback state machine, and
    every event/counter name appear verbatim."""
    arch = open(
        os.path.join(REPO, "ARCHITECTURE.md"), encoding="utf-8"
    ).read()
    assert "## 14. Coded redundancy plane" in arch
    for etype in (
        "coded_replica_ship", "coded_recover", "coded_budget_exceeded"
    ):
        assert f"`{etype}`" in arch, f"event {etype} undocumented"
        assert etype in EVENT_TYPES
    for counter in (
        "coded_recoveries", "coded_replica_bytes", "coded_recovered_keys"
    ):
        assert f"`{counter}`" in arch, f"counter {counter} undocumented"
        assert counter in COUNTERS
    for term in (
        "--redundancy", "REDUNDANCY", "resolve_redundancy",
        "coded_reconstruct", "CodedBudgetExceeded", "fail_sequence",
        "replica_overhead_frac", "throughput_under_failure_ratio",
        "`recovery`", "arXiv:1702.04850",
    ):
        assert term in arch, f"{term} missing from §14"
