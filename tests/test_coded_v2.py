"""Coded exchange v2 (`parallel.coded` parity plane, ARCHITECTURE §18).

The acceptance bar (ISSUE 19): parity slots cut the wire premium below
0.75x of r=2 replication at the same single-loss survivability; kv
payloads ride the replica/parity plane (no silent uncoded downgrade);
a live-but-slow owner's range is served straggler-first under an
exactly-once journaled claim; and every mode x fault-shape cell stays
bit-identical, including the GF(256) byte-plane round trip on floats,
NaNs and sentinels.
"""

import os
import time

import numpy as np
import pytest

from dsort_tpu.analysis.spec import assert_conformant
from dsort_tpu.config import ConfigError, JobConfig, SortConfig
from dsort_tpu.data.ingest import gen_terasort, gen_uniform, gen_zipf
from dsort_tpu.parallel.coded import (
    CodedBudgetExceeded,
    _byte_row,
    _gf_scale,
    _parity_solve,
)
from dsort_tpu.parallel.exchange import (
    parity_slots,
    parity_wire_bytes,
    replica_wire_bytes,
    resolve_redundancy_mode,
)
from dsort_tpu.parallel.sample_sort import SampleSort
from dsort_tpu.scheduler.fault import FaultInjector, WorkerFailure
from dsort_tpu.utils.events import COUNTERS, EVENT_TYPES, EventLog
from dsort_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _metered():
    return Metrics(journal=EventLog())


def _sweep_hook(injector, p, stage="ring"):
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


def test_resolve_redundancy_mode_vocabulary():
    assert resolve_redundancy_mode(None, "replicate") == "replicate"
    assert resolve_redundancy_mode(None, "parity") == "parity"
    assert resolve_redundancy_mode("parity", "replicate") == "parity"
    with pytest.raises(ValueError):
        resolve_redundancy_mode("raid", "replicate")


def test_parity_slots_budget():
    assert parity_slots(1) == 0      # uncoded: no parity plane
    assert parity_slots(2) == 1      # XOR covers any single loss
    assert parity_slots(3) == 2      # P+Q covers any double
    assert parity_slots(8) == 2      # two erasures is the RAID-6 ceiling


def test_job_config_redundancy_mode_validated(tmp_path):
    assert JobConfig().redundancy_mode == "replicate"
    assert JobConfig(redundancy_mode="parity").redundancy_mode == "parity"
    with pytest.raises(ConfigError):
        JobConfig(redundancy_mode="raid")
    conf = tmp_path / "job.conf"
    conf.write_text("REDUNDANCY=2\nREDUNDANCY_MODE=parity\nEXCHANGE=ring\n")
    cfg = SortConfig.from_conf_file(str(conf))
    assert cfg.job.redundancy_mode == "parity"
    assert cfg.job.is_explicit("redundancy_mode")

    from dsort_tpu import cli

    class A:
        conf = None
        redundancy = 2
        redundancy_mode = "parity"

    assert cli._load_config(A()).job.redundancy_mode == "parity"


def test_parity_wire_bytes_model():
    caps = (16, 8, 8, 24)
    p, bps = 4, 4
    # One XOR slot sized at the max-cap bucket per device.
    assert parity_wire_bytes(caps, bps, p, 2) == 24 * bps * p
    # P+Q doubles it; uncoded ships nothing.
    assert parity_wire_bytes(caps, bps, p, 3) == 2 * 24 * bps * p
    assert parity_wire_bytes(caps, bps, p, 1) == 0
    # THE premium claim, on the model: parity r=2 < 0.75x replicate r=2
    # whenever the mesh has more than a few buckets per device.
    caps8 = (16, 8, 8, 24, 16, 8, 8, 12)
    ratio = parity_wire_bytes(caps8, 8, 8, 2) / replica_wire_bytes(
        caps8, 8, 8, 2
    )
    assert ratio < 0.75


# ---- GF(256) math: the byte-plane round trip ------------------------------


def test_gf256_parity_solve_round_trip_bit_identical():
    """Kill any 1 or 2 rows of a byte group; P (XOR) alone recovers one,
    P+Q recovers two — bit-identical, including NaN payload bytes."""
    rng = np.random.default_rng(7)
    p, cap = 8, 64
    rows = {}
    for k in range(p):
        f = rng.standard_normal(cap // 2).astype(np.float32)
        f[:4] = [np.nan, -0.0, np.inf, -np.inf]
        rows[k] = np.ascontiguousarray(f).view(np.uint8).reshape(-1)
    xor = np.zeros(rows[0].shape, np.uint8)
    q = np.zeros(rows[0].shape, np.uint8)
    from dsort_tpu.parallel.coded import _GF_EXP

    for k, r in rows.items():
        xor ^= r
        q ^= _gf_scale(r, int(_GF_EXP[k % 255]))
    # single erasure: XOR peel
    known = {k: r for k, r in rows.items() if k != 3}
    out = _parity_solve(known, [xor], [3])
    np.testing.assert_array_equal(out[3], rows[3])
    # double erasure: the P+Q closed form, every pair
    for i, j in ((0, 1), (2, 5), (6, 7)):
        known = {k: r for k, r in rows.items() if k not in (i, j)}
        out = _parity_solve(known, [xor, q], [i, j])
        np.testing.assert_array_equal(out[i], rows[i])
        np.testing.assert_array_equal(out[j], rows[j])


def test_byte_row_pads_with_sentinel():
    run = np.array([3, 1 << 40], np.int64)
    row = _byte_row(run, 4, np.array(np.iinfo(np.int64).max, np.int64))
    back = row.view(np.int64)
    assert list(back[:2]) == [3, 1 << 40]
    assert (back[2:] == np.iinfo(np.int64).max).all()


# ---- exchange-level: healthy parity bit-identical + premium ---------------


@pytest.mark.parametrize("red", [2, 3])
def test_parity_healthy_bit_identical(mesh8, red):
    ss = SampleSort(
        mesh8,
        JobConfig(exchange="ring", redundancy=red, redundancy_mode="parity"),
    )
    data = gen_uniform(100_003, seed=1)
    m = _metered()
    np.testing.assert_array_equal(ss.sort(data, metrics=m), np.sort(data))
    assert m.counters["coded_replica_bytes"] > 0
    ship = next(
        e for e in m.journal.events() if e.type == "coded_replica_ship"
    )
    assert ship.fields["mode"] == "parity"
    assert ship.fields["slots"] == parity_slots(red) * 8


@pytest.mark.parametrize("dtype", [np.int64, np.int32])
def test_parity_premium_below_three_quarters_of_replicate(mesh8, dtype):
    """THE wire-premium acceptance gate at equal single-loss
    survivability: parity r=2 ships < 0.75x replicate r=2's
    `coded_replica_bytes` on the same measured plan."""
    data = gen_zipf(1 << 17, a=1.2, dtype=dtype, seed=3)
    bytes_by_mode = {}
    for mode in ("replicate", "parity"):
        ss = SampleSort(
            mesh8,
            JobConfig(
                exchange="ring", redundancy=2, redundancy_mode=mode,
                key_dtype=np.int64,
            ),
        )
        m = _metered()
        np.testing.assert_array_equal(
            ss.sort(data, metrics=m), np.sort(data)
        )
        bytes_by_mode[mode] = m.counters["coded_replica_bytes"]
    assert bytes_by_mode["parity"] > 0
    assert bytes_by_mode["parity"] < 0.75 * bytes_by_mode["replicate"]


def test_parity_float_keys_ride_mapped(mesh8):
    ss = SampleSort(
        mesh8,
        JobConfig(exchange="ring", redundancy=2, redundancy_mode="parity"),
    )
    rng = np.random.default_rng(3)
    f = rng.standard_normal(20_000).astype(np.float32)
    f[:7] = [np.nan, -np.nan, 0.0, -0.0, np.inf, -np.inf, 1.5]
    np.testing.assert_array_equal(ss.sort(f), np.sort(f))


# ---- fault matrix: snapshot-level reconstruction --------------------------


def test_parity_snapshot_fault_matrix(mesh8):
    """r=2 (XOR): any single loss solves, any double exceeds.  r=3
    (P+Q): non-adjacent doubles solve; an adjacent pair kills a parity
    holder and degrades cleanly."""
    data = gen_uniform(80_000, seed=5)
    expect = np.sort(data)
    ss = SampleSort(
        mesh8,
        JobConfig(exchange="ring", redundancy=2, redundancy_mode="parity"),
    )
    ss.fault_hook = lambda: (_ for _ in ()).throw(WorkerFailure(3, "ring"))
    with pytest.raises(WorkerFailure) as ei:
        ss.sort(data)
    st = ei.value.coded_state
    assert st.mode == "parity" and st.num_workers == 8
    for d in range(8):
        out, info = st.assemble([d])
        np.testing.assert_array_equal(out, expect)
        assert info["recovered_keys"] == len(st.ranges[d])
        assert info["replica_bytes"] > 0
    with pytest.raises(CodedBudgetExceeded):
        st.assemble([2, 5])  # one XOR slot cannot solve two erasures

    ss3 = SampleSort(
        mesh8,
        JobConfig(exchange="ring", redundancy=3, redundancy_mode="parity"),
    )
    e3 = WorkerFailure(2, "ring")
    e3.workers = [2, 5]
    ss3.fault_hook = lambda: (_ for _ in ()).throw(e3)
    with pytest.raises(WorkerFailure) as ei3:
        ss3.sort(data)
    st3 = ei3.value.coded_state
    out3, info3 = st3.assemble([2, 5])  # P+Q, non-adjacent pair
    np.testing.assert_array_equal(out3, expect)
    assert info3["holders"] == {2: [3, 4], 5: [6, 7]}
    with pytest.raises(CodedBudgetExceeded):
        st3.assemble([3, 4])  # 4 holds 3's P slot: holder dead
    with pytest.raises(CodedBudgetExceeded):
        st3.assemble([1, 2, 5])  # three erasures beat the RAID-6 ceiling


def test_parity_kv_snapshot_reconstructs_payload(mesh8):
    tk, tv = gen_terasort(6144, seed=9)
    ss = SampleSort(
        mesh8,
        JobConfig(
            exchange="ring", redundancy=2, redundancy_mode="parity",
            key_dtype=np.uint64, payload_bytes=tv.shape[1],
        ),
    )
    ss.fault_hook = lambda: (_ for _ in ()).throw(WorkerFailure(4, "ring"))
    with pytest.raises(WorkerFailure) as ei:
        ss.sort_kv(tk, tv)
    st = ei.value.coded_state
    assert st.kv and st.mode == "parity"
    (out_k, out_v), info = st.assemble([4])
    order = np.argsort(tk, kind="stable")
    np.testing.assert_array_equal(out_k, tk[order])
    np.testing.assert_array_equal(out_v, tv[order])
    assert info["replica_bytes"] > 0


# ---- scheduler drills: both modes through the full fault contract ---------


@pytest.mark.parametrize("dtype", [np.int64, np.int32])
def test_scheduler_parity_recovery_zero_rerun(tmp_path, dtype):
    """The §14 acceptance drill in parity mode: one loss at r=2 recovers
    with zero re-dispatch, journals `parity_recover`, dumps a
    `parity_reconstruct` bundle."""
    from dsort_tpu.obs.flight import FlightRecorder
    from dsort_tpu.scheduler import SpmdScheduler

    inj = FaultInjector()
    sched = SpmdScheduler(
        job=JobConfig(
            settle_delay_s=0.01, exchange="ring", redundancy=2,
            redundancy_mode="parity", flight_recorder_dir=str(tmp_path),
        ),
        injector=inj,
    )
    z = gen_zipf(1 << 16, a=1.3, dtype=dtype, seed=5)
    np.testing.assert_array_equal(sched.sort(z), np.sort(z))  # warm
    inj.fail_once(3, "ring")
    m = _metered()
    np.testing.assert_array_equal(sched.sort(z, metrics=m), np.sort(z))
    assert m.counters["coded_recoveries"] == 1
    assert m.counters.get("device_handle_reruns", 0) == 0
    assert m.counters.get("shuffle_resort_keys", 0) == 0
    types = m.journal.types()
    assert types.count("attempt_start") == 1
    assert "parity_recover" in types and "coded_recover" not in types
    assert (
        types.index("worker_dead")
        < types.index("mesh_reform")
        < types.index("parity_recover")
    )
    rec = next(e for e in m.journal.events() if e.type == "parity_recover")
    assert rec.fields["dead"] == [3] and rec.fields["mode"] == "parity"
    assert rec.fields["recovered_keys"] > 0
    bundles = [
        b["recovery_path"]
        for b in FlightRecorder.read_bundles(str(tmp_path))
    ]
    assert bundles.count("parity_reconstruct") == 1
    assert_conformant(m.journal)  # parity_recovery grammar holds


def test_scheduler_parity_over_budget_degrades():
    from dsort_tpu.scheduler import SpmdScheduler

    inj = FaultInjector()
    sched = SpmdScheduler(
        job=JobConfig(
            settle_delay_s=0.01, exchange="ring", redundancy=2,
            redundancy_mode="parity",
        ),
        injector=inj,
    )
    z = gen_zipf(1 << 16, a=1.3, seed=5)
    np.testing.assert_array_equal(sched.sort(z), np.sort(z))
    inj.fail_sequence([(2, "ring"), (5, "ring")])  # 2 erasures > 1 XOR slot
    m = _metered()
    np.testing.assert_array_equal(sched.sort(z, metrics=m), np.sort(z))
    types = m.journal.types()
    assert "coded_budget_exceeded" in types
    assert "parity_recover" not in types
    assert types.count("attempt_start") == 2  # the re-run happened


def test_kv_parity_end_to_end_and_cheaper_than_replicate(mesh8):
    """kv + parity end-to-end: payloads follow their keys bit-exactly,
    and the kv premium (keys AND payload planes) still undercuts kv
    replication."""
    tk, tv = gen_terasort(8192, seed=21)
    order = np.argsort(tk, kind="stable")
    bytes_by_mode = {}
    for mode in ("replicate", "parity"):
        ss = SampleSort(
            mesh8,
            JobConfig(
                exchange="ring", redundancy=2, redundancy_mode=mode,
                key_dtype=np.uint64, payload_bytes=tv.shape[1],
            ),
        )
        m = _metered()
        ok, ov = ss.sort_kv(tk, tv, metrics=m)
        np.testing.assert_array_equal(ok, tk[order])
        np.testing.assert_array_equal(ov, tv[order])
        bytes_by_mode[mode] = m.counters["coded_replica_bytes"]
    assert 0 < bytes_by_mode["parity"] < 0.75 * bytes_by_mode["replicate"]


# ---- straggler-first range serving ----------------------------------------


@pytest.mark.parametrize("mode", ["replicate", "parity"])
def test_straggler_serve_exactly_once(mesh8, mode):
    """A live-but-slow owner's range is served from the coded plane:
    exactly one `coded_straggler_serve`, the losing owner fetch journals
    `won=False` after the drain, output bit-identical, no failure, no
    mesh re-form."""
    ss = SampleSort(
        mesh8,
        JobConfig(exchange="ring", redundancy=2, redundancy_mode=mode),
    )
    ss.straggler_fn = lambda: 3
    ss.fetch_delay_fn = lambda s: 0.75  # the holder leg always wins
    data = gen_uniform(60_000, seed=11)
    m = _metered()
    t0 = time.perf_counter()
    np.testing.assert_array_equal(ss.sort(data, metrics=m), np.sort(data))
    wall = time.perf_counter() - t0
    assert m.counters["coded_straggler_serves"] == 1
    serve = next(
        e for e in m.journal.events() if e.type == "coded_straggler_serve"
    )
    assert serve.fields["range"] == 3 and serve.fields["mode"] == mode
    assert serve.fields["recovered_keys"] > 0
    # the sort returned WITHOUT paying the owner's injected delay
    assert serve.fields["wall_s"] < 0.75
    types = m.journal.types()
    assert "worker_dead" not in types and "mesh_reform" not in types
    ss.join_stragglers()
    fetch = next(
        e for e in m.journal.events() if e.type == "coded_owner_fetch"
    )
    assert fetch.fields["won"] is False and fetch.fields["range"] == 3
    report = assert_conformant(m.journal)
    assert report["contracts"]["straggler_serve"]["checked"] >= 1
    del wall


def test_straggler_serve_uncoded_ignored(mesh8):
    """No replica plane, no race: redundancy=1 keeps the plain wait-on-
    owner path even with a named straggler."""
    ss = SampleSort(mesh8, JobConfig(exchange="ring"))
    ss.straggler_fn = lambda: 3
    ss.fetch_delay_fn = lambda s: 0.0
    data = gen_uniform(30_000, seed=13)
    m = _metered()
    np.testing.assert_array_equal(ss.sort(data, metrics=m), np.sort(data))
    assert m.counters.get("coded_straggler_serves", 0) == 0
    assert "coded_straggler_serve" not in m.journal.types()


@pytest.mark.parametrize("mode", ["replicate", "parity"])
def test_scheduler_straggler_binding_via_injector(mode):
    """`FaultInjector.slow` names a WORKER; the scheduler translates to
    the attempt's mesh POSITION and the serve happens with no fault."""
    from dsort_tpu.scheduler import SpmdScheduler

    inj = FaultInjector()
    sched = SpmdScheduler(
        job=JobConfig(settle_delay_s=0.01, exchange="ring", redundancy=2,
                      redundancy_mode=mode),
        injector=inj,
    )
    z = gen_zipf(1 << 16, a=1.3, seed=7)
    np.testing.assert_array_equal(sched.sort(z), np.sort(z))  # warm
    inj.slow(5, 0.75)
    m = _metered()
    np.testing.assert_array_equal(sched.sort(z, metrics=m), np.sort(z))
    assert m.counters["coded_straggler_serves"] == 1
    serve = next(
        e for e in m.journal.events() if e.type == "coded_straggler_serve"
    )
    assert serve.fields["range"] == 5
    types = m.journal.types()
    assert types.count("attempt_start") == 1
    assert "worker_dead" not in types  # no failure was injected
    assert m.counters.get("mesh_reforms", 0) == 0
    for ss in sched._sorters.values():
        ss.join_stragglers()
    assert_conformant(m.journal)
    inj.slow(5, 0)  # clear
    # all 8 workers still live: serving never evicts the slow owner
    assert sorted(sched.table.live_workers()) == list(range(8))


def test_health_verdict_names_straggler_position():
    """`obs.health.straggler_position` is the production binding: a
    verdict that is BOTH straggler and degraded maps to its mesh
    position; healthy or merely-degraded agents don't."""
    from dsort_tpu.obs.health import straggler_position

    class FakeAnalyzer:
        def __init__(self, verdicts):
            self._v = verdicts

        def verdicts(self):
            return self._v

    v = {
        "a0": {"straggler": False, "degraded": False},
        "a1": {"straggler": True, "degraded": False},   # fast blip only
        "a2": {"straggler": True, "degraded": True},    # the real one
    }
    assert straggler_position(FakeAnalyzer(v), ["a0", "a1", "a2"]) == 2
    assert straggler_position(FakeAnalyzer(v), ["a0", "a1"]) is None
    assert straggler_position(FakeAnalyzer({}), ["a0"]) is None


# ---- wave pipeline: parity + retention ------------------------------------


def test_wave_parity_repair_and_restart_resume(tmp_path):
    """A parity-coded wave repairs a mid-ring loss from the parity plane
    (no host re-sort) and its runs stay ordinary durable entries for
    restart-resume."""
    from dsort_tpu.models.wave_sort import ExternalWaveSort

    data = gen_uniform(1 << 17, seed=17)
    kw = dict(
        wave_elems=1 << 16, spill_dir=str(tmp_path), job_id="parwave",
        job=JobConfig(exchange="ring"), redundancy=2,
        redundancy_mode="parity",
    )
    ws = ExternalWaveSort(**kw)
    assert ws.redundancy_mode == "parity"
    inj = FaultInjector()
    inj.fail_once(3, "ring")
    ws.fault_hook = _sweep_hook(inj, ws.num_workers)
    m = _metered()
    np.testing.assert_array_equal(ws.sort(data, metrics=m), np.sort(data))
    assert m.counters["coded_recoveries"] == 1
    assert m.counters.get("wave_runs_resorted", 0) == 0
    types = m.journal.types()
    assert "parity_recover" in types and "wave_resume" not in types
    assert_conformant(m.journal)
    # restart: coded runs restore for free
    ws2 = ExternalWaveSort(**kw)
    m2 = _metered()
    np.testing.assert_array_equal(ws2.sort(data, metrics=m2), np.sort(data))
    assert m2.counters["runs_resumed"] == 2 * ws2.num_workers
    assert m2.counters.get("waves_sorted", 0) == 0


def test_wave_terasort_coded_retention_repair(tmp_path, devices):
    """Record waves keep the retention doctrine: a coded TeraSort wave
    repairs from the retained D2H shards — `coded_recover` with
    mode="retain", replica_bytes=0, zero runs re-sorted — and the
    output still matches the oracle byte-for-byte."""
    from dsort_tpu.data.ingest import _pack_be64, gen_terasort_file, terasort_secondary
    from dsort_tpu.models.wave_sort import ExternalWaveTeraSort
    from dsort_tpu.parallel.mesh import local_device_mesh

    in_path = str(tmp_path / "in.bin")
    out_path = str(tmp_path / "out.bin")
    gen_terasort_file(in_path, 16000, seed=23)
    t = ExternalWaveTeraSort(
        local_device_mesh(8), wave_recs=4096,
        spill_dir=str(tmp_path / "spill"), job_id="twc", redundancy=2,
        resume=False,
    )
    inj = FaultInjector()
    inj.fail_once(3, "ring")
    t.fault_hook = _sweep_hook(inj, t.num_workers)
    m = _metered()
    t.sort_file(in_path, out_path, metrics=m)
    raw = np.fromfile(in_path, np.uint8).reshape(-1, 100)
    order = np.lexsort(
        (terasort_secondary(raw[:, 8:10]), _pack_be64(raw[:, :8]))
    )
    got = np.fromfile(out_path, np.uint8).reshape(-1, 100)
    np.testing.assert_array_equal(got, raw[order])
    rec = next(e for e in m.journal.events() if e.type == "coded_recover")
    assert rec.fields["mode"] == "retain"
    assert rec.fields["replica_bytes"] == 0
    assert m.counters.get("wave_runs_resorted", 0) == 0
    assert_conformant(m.journal)


# ---- planner: the mode and slice policies ---------------------------------


def test_plan_redundancy_mode_policy_replay():
    from dsort_tpu.obs.plan import replay_decision

    # observed losses: full copies
    chosen, rejected = replay_decision(
        "redundancy_mode", {"agents": 4, "degraded": 0, "loss_events": 2}
    )
    assert chosen == "replicate"
    assert rejected[0]["value"] == "parity"
    # degraded-but-alive fleet: parity
    chosen, rejected = replay_decision(
        "redundancy_mode", {"agents": 4, "degraded": 2, "loss_events": 0}
    )
    assert chosen == "parity"
    assert rejected[0]["value"] == "replicate"
    # healthy fleet: replicate (the no-signal default)
    chosen, _ = replay_decision(
        "redundancy_mode", {"agents": 4, "degraded": 0, "loss_events": 0}
    )
    assert chosen == "replicate"


def test_plan_slice_devices_policy_replay():
    from dsort_tpu.obs.plan import SLICE_KEYS_PER_DEVICE, replay_decision

    # small admitted rungs: 1-device slices (max packing)
    chosen, _ = replay_decision(
        "slice_devices",
        {"num_devices": 8, "current": 4, "rungs": [1 << 12] * 10},
    )
    assert chosen == 1
    # heavy mix: widen until p90/w fits the per-device budget
    heavy = [4 * SLICE_KEYS_PER_DEVICE] * 10
    chosen, _ = replay_decision(
        "slice_devices",
        {"num_devices": 8, "current": 1, "rungs": heavy},
    )
    assert chosen == 4
    # no admissions: keep the current width, named rejection
    chosen, rejected = replay_decision(
        "slice_devices", {"num_devices": 8, "current": 2, "rungs": []}
    )
    assert chosen == 2 and rejected[0]["value"] == "resize"


def test_planned_slice_devices_seam_replay_equals_live():
    from dsort_tpu.obs.plan import planned_slice_devices

    job = JobConfig(autotune=True)
    records = [
        {"type": "job_admitted", "n_keys": 1 << 12, "dtype": "int32"}
        for _ in range(6)
    ]
    m = _metered()
    live = planned_slice_devices(job, None, 4, 8, records, m)
    assert live == 1
    dec = next(
        e for e in m.journal.events() if e.type == "plan_decision"
    )
    assert dec.fields["policy"] == "slice_devices"
    assert dec.fields["chosen"] == 1
    # replay the journaled decision from its own inputs
    from dsort_tpu.obs.plan import replay_decision

    assert replay_decision("slice_devices", dec.fields["inputs"])[0] == live
    # a second replay from the same records is bit-identical
    assert planned_slice_devices(job, None, 4, 8, records, _metered()) == 1
    # autotune off: the knob rides untouched, nothing journaled
    m2 = _metered()
    assert planned_slice_devices(JobConfig(), None, 4, 8, records, m2) == 4
    assert m2.journal.types() == []


def test_planned_slice_devices_explicit_wins():
    from dsort_tpu.obs.plan import planned_slice_devices

    job = JobConfig(autotune=True, explicit=("slice_devices",))
    records = [
        {"type": "job_admitted", "n_keys": 1 << 12, "dtype": "int32"}
        for _ in range(6)
    ]
    m = _metered()
    assert planned_slice_devices(job, None, 4, 8, records, m) == 4
    ov = next(e for e in m.journal.events() if e.type == "plan_override")
    assert ov.fields["policy"] == "slice_devices"
    assert ov.fields["explicit"] == 4 and ov.fields["planned"] == 1


def test_serve_replans_slice_width_from_journal():
    """`SortService.__init__` replays the attached journal through the
    slice policy: a small-rung admission history narrows the slices
    before any worker starts."""
    from dsort_tpu.config import ServeConfig
    from dsort_tpu.serve.service import SortService

    journal = EventLog()
    for _ in range(6):
        journal.emit("job_admitted", n_keys=1 << 12, dtype="int32")
    svc = SortService(
        job=JobConfig(autotune=True),
        serve=ServeConfig(slice_devices=4),
        journal=journal, start=False,
    )
    try:
        assert all(len(g) == 1 for g in svc._slices.values())
        assert len(svc._slices) == len(svc._devices)
    finally:
        svc.shutdown()


# ---- analyzer: the v2 recovery verdict ------------------------------------


def test_analyze_recovery_verdict_parity_and_straggler():
    from dsort_tpu.obs.analyze import analyze_records
    from dsort_tpu.scheduler import SpmdScheduler

    z = gen_zipf(1 << 16, a=1.3, seed=5)
    inj = FaultInjector()
    sched = SpmdScheduler(
        job=JobConfig(
            settle_delay_s=0.01, exchange="ring", redundancy=2,
            redundancy_mode="parity",
        ),
        injector=inj,
    )
    sched.sort(z)  # warm
    inj.fail_once(3, "ring")
    m = _metered()
    np.testing.assert_array_equal(sched.sort(z, metrics=m), np.sort(z))
    v = analyze_records([e.to_dict() for e in m.journal.events()])["recovery"]
    assert v["path"] == "parity_reconstruct"
    assert v["coded"]["parity_recoveries"] == 1
    assert v["coded"]["recoveries"] == 0
    assert v["straggler"]["serves"] == 0
    # straggler-only journal: serves counted, no failure posture
    inj.slow(5, 0.4)
    m2 = _metered()
    np.testing.assert_array_equal(sched.sort(z, metrics=m2), np.sort(z))
    for ss in sched._sorters.values():
        ss.join_stragglers()
    v2 = analyze_records(
        [e.to_dict() for e in m2.journal.events()]
    )["recovery"]
    assert v2["path"] == "straggler_serve"
    assert v2["straggler"]["serves"] == 1
    assert v2["straggler"]["served_keys"] > 0


# ---- registries + docs ----------------------------------------------------


def test_v2_events_and_counters_registered():
    for ev in ("parity_recover", "coded_straggler_serve",
               "coded_owner_fetch"):
        assert ev in EVENT_TYPES
    assert "coded_straggler_serves" in COUNTERS
    from dsort_tpu.analysis.spec.contracts import TRACE_CONTRACTS

    assert "parity_recovery" in TRACE_CONTRACTS
    assert "straggler_serve" in TRACE_CONTRACTS


def test_architecture_documents_coded_v2():
    """§18's schema is test-enforced like §7–§17: the section must name
    the knob, the parity math, the events, and the straggler drain."""
    text = open(os.path.join(REPO, "ARCHITECTURE.md")).read()
    assert "## 18. Coded exchange v2" in text
    s18 = text.split("## 18. Coded exchange v2", 1)[1]
    for term in (
        "`redundancy_mode`", "REDUNDANCY_MODE", "parity_slots", "GF(256)",
        "0x11D", "`parity_recover`", "`coded_straggler_serve`",
        "`coded_owner_fetch`", "`coded_straggler_serves`",
        "`StragglerClaim`", "`coded_replica_bytes`", "straggler_serve",
        "parity_recovery", "join_stragglers",
    ):
        assert term in s18, f"§18 must document {term}"
