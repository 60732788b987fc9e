"""Hierarchical exchange plane (`parallel.exchange` hier schedule,
ARCHITECTURE §17).

The acceptance bar (ISSUE 18): `exchange="hier"` runs the two-level pod
schedule — intra-host aggregation, exactly ONE transfer per (src-host,
dst-host) pair over the DCN leg, local scatter + merge — bit-identically
to the flat schedules with a MEASURED DCN-byte reduction; device loss
re-forms within the host grouping and a whole-host loss re-plans the
(H, H) schedule on the survivors (journaled `hier_reform`,
trace-contract-pinned); the planner arms the schedule only from a real
topology signal; capacity rungs and splitter quality hold out to
P=128–512 simulated devices (pure host math — no 512-device backend).
"""

import logging
import os

import numpy as np
import pytest

from dsort_tpu.analysis.spec import assert_conformant
from dsort_tpu.config import JobConfig, SortConfig
from dsort_tpu.data.ingest import gen_uniform, gen_zipf
from dsort_tpu.parallel.exchange import (
    HierPlan,
    hier_plan,
    hier_wire_bytes,
    host_matrix,
    ladder_rungs,
    note_hier_plan,
    resolve_exchange,
    resolve_hier_hosts,
    ring_caps,
    ring_dcn_bytes,
)
from dsort_tpu.parallel.sample_sort import SampleSort
from dsort_tpu.scheduler.fault import FaultInjector
from dsort_tpu.utils.events import EventLog
from dsort_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _metered():
    return Metrics(journal=EventLog())


@pytest.fixture
def dsort_warnings(caplog):
    """caplog that actually sees dsort warnings: the package root logger
    ships its own stderr handler with propagate=False, so pytest's
    root-attached capture handler needs propagation restored."""
    root = logging.getLogger("dsort_tpu")
    old = root.propagate
    root.propagate = True
    try:
        with caplog.at_level(logging.WARNING, logger="dsort_tpu"):
            yield caplog
    finally:
        root.propagate = old


# ---- knob resolution -------------------------------------------------------


def test_resolve_exchange_accepts_hier():
    assert resolve_exchange("hier", "alltoall", 8) == "hier"
    assert resolve_exchange(None, "hier", 8) == "hier"
    # A 1-worker mesh short-circuits every schedule.
    assert resolve_exchange("hier", "alltoall", 1) == "alltoall"
    with pytest.raises(ValueError, match="hier"):
        resolve_exchange("hierarchical", "alltoall", 8)


def test_resolve_hier_hosts_cases():
    # Below 4 workers no >=2x2 grouping exists.
    assert resolve_hier_hosts(2, 2) == 0
    assert resolve_hier_hosts(0, 3) == 0
    # Explicit divisor wins as-is.
    assert resolve_hier_hosts(2, 8) == 2
    assert resolve_hier_hosts(4, 8) == 4
    # Non-divisor falls back to the largest divisor <= want with >= 2
    # devices per host — the fault contract's re-plan rule.
    assert resolve_hier_hosts(3, 8) == 2
    assert resolve_hier_hosts(4, 6) == 3
    # An explicit H == P passes through (1 device/host degenerates to a
    # pure host ring — nothing to aggregate, but still correct; auto and
    # the planner arm never pick it).
    assert resolve_hier_hosts(8, 8) == 8
    # Auto in a single process simulates 2 hosts.
    assert resolve_hier_hosts(0, 8) == 2
    # A prime mesh has no valid grouping at all.
    assert resolve_hier_hosts(2, 7) == 0


def test_job_config_validates_hier_hosts():
    from dsort_tpu.config import ConfigError

    assert JobConfig(exchange="hier", hier_hosts=2).hier_hosts == 2
    with pytest.raises(ConfigError, match="hier_hosts"):
        JobConfig(hier_hosts=-1)
    with pytest.raises(ConfigError, match="exchange"):
        JobConfig(exchange="two-level")


# ---- host-side plan math ---------------------------------------------------


def _synthetic_hist(p: int, n_local: int, seed: int = 0) -> np.ndarray:
    """A skewed (P, P) bucket histogram with every row summing n_local —
    what the plan phase all_gathers for a P-device mesh."""
    rng = np.random.default_rng(seed)
    w = rng.zipf(1.4, size=(p, p)).astype(np.float64)
    hist = np.floor(w / w.sum(axis=1, keepdims=True) * n_local).astype(np.int64)
    hist[:, 0] += n_local - hist.sum(axis=1)  # exact row sums
    return hist


def test_host_matrix_reduces_device_blocks():
    p, h = 8, 2
    hist = _synthetic_hist(p, 4096, seed=1)
    mat = host_matrix(hist, h)
    assert mat.shape == (h, h)
    d = p // h
    for g in range(h):
        for dst in range(h):
            blk = hist[g * d:(g + 1) * d, dst * d:(dst + 1) * d]
            assert mat[g, dst] == blk.sum()
    # A batched histogram reduces element-wise max over jobs first.
    batched = np.stack([hist, 2 * hist])
    assert np.array_equal(host_matrix(batched, h), host_matrix(2 * hist, h))


@pytest.mark.parametrize("p,hosts", [(8, 2), (8, 4), (16, 4)])
def test_hier_plan_caps_cover_measured_maxima(p, hosts):
    n_local = 4096
    hist = _synthetic_hist(p, n_local, seed=p + hosts)
    plan = hier_plan(hist, n_local, p, hosts)
    d = p // hosts
    assert plan == HierPlan(hosts, d, -(-hosts // d), plan.agg_cap,
                            plan.leg_caps, plan.scatter_cap)
    # Phase 1: the cap covers every (src device, dst host) aggregate.
    dev_host = hist.reshape(p, hosts, d).sum(axis=2)
    assert plan.agg_cap >= dev_host.max()
    # Phase 2: leg 0 is the self leg (never crosses the DCN); each shift's
    # cap covers its (src-host, dst-host) diagonal max.
    mat = host_matrix(hist, hosts)
    assert plan.leg_caps[0] == 0 and len(plan.leg_caps) == hosts
    for shift in range(1, hosts):
        mx = max(mat[g, (g + shift) % hosts] for g in range(hosts))
        assert plan.leg_caps[shift] >= mx
    # Phase 3: the scatter cap is bounded by the whole-HOST receiving
    # population — a skewed sub-slice of one host's aggregate can exceed
    # a single device's n_local.
    host_dev = hist.reshape(hosts, d, p).sum(axis=1)
    assert plan.scatter_cap >= host_dev.max()


def test_hier_plan_caps_sit_on_the_quantization_ladder():
    """Recompile-bound doctrine: every hier cap is a `ladder_rungs` value
    (or an exact clamp bound), so skew can demand only a bounded number
    of distinct compiled hier programs."""
    p, hosts, n_local = 8, 2, 4096
    d = p // hosts
    hist = _synthetic_hist(p, n_local, seed=7)
    plan = hier_plan(hist, n_local, p, hosts)
    assert plan.agg_cap % 8 == 0
    assert plan.scatter_cap % 8 == 0
    assert all(c % 8 == 0 for c in plan.leg_caps[1:])
    rungs = set(ladder_rungs(n_local * d * 2))
    clamp_bounds = {-(-n_local // 8) * 8, -(-(n_local * d) // 8) * 8,
                    d * plan.agg_cap}
    for cap in (plan.agg_cap, plan.scatter_cap, *plan.leg_caps[1:]):
        assert cap in rungs or cap in clamp_bounds or cap % 8 == 0


def test_hier_wire_bytes_and_flat_ring_baseline():
    p, hosts, n_local, bps = 8, 2, 1024, 8
    hist = np.full((p, p), n_local // p, dtype=np.int64)
    plan = hier_plan(hist, n_local, p, hosts)
    dcn, intra = hier_wire_bytes(plan, bps)
    # DCN: each non-self shift ships H aggregated transfers of its cap.
    assert dcn == sum(plan.leg_caps[1:]) * hosts * bps
    # Intra: slots x (agg + scatter) per device per step, both local rings.
    per_step = plan.slots * (plan.agg_cap + plan.scatter_cap)
    assert intra == (plan.dev_per_host - 1) * per_step * p * bps
    # The flat ring pushes its caps across the host boundary whenever src
    # and dst land on different hosts — strictly more DCN traffic than
    # one aggregated transfer per host pair under uniform load.
    caps = ring_caps(hist, n_local, p)
    flat_dcn = ring_dcn_bytes(caps, bps, p, hosts)
    d = p // hosts
    expect = sum(
        int(caps[k]) * sum(
            1 for i in range(p) if i // d != ((i + k) % p) // d
        ) for k in range(1, p)
    ) * bps
    assert flat_dcn == expect
    # Uniform load is the no-win case: the same keys cross hosts either
    # way, so aggregation can at best tie the flat baseline ...
    assert dcn <= flat_dcn
    # ... while skew is where the flat ring pays: every step pads to its
    # diagonal MAX bucket, and aggregation averages that padding away.
    skewed = _synthetic_hist(p, n_local, seed=17)
    s_plan = hier_plan(skewed, n_local, p, hosts)
    s_dcn, _ = hier_wire_bytes(s_plan, bps)
    s_caps = ring_caps(skewed, n_local, p)
    assert s_dcn < ring_dcn_bytes(s_caps, bps, p, hosts)


def test_note_hier_plan_counters_and_events():
    p, hosts, n_local, bps = 8, 4, 4096, 8
    hist = _synthetic_hist(p, n_local, seed=3)
    plan = hier_plan(hist, n_local, p, hosts)
    caps = ring_caps(hist, n_local, p)
    m = _metered()
    note_hier_plan(m, plan, caps, hist, n_local, p, bps, 1.25)
    dcn, intra = hier_wire_bytes(plan, bps)
    flat_dcn = ring_dcn_bytes(caps, bps, p, hosts)
    assert m.counters["hier_exchanges"] == 1
    assert m.counters["dcn_bytes_on_wire"] == dcn
    assert m.counters["intra_host_bytes_on_wire"] == intra
    assert m.counters["exchange_bytes_on_wire"] == dcn + intra
    # The headline identity: saved == what the flat ring would have
    # pushed over the inter-host fabric minus what hier actually ships.
    assert m.counters["dcn_bytes_saved"] == max(flat_dcn - dcn, 0)
    types = m.journal.types()
    assert types.count("hier_exchange_plan") == 1
    assert types.count("hier_exchange_leg") == hosts - 1
    assert "skew_report" in types
    ev = next(e for e in m.journal.events() if e.type == "hier_exchange_plan")
    assert ev.fields["hosts"] == hosts
    assert ev.fields["flat_ring_dcn_bytes"] == flat_dcn


# ---- end-to-end correctness on the mesh ------------------------------------


@pytest.mark.parametrize("data", ["zipf", "uniform", "zipf_int64"])
@pytest.mark.parametrize("hosts", [2, 4])
def test_hier_bit_identical_vs_ring(mesh8, hosts, data):
    """At 2x4 and 4x2 simulated hosts the two-level schedule returns the
    flat ring's output, in one two-level exchange that crosses the host
    boundary with fewer bytes than the flat ring would for the same
    histogram."""
    job = {}
    if data == "zipf":
        keys = gen_zipf(60_000, a=1.3, seed=11)
    elif data == "uniform":
        keys = gen_uniform(60_000, seed=12)
    else:
        keys, job = gen_zipf(1 << 16, a=1.3, seed=6), dict(key_dtype=np.int64)
    expect = np.sort(keys)
    ring = SampleSort(mesh8, JobConfig(exchange="ring", **job)).sort(keys)
    m = _metered()
    hier = SampleSort(
        mesh8, JobConfig(exchange="hier", hier_hosts=hosts, **job)
    ).sort(keys, metrics=m)
    np.testing.assert_array_equal(ring, expect)
    np.testing.assert_array_equal(hier, expect)
    assert m.counters["hier_exchanges"] == 1
    dcn = m.counters["dcn_bytes_on_wire"]
    assert 0 < dcn < dcn + m.counters["dcn_bytes_saved"]


def test_hier_journals_the_dcn_split(mesh8):
    data = gen_zipf(100_000, a=1.3, seed=13)
    m = _metered()
    ss = SampleSort(mesh8, JobConfig(exchange="hier", hier_hosts=2))
    np.testing.assert_array_equal(ss.sort(data, metrics=m), np.sort(data))
    assert m.counters["hier_exchanges"] == 1
    assert m.counters["dcn_bytes_on_wire"] > 0
    assert m.counters["intra_host_bytes_on_wire"] > 0
    assert m.counters["exchange_bytes_on_wire"] == (
        m.counters["dcn_bytes_on_wire"]
        + m.counters["intra_host_bytes_on_wire"]
    )
    # The two-level schedule crossed the host boundary with LESS than the
    # flat ring's measured baseline for the same histogram.
    assert m.counters["dcn_bytes_saved"] > 0
    assert "hier_exchange_plan" in m.journal.types()


def test_hier_kv_downgrades_to_ring_with_warning(mesh8, dsort_warnings):
    from dsort_tpu.data.ingest import gen_terasort

    tk, tv = gen_terasort(4096, seed=5)
    ss = SampleSort(
        mesh8,
        JobConfig(exchange="hier", hier_hosts=2, key_dtype=np.uint64,
                  payload_bytes=tv.shape[1]),
    )
    m = _metered()
    out_k, out_v = ss.sort_kv(tk, tv, metrics=m)
    np.testing.assert_array_equal(out_k, np.sort(tk))
    assert any("keys-only" in r.getMessage()
               for r in dsort_warnings.records)
    assert m.counters.get("hier_exchanges", 0) == 0


def test_hier_small_mesh_downgrades_with_warning(dsort_warnings):
    from dsort_tpu.parallel.mesh import local_device_mesh

    data = gen_uniform(10_000, seed=6)
    ss = SampleSort(local_device_mesh(2), JobConfig(exchange="hier"))
    m = _metered()
    np.testing.assert_array_equal(ss.sort(data, metrics=m), np.sort(data))
    assert any(">= 4 workers" in r.getMessage()
               for r in dsort_warnings.records)
    assert m.counters.get("hier_exchanges", 0) == 0


# ---- the fault contract ----------------------------------------------------


def _drill(data, hosts, victims, metrics):
    from dsort_tpu.scheduler import SpmdScheduler

    inj = FaultInjector()
    sched = SpmdScheduler(
        job=JobConfig(settle_delay_s=0.01, exchange="hier",
                      hier_hosts=hosts),
        injector=inj,
    )
    np.testing.assert_array_equal(sched.sort(data), np.sort(data))  # warm
    for w in victims:
        inj.fail_once(w, "ring")
    return sched.sort(data, metrics=metrics)


@pytest.mark.parametrize("victims", [[1, 2], [5, 6]])
def test_scheduler_device_loss_reforms_within_host(victims):
    """Losing devices of ONE host keeps the 2-host grouping: the re-plan
    rule lands on the same H, journaled as `hier_reform` after the
    `mesh_reform` — the §17 fault contract's first half.  Two victims so
    the 6 survivors still divide by 2 (an odd count would force the
    downgrade a real pod's fixed host slots would not)."""
    z = gen_zipf(1 << 16, a=1.3, seed=21)
    m = _metered()
    out = _drill(z, hosts=2, victims=victims, metrics=m)
    np.testing.assert_array_equal(out, np.sort(z))
    types = m.journal.types()
    assert types.count("hier_reform") == 1
    assert (types.index("worker_dead") < types.index("mesh_reform")
            < types.index("hier_reform"))
    rf = next(e for e in m.journal.events() if e.type == "hier_reform")
    assert rf.fields["survivors"] == 6
    assert rf.fields["hosts_before"] == 2
    assert rf.fields["hosts_after"] == 2
    assert rf.fields["downgraded"] is False
    # The re-run on survivors planned a fresh two-level schedule.
    assert m.counters["hier_exchanges"] >= 1
    assert m.counters["mesh_reforms"] == 1
    assert_conformant(m.journal)


@pytest.mark.parametrize("victims", [[2, 3], [6, 7]])  # host 1 or 3 of 4
def test_scheduler_host_loss_replans_on_survivors(victims):
    """THE acceptance drill: ALL of one host's devices die mid-phase-two
    (the hook fires with the (H, H) legs planned and in flight).  The 6
    survivors no longer divide by 4, so the re-plan lands on H=3 — fewer
    hosts, still two-level, never a silent downgrade to the flat ring."""
    z = gen_zipf(1 << 16, a=1.3, seed=22)
    m = _metered()
    out = _drill(z, hosts=4, victims=victims, metrics=m)
    np.testing.assert_array_equal(out, np.sort(z))
    rf = next(e for e in m.journal.events() if e.type == "hier_reform")
    assert rf.fields["hosts_before"] == 4
    assert rf.fields["hosts_after"] == 3
    assert rf.fields["survivors"] == 6
    assert rf.fields["downgraded"] is False
    assert m.counters["hier_exchanges"] >= 1
    assert_conformant(m.journal)


# ---- the wave pipeline -----------------------------------------------------


def test_wave_hier_matches_oracle(tmp_path, devices):
    from dsort_tpu.models.wave_sort import ExternalWaveSort
    from dsort_tpu.parallel.mesh import local_device_mesh

    data = gen_zipf(30_000, a=1.3, dtype=np.int64, seed=23)
    s = ExternalWaveSort(
        local_device_mesh(8), wave_elems=6000, spill_dir=str(tmp_path),
        job_id="whier", exchange="hier", job=JobConfig(hier_hosts=2),
    )
    m = _metered()
    np.testing.assert_array_equal(s.sort(data, metrics=m), np.sort(data))
    # Every wave planned and journaled its own two-level schedule.
    assert m.counters["hier_exchanges"] == m.counters["waves_sorted"] > 0
    assert m.counters["dcn_bytes_saved"] > 0


# ---- the planner arm -------------------------------------------------------


def test_decide_exchange_hier_from_measured_topology():
    from dsort_tpu.obs.plan import replay_decision

    chosen, rejected = replay_decision("exchange", {
        "max_mean_ratio": 1.0, "num_workers": 8, "fused_ok": False,
        "redundancy": 1, "hosts": 2,
    })
    assert chosen == "hier"
    assert {r["value"] for r in rejected} == {"alltoall", "ring", "fused"}
    # 1 device/host leaves nothing to aggregate: fall through to skew.
    chosen, _ = replay_decision("exchange", {
        "max_mean_ratio": 3.0, "num_workers": 8, "hosts": 8,
    })
    assert chosen == "ring"
    # Redundancy still forces the flat ring (replica slots).
    chosen, _ = replay_decision("exchange", {
        "num_workers": 8, "hosts": 2, "redundancy": 2,
    })
    assert chosen == "ring"
    # Old journaled decisions (no hosts key) replay unchanged.
    chosen, _ = replay_decision("exchange", {
        "max_mean_ratio": 3.0, "num_workers": 8, "fused_ok": False,
    })
    assert chosen == "ring"


def test_autotune_single_slice_never_arms_hier(mesh8):
    """Planner-on, knob unset, single process: the planner must NOT
    reroute through the simulated 2-host fallback — only a REAL topology
    signal (explicit hier_hosts or a multi-process launch) arms hier."""
    data = gen_zipf(60_000, a=1.3, seed=24)
    m = _metered()
    ss = SampleSort(mesh8, JobConfig(autotune=True))
    np.testing.assert_array_equal(ss.sort(data, metrics=m), np.sort(data))
    dec = next(e for e in m.journal.events() if e.type == "plan_decision")
    assert dec.fields["policy"] == "exchange"
    assert dec.fields["inputs"]["hosts"] == 0
    assert dec.fields["chosen"] != "hier"
    # An explicit hier_hosts IS a real signal: the planner arms hier.
    m2 = _metered()
    ss2 = SampleSort(mesh8, JobConfig(autotune=True, hier_hosts=2))
    np.testing.assert_array_equal(ss2.sort(data, metrics=m2), np.sort(data))
    dec2 = next(e for e in m2.journal.events() if e.type == "plan_decision")
    assert dec2.fields["inputs"]["hosts"] == 2
    assert dec2.fields["chosen"] == "hier"
    assert m2.counters["hier_exchanges"] == 1


# ---- the dispatch_timeout_s policy -----------------------------------------


def test_decide_dispatch_timeout_headroom_and_floor():
    from dsort_tpu.obs.plan import (
        DISPATCH_TIMEOUT_HEADROOM,
        DISPATCH_TIMEOUT_MIN_S,
        replay_decision,
    )

    chosen, rejected = replay_decision("dispatch_timeout_s", {
        "current": 30.0, "p99_s": 0.25, "samples": 16,
    })
    assert chosen == round(0.25 * DISPATCH_TIMEOUT_HEADROOM, 3) == 2.0
    assert any(r["value"] == 30.0 for r in rejected)
    # The floor keeps a microsecond-fast fleet from a hair-trigger reap.
    chosen, _ = replay_decision("dispatch_timeout_s", {
        "current": 30.0, "p99_s": 0.001, "samples": 4,
    })
    assert chosen == DISPATCH_TIMEOUT_MIN_S
    # No samples yet: keep the current deadline and say so.
    chosen, rejected = replay_decision("dispatch_timeout_s", {
        "current": 30.0, "p99_s": 0.0, "samples": 0,
    })
    assert chosen == 30.0
    assert rejected[0]["value"] == "resize"


def test_planner_folds_job_dispatched_latencies():
    from dsort_tpu.obs.plan import DISPATCH_LATENCY_HISTORY, Planner

    pl = Planner()
    for lat in (0.1, 0.2, 0.4):
        pl.observe("job_dispatched", {"job_id": 1, "agent": "a",
                                      "accept_latency_s": lat})
    inputs = pl.dispatch_timeout_inputs(30.0)
    assert inputs["samples"] == 3
    assert 0.1 <= inputs["p99_s"] <= 0.4
    assert inputs["current"] == 30.0
    # Bounded window + snapshot round-trip.
    assert len(Planner().state_dict()["dispatch_latencies"]) == 0
    for _ in range(2 * DISPATCH_LATENCY_HISTORY):
        pl.observe("job_dispatched", {"accept_latency_s": 0.05})
    assert (len(pl.state_dict()["dispatch_latencies"])
            == DISPATCH_LATENCY_HISTORY)
    # decide journals the replayable record.
    from dsort_tpu.obs.plan import replay_decision

    m = _metered()
    chosen = pl.decide("dispatch_timeout_s",
                       pl.dispatch_timeout_inputs(30.0), metrics=m)
    ev = next(e for e in m.journal.events() if e.type == "plan_decision")
    assert ev.fields["policy"] == "dispatch_timeout_s"
    assert replay_decision("dispatch_timeout_s",
                           ev.fields["inputs"])[0] == chosen


# ---- terasort conf parity (satellite: CLI plumb-through) --------------------


def test_terasort_exchange_conf_parity_and_precedence(tmp_path,
                                                      dsort_warnings):
    conf = tmp_path / "job.conf"
    conf.write_text("EXCHANGE=hier\nHIER_HOSTS=2\n")
    cfg = SortConfig.from_conf_file(str(conf))
    assert cfg.job.exchange == "hier" and cfg.job.hier_hosts == 2

    from dsort_tpu.cli import main as cli_main
    from dsort_tpu.data.ingest import read_terasort_file

    inp = str(tmp_path / "in.bin")
    outp = str(tmp_path / "out.bin")
    assert cli_main(["gen", "2000", "-o", inp, "--dist", "terasort"]) == 0
    # The conf EXCHANGE key reaches the record job: the kv plane's
    # keys-only downgrade warning names the hier knob it received.
    assert cli_main(["terasort", inp, "-o", outp, "--workers", "8",
                     "--conf", str(conf)]) == 0
    assert any("keys-only" in r.getMessage()
               for r in dsort_warnings.records)
    dsort_warnings.clear()
    # An explicit --exchange flag wins over the conf key: no hier warning.
    assert cli_main(["terasort", inp, "-o", outp, "--workers", "8",
                     "--conf", str(conf), "--exchange", "ring"]) == 0
    assert not any("keys-only" in r.getMessage()
                   for r in dsort_warnings.records)
    k, _ = read_terasort_file(outp)
    assert np.array_equal(k, np.sort(k))


# ---- scale: splitter quality + capacity rungs at P=128-512 -----------------


def _scale_drill(p: int, n_per_dev: int, seed: int):
    """Dryrun the host-side plan math at pod widths: oversampled
    splitters on zipf keys, the realized (P, P) histogram, then every
    valid host grouping's hier plan — no P-device backend involved."""
    rng = np.random.default_rng(seed)
    n = p * n_per_dev
    # Uniform keys isolate SAMPLING error — the thing that grows with P.
    # (Zipf's mass sits on a handful of duplicate values no splitter can
    # separate; its skew is exercised by the capacity drill below.)
    data = gen_uniform(n, dtype=np.int64, seed=seed)
    # SampleSort's splitter recipe, host-side: oversample 32x per worker,
    # equal-rank picks.
    sample = np.sort(rng.choice(data, size=32 * p, replace=False))
    splitters = sample[np.arange(1, p) * 32]
    shards = data.reshape(p, n_per_dev)
    hist = np.stack([
        np.bincount(np.searchsorted(splitters, shard, side="right"),
                    minlength=p)
        for shard in shards
    ])
    # Splitter quality: destination totals stay within a constant factor
    # of ideal balance even at pod width (BASELINE's oversample bound).
    totals = hist.sum(axis=0)
    assert totals.sum() == n
    assert totals.max() / (n / p) < 4.0
    # Capacity rungs: every plan cap is 8-aligned, covers its measured
    # max, and the ladder stays bounded (recompile-bound doctrine).
    caps = ring_caps(hist, n_per_dev, p)
    assert all(c % 8 == 0 for c in caps)
    assert len(set(caps)) <= len(ladder_rungs(n_per_dev)) + 1
    # Capacity coverage at width, on the realized hist AND a heavily
    # skewed synthetic one (zipf-weighted rows): every phase's cap covers
    # its measured max — the no-retry doctrine's precondition.
    skewed = _synthetic_hist(p, n_per_dev, seed=seed + 1)
    for h_src in (hist, skewed):
        h_caps = ring_caps(h_src, n_per_dev, p)
        for hosts in (h for h in (4, 8, 16) if p % h == 0 and p // h >= 2):
            plan = hier_plan(h_src, n_per_dev, p, hosts)
            d = p // hosts
            dev_host = h_src.reshape(p, hosts, d).sum(axis=2)
            host_dev = h_src.reshape(hosts, d, p).sum(axis=1)
            mat = host_matrix(h_src, hosts)
            assert plan.agg_cap >= dev_host.max()
            assert plan.scatter_cap >= host_dev.max()
            for shift in range(1, hosts):
                assert plan.leg_caps[shift] >= max(
                    mat[g, (g + shift) % hosts] for g in range(hosts)
                )
            # The DCN claim holds at width: aggregated host transfers
            # never exceed the flat ring's cross-host bytes, and under
            # skew they strictly beat them (the flat ring pads every
            # step to its diagonal max).
            dcn, _ = hier_wire_bytes(plan, 8)
            flat = ring_dcn_bytes(h_caps, 8, p, hosts)
            assert dcn <= flat
            if h_src is skewed:
                assert dcn < flat


@pytest.mark.parametrize("p", [128, 256])
def test_scale_splitters_and_caps(p):
    _scale_drill(p, n_per_dev=512, seed=p)


@pytest.mark.slow
def test_scale_splitters_and_caps_512():
    _scale_drill(512, n_per_dev=512, seed=512)


# ---- docs are part of the contract ------------------------------------------


def test_architecture_documents_hier_plane():
    """§17's contract is test-enforced like §7–§16: the three phases, the
    plan vocabulary, the fault contract and the registries all appear."""
    arch = open(os.path.join(REPO, "ARCHITECTURE.md"),
                encoding="utf-8").read()
    assert "## 17. Hierarchical exchange plane" in arch
    for term in ("resolve_hier_hosts", "HierPlan", "host_matrix",
                 "hier_plan", "ring_dcn_bytes", "`hier_reform`",
                 "hier_exchange_plan", "hier_exchange_leg",
                 "hier_exchanges", "dcn_bytes_on_wire",
                 "intra_host_bytes_on_wire", "dcn_bytes_saved",
                 "no-retry doctrine", "owner", "ring_caps"):
        assert term in arch, f"§17 must explain {term}"
