"""Closed-loop planner plane tests (ISSUE 16, ARCHITECTURE §15).

The acceptance bar: every automatic knob choice is a journaled, typed,
REPLAYABLE ``plan_decision`` — policy, chosen value, the measured inputs
it saw, the rejected alternatives — emitted BEFORE dispatch; explicit
flags always win (journaled ``plan_override``); ``--no-autotune`` makes
the planner vanish bit-identically; and `obs.analyze`'s ``plan`` verdict
replays every decision from the journal alone with zero mismatches.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from dsort_tpu.config import ConfigError, JobConfig, ServeConfig, SortConfig
from dsort_tpu.data.ingest import gen_uniform, gen_zipf
from dsort_tpu.obs.analyze import analyze_records, format_analysis
from dsort_tpu.obs.plan import (
    PLAN_DECISION_FIELDS,
    PLAN_OVERRIDE_FIELDS,
    PLAN_POLICIES,
    PREWARM_HISTORY,
    SKEW_RING_THRESHOLD,
    WAVE_MAX_ELEMS,
    WAVE_MIN_ELEMS,
    Planner,
    plan_ladder,
    plan_rung,
    plan_table,
    planned_exchange,
    planned_wave_elems,
    probe_skew,
    replay_decision,
    variant_key_label,
)
from dsort_tpu.utils.events import COUNTERS, EVENT_TYPES, EventLog
from dsort_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _metered():
    return Metrics(journal=EventLog())


def _records(journal):
    return [e.to_dict() for e in journal.events()]


# ---- registries + pure-twin math -------------------------------------------


def test_plan_events_and_counters_registered():
    for etype in ("plan_decision", "plan_override"):
        assert etype in EVENT_TYPES
    for counter in ("plan_decisions", "plan_overrides"):
        assert counter in COUNTERS
    assert PLAN_POLICIES == (
        "exchange", "wave_elems", "redundancy", "redundancy_mode",
        "prewarm", "dispatch_timeout_s", "slice_devices",
    )
    assert PLAN_DECISION_FIELDS == ("policy", "chosen", "inputs", "rejected")
    assert PLAN_OVERRIDE_FIELDS == ("policy", "explicit", "planned", "inputs")


def test_plan_rung_and_ladder_pinned_to_serving_twins():
    """The planner quantizes admissions with the SAME rung math the
    serving cache keys variants on — pinned against the jax-backed
    originals so the two can never drift."""
    from dsort_tpu.models.pipelines import pad_rung
    from dsort_tpu.parallel.exchange import ladder_rungs

    for n in (1, 7, 8, 9, 100, 3000, 3050, 9000, 16384, 16385,
              (1 << 20) - 1, 1 << 20, (1 << 22) + 17):
        assert plan_rung(n) == pad_rung(n), n
    for hi, lo in ((1 << 16, 8), (1 << 16, 1 << 14), (20000, 12000), (64, 8)):
        assert plan_ladder(hi, lo) == ladder_rungs(hi, lo=lo), (hi, lo)


def test_probe_skew_deterministic_and_separates_workloads():
    zipf = gen_zipf(1 << 17, a=1.3, seed=4)
    uni = gen_uniform(1 << 17, seed=0)
    a = probe_skew(zipf, 8)
    b = probe_skew(zipf, 8)
    assert a == b  # deterministic stride sample: same data, same inputs
    assert a["max_mean_ratio"] >= SKEW_RING_THRESHOLD
    assert a["num_workers"] == 8 and a["n_keys"] == len(zipf)
    u = probe_skew(uni, 8)
    assert u["max_mean_ratio"] < SKEW_RING_THRESHOLD
    # degenerate shapes answer neutrally rather than raising
    assert probe_skew(np.array([], dtype=np.int32), 8)["max_mean_ratio"] == 1.0
    assert probe_skew(uni, 1)["max_mean_ratio"] == 1.0


# ---- pure policies (decision == f(inputs)) ---------------------------------


def test_exchange_policy_decisions():
    # skewed + no TPU -> ring; skewed + TPU -> fused
    chosen, rejected = replay_decision(
        "exchange", {"max_mean_ratio": 3.2, "num_workers": 8}
    )
    assert chosen == "ring"
    assert {r["value"] for r in rejected} == {"alltoall", "fused"}
    chosen, _ = replay_decision(
        "exchange",
        {"max_mean_ratio": 3.2, "num_workers": 8, "fused_ok": True},
    )
    assert chosen == "fused"
    # uniform -> alltoall; replica plane -> ring regardless of skew
    chosen, _ = replay_decision(
        "exchange", {"max_mean_ratio": 1.1, "num_workers": 8}
    )
    assert chosen == "alltoall"
    chosen, _ = replay_decision(
        "exchange",
        {"max_mean_ratio": 1.1, "num_workers": 8, "redundancy": 2},
    )
    assert chosen == "ring"
    # one worker: every schedule is the same program
    assert replay_decision(
        "exchange", {"max_mean_ratio": 9.9, "num_workers": 1}
    )[0] == "alltoall"


def test_wave_policy_decisions():
    # no device stats (cpu backend): keep the hand-set size, say why
    chosen, rejected = replay_decision(
        "wave_elems", {"current": 1 << 20, "itemsize": 4}
    )
    assert chosen == 1 << 20
    assert rejected and "keeping wave_elems" in rejected[0]["reason"]
    # measured watermark: budget / per-elem bytes, floored to a pow2
    chosen, _ = replay_decision("wave_elems", {
        "current": 1 << 20, "itemsize": 4,
        "max_device_bytes": 1 << 30, "peak_bytes": 32 << 20,
    })
    assert chosen & (chosen - 1) == 0  # a power of two
    assert WAVE_MIN_ELEMS <= chosen <= WAVE_MAX_ELEMS
    per_elem = (32 << 20) / (1 << 20)
    assert chosen * per_elem <= (1 << 30) * 0.6  # inside the budget
    assert chosen * 2 * per_elem > (1 << 30) * 0.6  # maximal pow2
    # clamps hold at the extremes
    assert replay_decision("wave_elems", {
        "current": 1 << 20, "itemsize": 8,
        "max_device_bytes": 1 << 16, "peak_bytes": 1 << 15,
    })[0] == WAVE_MIN_ELEMS
    assert replay_decision("wave_elems", {
        "current": 1 << 20, "itemsize": 4,
        "max_device_bytes": 1 << 45, "peak_bytes": 0,
    })[0] == WAVE_MAX_ELEMS


def test_redundancy_policy_decisions():
    # no signal at all: keep the current posture
    assert replay_decision("redundancy", {"current": 1})[0] == 1
    assert replay_decision("redundancy", {"current": 2})[0] == 2
    # any observed loss buys a replica
    chosen, rejected = replay_decision(
        "redundancy", {"loss_events": 1, "agents": 2, "degraded": 0}
    )
    assert chosen == 2
    assert {r["value"] for r in rejected} == {1, 3}
    # a quarter of the fleet degraded buys one too
    assert replay_decision(
        "redundancy", {"agents": 4, "degraded": 1, "loss_events": 0}
    )[0] == 2
    # healthy fleet: r=1, with the premium named in the rejection
    chosen, rejected = replay_decision(
        "redundancy", {"agents": 4, "degraded": 0, "loss_events": 0}
    )
    assert chosen == 1
    assert rejected[0]["value"] == 2


def test_prewarm_policy_decisions():
    ladder = [12288, 14336, 16384]
    # cold start: the exhaustive ladder is the only honest warm set
    chosen, rejected = replay_decision(
        "prewarm", {"history": [], "ladder": ladder, "dtype": "int32"}
    )
    assert chosen == [variant_key_label(r, "int32") for r in ladder]
    assert rejected == []
    # history: the admission mix ranks the set, the rest is rejected
    hist = ["14336:int32"] * 5 + ["16384:int64"] * 2
    chosen, rejected = replay_decision("prewarm", {
        "history": hist, "ladder": ladder, "dtype": "int32", "limit": 2,
    })
    assert chosen == sorted(["14336:int32", "16384:int64"])
    assert {r["value"] for r in rejected} == {"12288:int32", "16384:int32"}


def test_replay_decision_unknown_policy_raises():
    with pytest.raises(ValueError, match="unknown plan policy"):
        replay_decision("mystery", {})


# ---- decision emission + schema --------------------------------------------


def test_decide_journals_schema_and_bumps_counter():
    m = _metered()
    p = Planner(job=JobConfig(autotune=True))
    chosen = p.decide(
        "exchange", {"max_mean_ratio": 3.0, "num_workers": 8}, m
    )
    assert chosen == "ring"
    (ev,) = [e for e in m.journal.events() if e.type == "plan_decision"]
    # every Metrics event also stamps the `job` ordinal; the typed schema
    # is exactly PLAN_DECISION_FIELDS on top of that
    assert set(ev.fields) - {"job"} == set(PLAN_DECISION_FIELDS)
    assert ev.fields["chosen"] == "ring"
    assert ev.fields["inputs"]["max_mean_ratio"] == 3.0
    assert all({"value", "reason"} <= set(r) for r in ev.fields["rejected"])
    assert m.counters["plan_decisions"] == 1
    # the journaled inputs alone reproduce the choice (the replay seam)
    assert replay_decision("exchange", ev.fields["inputs"])[0] == "ring"


def test_note_override_journals_planned_value():
    m = _metered()
    p = Planner(job=JobConfig(autotune=True, exchange="alltoall",
                              explicit=("exchange",)))
    got = p.resolve(
        "exchange", {"max_mean_ratio": 3.0, "num_workers": 8}, m
    )
    assert got == "alltoall"  # the explicit flag won
    (ev,) = [e for e in m.journal.events() if e.type == "plan_override"]
    assert set(ev.fields) - {"job"} == set(PLAN_OVERRIDE_FIELDS)
    assert ev.fields["explicit"] == "alltoall"
    assert ev.fields["planned"] == "ring"  # what the planner would have done
    assert m.counters["plan_overrides"] == 1
    assert not [e for e in m.journal.events() if e.type == "plan_decision"]


def test_resolve_precedence_call_beats_config_beats_planner():
    m = _metered()
    p = Planner(job=JobConfig(autotune=True, exchange="alltoall",
                              explicit=("exchange",)))
    inputs = {"max_mean_ratio": 3.0, "num_workers": 8}
    assert p.resolve("exchange", inputs, m, call_value="fused") == "fused"
    assert p.resolve("exchange", inputs, m) == "alltoall"
    off = Planner(job=JobConfig())  # autotune off: planner is inert
    assert off.resolve("exchange", inputs, m) is None
    assert off.resolve("exchange", inputs, m, call_value="ring") == "ring"
    # inert means inert: the off-planner journaled nothing
    types = [e.type for e in m.journal.events()]
    assert types.count("plan_override") == 2  # both from the ON planner


# ---- rolling state: live == journal replay ---------------------------------


def test_planner_live_state_equals_journal_replay():
    m = _metered()
    p = Planner(job=JobConfig(autotune=True))
    p.attach(m)
    m.event("job_admitted", tenant="a", queue_depth=1, n_keys=3050,
            dtype="int32")
    m.event("job_admitted", tenant="a", queue_depth=1, n_keys=14000,
            dtype="int64")
    m.event("hbm_watermark", phase="exchange", bytes_in_use=123456,
            max_device_bytes=1 << 30, device=0)
    m.event("worker_dead", worker=3)
    m.event("job_rerouted", job_id="j1", frm="a0", to="a1",
            reason="agent_lost")
    m.event("job_rerouted", job_id="j2", frm="a1", to="a0",
            reason="dispatch_failed")  # NOT a loss signal
    m.event("health_verdict", agent="a0", score=2.5, degraded=True)
    st = p.state_dict()
    assert st["admissions"] == [
        variant_key_label(plan_rung(3050), "int32"),
        variant_key_label(plan_rung(14000), "int64"),
    ]
    assert st["hbm_peak"] == 123456
    assert st["max_device_bytes"] == 1 << 30
    assert st["loss_events"] == 2  # worker_dead + agent_lost reroute only
    assert st["degraded"] == {"a0": True}
    # THE pin: a fold over the journal records rebuilds the live state
    assert Planner.replay(_records(m.journal)).state_dict() == st


def test_prewarm_history_is_bounded():
    p = Planner()
    for i in range(PREWARM_HISTORY + 40):
        p.observe("job_admitted", {"n_keys": 3050 + i, "dtype": "int32"})
    assert len(p.state_dict()["admissions"]) == PREWARM_HISTORY


# ---- the sample_sort seam (mesh) -------------------------------------------


@pytest.mark.parametrize("workload,expected", [
    ("zipf_int64", "ring"), ("uniform_int32", "alltoall"),
])
def test_autotune_picks_ring_on_zipf_alltoall_on_uniform(mesh8, workload,
                                                        expected):
    """The exchange policy end to end: the planner's measured probe picks
    ring for the skewed workload and alltoall for the uniform one, the
    dispatch journals ONE plan_decision, and the sorted output is
    bit-identical to the unplanned path under either hand-set schedule."""
    from dsort_tpu.parallel.sample_sort import SampleSort

    if workload == "zipf_int64":
        keys = gen_zipf(1 << 17, a=1.3, seed=4)
        job = JobConfig(key_dtype=np.int64)
    else:
        keys, job = gen_uniform(1 << 17, seed=0), JobConfig()
    m = _metered()
    auto = SampleSort(mesh8, dataclasses.replace(job, autotune=True))
    out = auto.sort(keys, metrics=m)
    plans = [e for e in m.journal.events() if e.type == "plan_decision"]
    assert [p.fields["policy"] for p in plans] == ["exchange"]
    assert plans[0].fields["chosen"] == expected
    # the decision's measured input is the probe of THIS job's keys
    skewed = plans[0].fields["inputs"]["max_mean_ratio"] >= SKEW_RING_THRESHOLD
    assert skewed == (expected == "ring")
    np.testing.assert_array_equal(out, np.sort(keys))
    # bit-identical to the unplanned path (--no-autotune A/B), whichever
    # schedule is set by hand
    plain = SampleSort(mesh8, job)
    for exchange in ("alltoall", "ring"):
        np.testing.assert_array_equal(out, plain.sort(keys, exchange=exchange))


def test_autotune_per_call_exchange_journals_override(mesh8):
    from dsort_tpu.parallel.sample_sort import SampleSort

    zipf = gen_zipf(1 << 16, a=1.3, seed=4)
    m = _metered()
    ss = SampleSort(mesh8, JobConfig(autotune=True, key_dtype=np.int64))
    out = ss.sort(zipf, metrics=m, exchange="alltoall")
    np.testing.assert_array_equal(out, np.sort(zipf))
    (ov,) = [e for e in m.journal.events() if e.type == "plan_override"]
    assert ov.fields["policy"] == "exchange"
    assert ov.fields["explicit"] == "alltoall"
    assert ov.fields["planned"] == "ring"  # skewed: the planner disagreed
    assert not [e for e in m.journal.events() if e.type == "plan_decision"]


def test_autotune_off_journals_nothing(mesh8):
    from dsort_tpu.parallel.sample_sort import SampleSort

    m = _metered()
    SampleSort(mesh8, JobConfig()).sort(gen_uniform(1 << 14, seed=1),
                                        metrics=m)
    types = [e.type for e in m.journal.events()]
    assert "plan_decision" not in types and "plan_override" not in types


def test_planned_exchange_respects_redundancy(mesh8):
    """A resolved redundancy > 1 reaches the policy as a measured input:
    the planner picks ring BECAUSE of the replica plane, and the journal
    says so."""
    from dsort_tpu.parallel.sample_sort import SampleSort

    uni = gen_uniform(1 << 14, seed=2)
    m = _metered()
    ss = SampleSort(mesh8, JobConfig(autotune=True, redundancy=2))
    out = ss.sort(uni, metrics=m)
    np.testing.assert_array_equal(out, np.sort(uni))
    (plan,) = [e for e in m.journal.events() if e.type == "plan_decision"]
    assert plan.fields["chosen"] == "ring"
    assert plan.fields["inputs"]["redundancy"] == 2


# ---- the wave seam ----------------------------------------------------------


def test_planned_wave_elems_reads_hbm_ledger():
    job = JobConfig(autotune=True)
    records = [
        {"type": "hbm_watermark", "seq": 0, "t": 0.0, "mono": 0.0,
         "phase": "exchange", "bytes_in_use": 32 << 20,
         "max_device_bytes": 1 << 30, "device": 0},
    ]
    m = _metered()
    chosen = planned_wave_elems(job, 1 << 20, 4, records, m)
    (ev,) = [e for e in m.journal.events() if e.type == "plan_decision"]
    assert ev.fields["policy"] == "wave_elems"
    assert ev.fields["chosen"] == chosen
    # the decision's inputs carry the ledger's ground truth verbatim
    assert ev.fields["inputs"]["peak_bytes"] == 32 << 20
    assert ev.fields["inputs"]["max_device_bytes"] == 1 << 30
    assert replay_decision("wave_elems", ev.fields["inputs"])[0] == chosen
    # autotune off: the seam is a pass-through, nothing journaled
    m2 = _metered()
    assert planned_wave_elems(JobConfig(), 1 << 20, 4, records, m2) == 1 << 20
    assert len(m2.journal) == 0
    # explicit wave_elems: the hand-set size wins, override journaled
    m3 = _metered()
    exp = JobConfig(autotune=True, explicit=("wave_elems",))
    assert planned_wave_elems(exp, 1 << 20, 4, records, m3) == 1 << 20
    (ov,) = [e for e in m3.journal.events() if e.type == "plan_override"]
    assert ov.fields["policy"] == "wave_elems"


# ---- the fleet redundancy seam ----------------------------------------------


def test_fleet_controller_plans_redundancy_from_loss_signal():
    from dsort_tpu.fleet.controller import FleetController, FleetTicket, _Job

    journal = EventLog()
    # one unreachable agent: the connect fails fast and is survived; with
    # start=False no dispatch/heartbeat threads ever run
    ctl = FleetController(agents=[("127.0.0.1", 1)], start=False,
                          journal=journal, autotune=True)
    job = _Job("j1", "acme", 100, "int32", None,
               FleetTicket("j1", "acme", 100, Metrics(journal=journal)))
    # healthy, no history: keep r=1 (no stamp semantics live in the value)
    assert ctl._plan_redundancy(job) == 1
    # an agent lost with work on it: the controller's own journal signal
    ctl._svc_metrics.event("job_rerouted", job_id="x", frm="a0", to="a1",
                           reason="agent_lost")
    assert ctl._plan_redundancy(job) == 2
    decisions = [e for e in journal.events() if e.type == "plan_decision"]
    assert [d.fields["chosen"] for d in decisions] == [1, 2]
    assert decisions[1].fields["inputs"]["loss_events"] == 1
    # every decision replays from its own journaled inputs
    for d in decisions:
        assert replay_decision("redundancy", d.fields["inputs"])[0] == \
            d.fields["chosen"]
    ctl.shutdown()


def test_fleet_controller_explicit_redundancy_overrides():
    from dsort_tpu.fleet.controller import FleetController, FleetTicket, _Job

    journal = EventLog()
    ctl = FleetController(agents=[("127.0.0.1", 1)], start=False,
                          journal=journal, autotune=True, redundancy=2)
    job = _Job("j1", "acme", 100, "int32", None,
               FleetTicket("j1", "acme", 100, Metrics(journal=journal)))
    assert ctl._plan_redundancy(job) == 2
    (ov,) = [e for e in journal.events() if e.type == "plan_override"]
    assert ov.fields["policy"] == "redundancy"
    assert ov.fields["explicit"] == 2
    assert ov.fields["planned"] == 2  # current posture, no signal: keep
    # autotune OFF forwards the explicit value silently (no planner plane)
    ctl2 = FleetController(agents=[("127.0.0.1", 1)], start=False,
                           autotune=False, redundancy=3)
    assert ctl2._plan_redundancy(job) == 3
    ctl.shutdown()
    ctl2.shutdown()


def test_service_submit_redundancy_reaches_exchange(devices):
    """The dispatch-header plumb: a per-job ``redundancy`` override rides
    submit -> ticket -> scheduler, and the coded replica plane runs."""
    from dsort_tpu.serve import SortService

    journal = EventLog()
    svc = SortService(
        job=JobConfig(settle_delay_s=0.01),
        serve=ServeConfig(small_job_max=1, max_queue_depth=16,
                          max_tenant_inflight=16),
        journal=journal,
    )
    d = gen_uniform(1 << 14, seed=3)
    _, t = svc.submit(d, redundancy=2)
    np.testing.assert_array_equal(t.result(120), np.sort(d))
    svc.shutdown(drain=True)
    assert "coded_replica_ship" in [e.type for e in journal.events()]


# ---- the prewarm seam --------------------------------------------------------


def _prewarm_svc(journal, policy="auto"):
    from dsort_tpu.serve import SortService

    return SortService(
        job=JobConfig(settle_delay_s=0.01),
        serve=ServeConfig(max_queue_depth=32, max_tenant_inflight=32,
                          prewarm_policy=policy,
                          prewarm_min_keys=12000, prewarm_max_keys=20000),
        journal=journal,
    )


def test_prewarm_auto_predicts_from_admission_mix(devices):
    journal = EventLog()
    svc = _prewarm_svc(journal)
    rng = np.random.default_rng(6)
    for _ in range(3):
        d = rng.integers(0, 1000, 14000, dtype=np.int32)
        svc.submit(d)[1].result(120)
    # the admission mix is all 14336:int32 -> predict exactly that rung,
    # which the traffic itself already compiled: ZERO fresh compiles,
    # where `--prewarm all` would still build the 2 cold rungs
    assert svc.prewarm() == 0
    (plan,) = [e for e in journal.events() if e.type == "plan_decision"
               and e.fields["policy"] == "prewarm"]
    assert plan.fields["chosen"] == [variant_key_label(plan_rung(14000),
                                                       "int32")]
    # the decision's history input IS the journal's admission stream
    admitted = [variant_key_label(plan_rung(e.fields["n_keys"]),
                                  e.fields["dtype"])
                for e in journal.events() if e.type == "job_admitted"]
    assert plan.fields["inputs"]["history"] == admitted
    assert replay_decision("prewarm", plan.fields["inputs"])[0] == \
        plan.fields["chosen"]
    svc.shutdown(drain=True)


def test_prewarm_auto_cold_start_compiles_full_ladder(devices):
    from dsort_tpu.parallel.exchange import ladder_rungs

    journal = EventLog()
    svc = _prewarm_svc(journal)
    ladder = ladder_rungs(20000, lo=12000)
    assert svc.prewarm() == len(ladder)  # no history: the honest warm set
    (plan,) = [e for e in journal.events() if e.type == "plan_decision"]
    assert plan.fields["chosen"] == [variant_key_label(r, "int32")
                                     for r in ladder]
    svc.shutdown(drain=True)


def test_prewarm_all_keeps_exhaustive_ladder(devices):
    from dsort_tpu.parallel.exchange import ladder_rungs

    journal = EventLog()
    svc = _prewarm_svc(journal, policy="all")
    rng = np.random.default_rng(7)
    svc.submit(rng.integers(0, 1000, 14000, dtype=np.int32))[1].result(120)
    # exhaustive: every rung the traffic did NOT already warm gets built
    assert svc.prewarm() == len(ladder_rungs(20000, lo=12000)) - 1
    # the old exhaustive behavior journals NO plan_decision: nothing was
    # predicted, the operator asked for everything
    assert not [e for e in journal.events() if e.type == "plan_decision"]
    svc.shutdown(drain=True)


# ---- tri-state config / CLI precedence --------------------------------------


def test_jobconfig_explicit_tristate():
    assert JobConfig().explicit == ()
    assert not JobConfig().autotune  # library default: OFF
    job = JobConfig(explicit=("exchange", "redundancy"))
    assert job.is_explicit("exchange") and not job.is_explicit("prewarm")
    # lists normalize; non-string knob names are a config error
    assert JobConfig(explicit=["exchange"]).explicit == ("exchange",)
    with pytest.raises(ConfigError, match="explicit"):
        JobConfig(explicit=(42,))


def test_conf_file_values_are_explicit():
    cfg = SortConfig.from_mapping({"EXCHANGE": "ring", "AUTOTUNE": "1"})
    assert cfg.job.autotune
    assert cfg.job.is_explicit("exchange")
    assert not cfg.job.is_explicit("redundancy")
    cfg2 = SortConfig.from_mapping({"SERVE_PREWARM": "all"})
    assert cfg2.serve.prewarm and cfg2.serve.prewarm_policy == "all"
    assert cfg2.job.is_explicit("prewarm")
    assert not SortConfig.from_mapping({"AUTOTUNE": "0"}).job.autotune


def test_cli_load_config_autotune_precedence(tmp_path):
    import argparse

    from dsort_tpu.cli import _load_config

    def ns(**kw):
        base = dict(conf=None, workers=None, dtype=None, kernel=None,
                    merge_kernel=None, exchange=None, redundancy=None,
                    checkpoint_dir=None, tenant=None, flight_dir=None,
                    no_autotune=False, prewarm=None)
        base.update(kw)
        return argparse.Namespace(**base)

    # CLI default: the closed loop is ON
    assert _load_config(ns()).job.autotune
    # --no-autotune wins over everything
    assert not _load_config(ns(no_autotune=True)).job.autotune
    # an explicit conf AUTOTUNE= is respected (no CLI re-default)
    conf = tmp_path / "dsort.conf"
    conf.write_text("AUTOTUNE=0\n")
    assert not _load_config(ns(conf=str(conf))).job.autotune
    # a knob flag joins the explicit set so the planner yields to it
    cfg = _load_config(ns(exchange="ring", redundancy=2))
    assert cfg.job.autotune
    assert cfg.job.is_explicit("exchange")
    assert cfg.job.is_explicit("redundancy")
    assert _load_config(ns(prewarm="all")).job.is_explicit("prewarm")


# ---- the audit drill: journal -> plan verdict -> replay ---------------------


def test_analyze_plan_verdict_replays_decisions(mesh8):
    """The §15 audit drill: a zipf job and a uniform job with autotune
    on; the ``plan`` verdict replays every decision from its journaled
    inputs with ZERO mismatches, the zipf decision is ring, and the
    decision's measured skew agrees with the ring plan's own
    ``skew_report`` ground truth from the SAME journal."""
    from dsort_tpu.parallel.sample_sort import SampleSort

    zipf = gen_zipf(1 << 17, a=1.3, seed=4)
    uni = gen_uniform(1 << 17, seed=0)
    m = _metered()
    SampleSort(mesh8, JobConfig(autotune=True, key_dtype=np.int64)).sort(
        zipf, metrics=m
    )
    SampleSort(mesh8, JobConfig(autotune=True)).sort(uni, metrics=m)
    recs = _records(m.journal)
    v = analyze_records(recs)["plan"]
    assert v["decisions"] == 2 and v["mismatches"] == 0
    assert v["overrides"] == 0 and v["by_policy"] == {"exchange": 2}
    ring_dec = next(d for d in v["replayed"] if d["chosen"] == "ring")
    assert ring_dec["match"] is True
    # ground truth: the chosen ring plan journaled its EXACT histogram
    # skew; the probe's sampled estimate must sit on the same side of the
    # threshold and in the same ballpark
    (skew_ev,) = [r for r in recs if r["type"] == "skew_report"]
    exact = skew_ev["max_mean_ratio"]
    probed = ring_dec["inputs"]["max_mean_ratio"]
    assert exact >= SKEW_RING_THRESHOLD and probed >= SKEW_RING_THRESHOLD
    assert 0.5 <= probed / exact <= 2.0
    # the human table renders the audit trail
    txt = format_analysis(analyze_records(recs))
    assert "planner decisions (replayed from journaled inputs):" in txt
    assert "2 decision(s)" in txt and "0 replay mismatch(es)" in txt


def test_analyze_plan_verdict_flags_tampered_inputs():
    """A decision whose journaled inputs do NOT reproduce its chosen
    value is an audit failure — mismatches counts it."""
    m = _metered()
    Planner(job=JobConfig(autotune=True)).decide(
        "exchange", {"max_mean_ratio": 3.0, "num_workers": 8}, m
    )
    recs = _records(m.journal)
    for r in recs:
        if r["type"] == "plan_decision":
            r["inputs"] = {"max_mean_ratio": 1.0, "num_workers": 8}
    v = analyze_records(recs)["plan"]
    assert v["mismatches"] == 1
    assert v["replayed"][0]["match"] is False


def test_planner_counters_reach_metrics_and_top():
    from dsort_tpu.obs import Telemetry
    from dsort_tpu.obs.telemetry import parse_prometheus_text
    from dsort_tpu.obs.top import render_top

    tel = Telemetry()
    m = _metered()
    tel.attach(m)
    p = Planner(job=JobConfig(autotune=True))
    p.decide("exchange", {"max_mean_ratio": 3.0, "num_workers": 8}, m)
    p.decide("exchange", {"max_mean_ratio": 1.0, "num_workers": 8}, m)
    p.note_override("redundancy", 2, {"current": 1}, m)
    scrape = parse_prometheus_text(tel.render_prometheus())
    assert scrape[("dsort_plan_decisions",
                   (("policy", "exchange"),))] == 2
    assert scrape[("dsort_plan_overrides",
                   (("policy", "redundancy"),))] == 1
    assert scrape[("dsort_plan_info", tuple(sorted({
        "policy": "exchange", "chosen": "alltoall",
    }.items())))] == 1
    out = render_top(scrape)
    assert "planner:" in out
    assert "exchange" in out and "alltoall" in out
    # the pane and the report renderer share plan_table (no-drift)
    assert plan_table([("exchange", 2, 0, "alltoall")]).splitlines()[1] \
        in out


def test_plan_table_renders_lists_and_empty():
    assert "(no planner decisions)" in plan_table([])
    txt = plan_table([("prewarm", 1, 0, ["a", "b", "c"])])
    assert "[3 key(s)]" in txt


# ---- CLI A/B ---------------------------------------------------------------


def test_cli_no_autotune_ab_bit_identical(tmp_path):
    """The escape hatch: the same input through ``dsort run`` with the
    planner on (the CLI default) and with ``--no-autotune`` produces
    byte-identical output files; only the planned run journals plan
    events."""
    from dsort_tpu import cli

    zipf = gen_zipf(20_000, a=1.3, seed=9, dtype=np.int32)
    inp = tmp_path / "in.txt"
    np.savetxt(inp, zipf, fmt="%d")
    out_a, out_b = tmp_path / "a.txt", tmp_path / "b.txt"
    j_a = tmp_path / "a.jsonl"
    # force the exchange plane (redundancy=2 skips the fused small-job
    # shortcut) so the planned run actually plans
    assert cli.main(["run", str(inp), "--redundancy", "2",
                     "--journal", str(j_a), "-o", str(out_a)]) == 0
    assert cli.main(["run", str(inp), "--redundancy", "2", "--no-autotune",
                     "-o", str(out_b)]) == 0
    assert out_a.read_bytes() == out_b.read_bytes()
    recs = [json.loads(ln) for ln in open(j_a)]
    plans = [r for r in recs if r["type"] == "plan_decision"]
    (exc,) = [p for p in plans if p["policy"] == "exchange"]
    assert exc["chosen"] == "ring"  # redundancy=2: the replica plane
    assert exc["inputs"]["redundancy"] == 2
    # --redundancy was explicit -> it cannot have been planner-chosen,
    # and the analyze verdict replays clean
    v = analyze_records(recs)["plan"]
    assert v["mismatches"] == 0


# ---- docs are part of the contract ------------------------------------------


def test_architecture_documents_planner_plane():
    """§15's contract is test-enforced like §7–§14: the policy catalog,
    both event schemas verbatim, the precedence order, the replay
    contract and the escape hatch."""
    arch = open(os.path.join(REPO, "ARCHITECTURE.md"),
                encoding="utf-8").read()
    assert "## 15. Planner plane" in arch
    for policy in PLAN_POLICIES:
        assert f"`{policy}`" in arch, f"policy {policy} undocumented"
    for field in PLAN_DECISION_FIELDS + PLAN_OVERRIDE_FIELDS:
        assert f"`{field}`" in arch, f"schema field {field} undocumented"
    for etype in ("plan_decision", "plan_override"):
        assert f"`{etype}`" in arch
    for term in ("SKEW_RING_THRESHOLD", "WAVE_HBM_BUDGET_FRAC",
                 "REDUNDANCY_DEGRADED_FRAC", "PREWARM_HISTORY",
                 "replay_decision", "--no-autotune", "AUTOTUNE",
                 "explicit flag > conf file > planner",
                 "--prewarm all"):
        assert term in arch, f"§15 must explain {term}"
