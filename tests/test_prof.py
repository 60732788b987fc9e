"""Introspection-plane tests (ISSUE 9, ARCHITECTURE §9).

Covers the three instruments and the satellites: the compile/cost/HBM
ledger (journal == live ledger == /metrics scrape, VariantCache entries
carried), skew & straggler attribution (skew_report fields, memwatch
watermarks), the journal-native analyzer (merged 2-process ground truth
with an injected-latency straggler; a REAL in-suite serve session), SLO
admission shedding with recovery, journal rotation + report stitching,
the analyzer's verdict on a real ring sort, and the §9 schema
enforcement.
"""

import json
import os
import time

import numpy as np
import pytest

from dsort_tpu.obs import (
    LEDGER,
    LEDGER_EVENT_FIELDS,
    MemWatch,
    Telemetry,
    VERDICT_KEYS,
    analyze_records,
    format_analysis,
    ledger_from_journal,
    parse_prometheus_text,
    variant_label,
)
from dsort_tpu.obs.merge import (
    group_rotated,
    merge_records,
    read_journal,
    read_journal_set,
    rotated_set,
)
from dsort_tpu.serve.admission import ADMISSION_REASONS
from dsort_tpu.utils.events import EVENT_TYPES, EventLog
from dsort_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile/cost/HBM ledger -------------------------------------------------


def test_variant_label_flattens_and_sanitizes():
    assert variant_label(("fused", 81920, "int32", "auto")) == (
        "fused|81920|int32|auto"
    )
    # Nested tuples (ring caps) flatten with '-'; characters the minimal
    # Prometheus parser would choke on (commas, spaces) become '_'.
    label = variant_label(("spmd_ring", 8, (16, 24), "a b,c"))
    assert label == "spmd_ring|8|16-24|a_b_c"
    assert "," not in label and " " not in label


def test_ledger_aggregates_and_journal_replay_matches():
    from dsort_tpu.obs.prof import CompileLedger

    led = CompileLedger()
    led.record(("fused", 64, "int32", "lax"), 0.25,
               cost=[{"flops": 100.0, "bytes accessed": 640.0}],
               mem=None)
    led.record(("fused", 64, "int32", "lax"), 0.15,
               cost={"flops": 100.0, "bytes accessed": 640.0}, mem=None)
    snap = led.snapshot()
    e = snap["fused|64|int32|lax"]
    assert e["compiles"] == 2 and e["compile_s"] == pytest.approx(0.40)
    assert e["flops"] == 100.0
    jl = EventLog()
    m = Metrics(journal=jl)
    assert led.drain_to(m) == 2
    assert led.pending() == 0
    # A metrics with no journal AND no taps must not swallow the queue.
    led.record(("x",), 0.1)
    assert led.drain_to(Metrics()) == 0 and led.pending() == 1
    replay = ledger_from_journal([ev.to_dict() for ev in jl.events()])
    assert replay == snap
    for field in LEDGER_EVENT_FIELDS:
        assert all(field in ev.fields for ev in jl.events()
                   if ev.type == "variant_compiled")


def test_instrumented_jit_times_real_compile(devices):
    import jax
    import jax.numpy as jnp

    from dsort_tpu.obs.prof import CompileLedger, LedgeredJit

    led = CompileLedger()
    fn = LedgeredJit(
        jax.jit(lambda x: jnp.sort(x)), lambda *a: ("t", a[0].shape[0]),
        ledger=led,
    )
    x = np.arange(4096, dtype=np.int32)[::-1].copy()
    out = np.asarray(fn(x))
    assert np.array_equal(out, np.sort(x))
    np.asarray(fn(x))  # repeat call: no second compile
    snap = led.snapshot()
    e = snap["t|4096"]
    assert e["compiles"] == 1
    assert e["compile_s"] > 0
    assert e["peak_hbm_bytes"] > 0
    assert e["output_hbm_bytes"] >= x.nbytes


def test_variant_cache_entries_carry_ledger_scrape_equals_journal(devices):
    """Acceptance: every VariantCache entry carries compile_s / flops /
    peak_hbm_bytes in BOTH the journal and a /metrics scrape, and the
    scrape equals the journal replay."""
    from dsort_tpu.models.pipelines import _fused_small_fn, pad_rung
    from dsort_tpu.serve.variants import VariantCache, fused_variant_key

    LEDGER.reset()
    _fused_small_fn.cache_clear()  # force fresh compiles into the ledger
    cache = VariantCache()
    jl = EventLog()
    m = Metrics(journal=jl)
    keys = set()
    for n in (1000, 5000, 1000):  # repeat size: cache hit, ONE compile
        key = fused_variant_key(n, "int32", "lax")
        keys.add(key)
        fn = cache.get_or_build(
            key,
            lambda n=n: _fused_small_fn(pad_rung(n), "int32", "lax"),
            metrics=m,
        )
        buf = np.zeros(pad_rung(n), np.int32)
        np.asarray(fn(buf, np.int32(n)))
    LEDGER.drain_to(m)
    records = [ev.to_dict() for ev in jl.events()]
    truth = ledger_from_journal(records)
    assert truth == LEDGER.snapshot()
    for key in keys:
        e = truth[variant_label(key)]
        assert e["compile_s"] > 0
        assert "flops" in e and e["flops"] >= 0
        assert e["peak_hbm_bytes"] > 0
    parsed = parse_prometheus_text(Telemetry().render_prometheus())
    for label, e in truth.items():
        lab = (("variant", label),)
        assert parsed[("dsort_variant_compile_seconds", lab)] == (
            pytest.approx(e["compile_s"], rel=1e-4)
        )
        assert parsed[("dsort_variant_compiles", lab)] == e["compiles"]
        assert parsed[("dsort_variant_flops", lab)] == (
            pytest.approx(e["flops"], rel=1e-4)
        )
        assert parsed[("dsort_variant_peak_hbm_bytes", lab)] == (
            pytest.approx(e["peak_hbm_bytes"], rel=1e-4)
        )


# -- skew & memwatch ---------------------------------------------------------


def test_skew_stats_fields_and_imbalance():
    from dsort_tpu.parallel.exchange import skew_stats

    hist = np.full((4, 4), 10, np.int32)
    # two sources both ship hot buckets to device 2: the RECEIVE side is
    # the concentrated one — device 2 is the predicted merge gate
    hist[1, 2] = 40
    hist[3, 2] = 40
    s = skew_stats(hist, 4)
    assert s["max_bucket"] == 40
    assert s["max_mean_ratio"] == pytest.approx(40 / hist.mean(), rel=1e-3)
    assert s["recv_argmax"] == 2
    assert s["recv_load"][2] == 100 and sum(s["send_load"]) == int(hist.sum())
    assert s["recv_imbalance"] > s["send_imbalance"] >= 1.0
    uniform = skew_stats(np.full((4, 4), 10, np.int32), 4)
    assert uniform["max_mean_ratio"] == 1.0


def test_memwatch_tap_emits_watermarks_at_phase_boundaries():
    snaps = iter(range(100))

    def fake_snapshot():
        return {"bytes_in_use": 1000 + next(snaps), "max_device_bytes": 500,
                "peak_bytes": 0, "devices": 2, "source": "fake"}

    jl = EventLog()
    m = Metrics(journal=jl)
    MemWatch(snapshot_fn=fake_snapshot).attach(m)
    from dsort_tpu.utils.metrics import PhaseTimer

    with PhaseTimer(m).phase("partition"):
        pass
    marks = [e for e in jl.events() if e.type == "hbm_watermark"]
    assert [e.fields["edge"] for e in marks] == ["start", "end"]
    assert all(e.fields["phase"] == "partition" for e in marks)
    assert m.counters["hbm_watermarks"] == 2
    # the tap never recurses into itself: exactly 2 watermarks, no more
    assert len(jl.events()) == 4  # phase_start/end + 2 watermarks


# -- journal-native analyzer -------------------------------------------------


def _proc_journal(wall_base, phases, jobs=(), tenant="default"):
    """Synthetic one-process journal mirroring the multihost emitters:
    clock_sync + phase spans + job boundaries on a private mono base."""
    mono = wall_base % 1000.0  # distinct mono base per process
    recs = [{"seq": 0, "t": wall_base, "mono": mono, "type": "clock_sync",
             "process": int(wall_base) % 7}]
    t = 0.01
    for job, n_keys in jobs:
        recs.append({"seq": len(recs), "t": wall_base + t, "mono": mono + t,
                     "type": "job_start", "job": job, "n_keys": n_keys,
                     "tenant": tenant})
    for phase, sec in phases:
        recs.append({"seq": len(recs), "t": wall_base + t, "mono": mono + t,
                     "type": "phase_start", "phase": phase})
        t += sec
        recs.append({"seq": len(recs), "t": wall_base + t, "mono": mono + t,
                     "type": "phase_end", "phase": phase,
                     "seconds": round(sec, 6)})
    for job, n_keys in jobs:
        recs.append({"seq": len(recs), "t": wall_base + t, "mono": mono + t,
                     "type": "job_done", "job": job, "n_keys": n_keys,
                     "counters": {"exchange_bytes_on_wire": 1 << 20}})
    return recs


def test_analyze_merged_multihost_names_straggler_and_critical_path():
    """Acceptance: a merged 2-process journal with an injected-latency
    straggler — the verdict names the straggler process and the
    critical-path phase, and the JSON matches journal ground truth."""
    fast = _proc_journal(
        1000.0, [("partition", 0.01), ("spmd_sort", 0.05), ("assemble", 0.01)],
        jobs=[(1, 1 << 20)],
    )
    slow = _proc_journal(  # the injected latency: 6x the spmd_sort phase
        1000.0, [("partition", 0.01), ("spmd_sort", 0.30), ("assemble", 0.01)],
        jobs=[(1, 1 << 20)],
    )
    merged = merge_records([fast, slow])
    v = analyze_records(merged)
    assert v["straggler"]["name"] == "p1"
    assert v["critical_src"] == "p1"
    assert v["critical_phase"] == "spmd_sort"
    assert v["dominant_phase"] == "spmd_sort"
    assert "spmd_sort" in v["straggler"]["phase_excess_s"]
    assert v["straggler"]["phase_excess_s"]["spmd_sort"] == (
        pytest.approx(0.25, abs=1e-6)
    )
    # JSON verdict == journal ground truth, independently derived.
    truth_phase = {}
    for r in merged:
        if r["type"] == "phase_end":
            truth_phase[(r["src"], r["phase"])] = (
                truth_phase.get((r["src"], r["phase"]), 0.0) + r["seconds"]
            )
    for (src, phase), sec in truth_phase.items():
        assert v["phases"][f"p{src}"][phase] == pytest.approx(sec)
    assert v["wire"]["bytes_on_wire"] == 2 * (1 << 20)
    assert v["splits"]["phase_wall_s"] == pytest.approx(
        sum(truth_phase.values())
    )
    # the verdict is JSON-able end to end (the --analyze-json contract)
    assert json.loads(json.dumps(v))["straggler"]["name"] == "p1"
    # and the human table names the same verdict
    table = format_analysis(v)
    assert "p1" in table and "spmd_sort" in table


def test_analyze_empty_and_wire_pricing():
    assert analyze_records([])["span_s"] is None
    recs = _proc_journal(5.0, [("spmd_sort", 0.1)], jobs=[(1, 10)])
    v = analyze_records(recs, link_bytes_per_s=1 << 20)
    assert v["wire"]["expected_transfer_s"] == pytest.approx(1.0)
    assert v["straggler"] is None  # one process: nothing to attribute


def test_analyze_real_serve_session_with_injected_latency(
    tmp_path, monkeypatch
):
    """Satellite: a REAL in-suite serve session with an injected-latency
    drill — --analyze names the injected dominant phase and the slowest
    job against journal ground truth (scrape==replay discipline)."""
    from dsort_tpu import cli
    from dsort_tpu.models import pipelines

    rng = np.random.default_rng(3)
    files, sizes = [], (1500, 4000, 1500)
    for i, n in enumerate(sizes):
        p = tmp_path / f"in{i}.txt"
        p.write_text("\n".join(str(x) for x in rng.integers(0, 10**6, n)))
        files.append(str(p))
    slow_rung = pipelines.pad_rung(4000)
    real = pipelines._fused_small_fn

    def injected(n_pad, dtype_str, kernel):
        fn = real(n_pad, dtype_str, kernel)
        if n_pad != slow_rung:
            return fn

        def slow(x, count):  # the latency lands INSIDE the local_sort phase
            time.sleep(0.25)
            return fn(x, count)

        return slow

    monkeypatch.setattr(pipelines, "_fused_small_fn", injected)
    feed = iter(files)
    monkeypatch.setattr(
        "builtins.input", lambda prompt="": next(feed, "exit")
    )
    journal = tmp_path / "serve.jsonl"
    rc = cli.main([
        "serve", "-o", str(tmp_path / "out.txt"), "--mode", "local",
        "--journal", str(journal), "--tenant", "acme",
    ])
    assert rc == 0
    records, skipped = read_journal(str(journal))
    assert skipped == 0
    v = analyze_records(records)
    # ground truth: the injected 0.25 s sleep dominates every other phase
    assert v["dominant_phase"] == "local_sort"
    assert v["critical_phase"] == "local_sort"
    assert v["critical_src"] == "p0"
    sj = v["slowest_job"]
    assert sj["n_keys"] == 4000 and sj["tenant"] == "acme"
    # verdict == journal ground truth for the phase waterfall
    truth = {}
    for r in records:
        if r["type"] == "phase_end":
            truth[r["phase"]] = truth.get(r["phase"], 0.0) + r["seconds"]
    for phase, sec in truth.items():
        assert v["phases"]["p0"][phase] == pytest.approx(sec)
    assert truth["local_sort"] > 0.25


def test_report_analyze_cli_writes_json(tmp_path, capsys):
    from dsort_tpu import cli

    path = tmp_path / "j.jsonl"
    with open(path, "w") as f:
        for r in _proc_journal(8.0, [("spmd_sort", 0.2)], jobs=[(1, 64)]):
            f.write(json.dumps(r) + "\n")
    vpath = tmp_path / "v.json"
    rc = cli.main([
        "report", str(path), "--analyze", "--analyze-json", str(vpath),
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "why-slow verdict" in out and "spmd_sort" in out
    v = json.loads(vpath.read_text())
    assert v["dominant_phase"] == "spmd_sort"
    assert set(VERDICT_KEYS) <= set(v)


# -- journal rotation (satellite) -------------------------------------------


def test_rotation_stitches_and_merge_keeps_sources(tmp_path):
    base = str(tmp_path / "a.jsonl")
    log = EventLog(rotate_bytes=300)
    for i in range(18):
        log.emit("probe", worker=i, ok=True)
        log.flush_jsonl(base)
    pieces = rotated_set(base)
    assert len(pieces) > 1, "the threshold must have rotated the journal"
    recs, skipped = read_journal_set(pieces)
    assert skipped == 0
    assert [r["seq"] for r in recs] == list(range(18))
    # pieces of ONE journal never masquerade as extra processes, even when
    # listed explicitly next to a second journal
    other = str(tmp_path / "b.jsonl")
    blog = EventLog()
    blog.emit("probe", worker=99, ok=True)
    blog.write_jsonl(other)
    groups = group_rotated([pieces[0], base, other])
    assert len(groups) == 2
    assert groups[0] == pieces and groups[1] == [other]
    journals = [read_journal_set(g)[0] for g in groups]
    merged = merge_records(journals)
    assert {r["src"] for r in merged} == {0, 1}
    assert sum(r["src"] == 0 for r in merged) == 18


def test_new_session_clears_stale_rotated_pieces(tmp_path):
    """A second session on the same journal path must not leave the first
    session's path.N pieces behind: the first flush's truncate-on-fresh
    guard covers the WHOLE rotated set, or `dsort report` would stitch a
    cross-session trace."""
    base = str(tmp_path / "s.jsonl")
    first = EventLog(rotate_bytes=250)
    for i in range(12):
        first.emit("probe", worker=i, ok=True)
        first.flush_jsonl(base)
    assert len(rotated_set(base)) > 2  # session 1 really rotated
    second = EventLog(rotate_bytes=250)
    second.emit("probe", worker=99, ok=True)
    second.flush_jsonl(base)
    recs, skipped = read_journal_set(rotated_set(base))
    assert skipped == 0
    assert [r["worker"] for r in recs] == [99]  # session 1 fully gone


def test_group_rotated_keeps_independent_dot_n_journals(tmp_path):
    """Per-rank journals named trace.0/trace.1 (no base file) are NOT a
    rotation set: each keeps its own merge group, so the multi-process
    clock alignment is never silently collapsed."""
    for i in range(2):
        log = EventLog()
        log.emit("probe", worker=i, ok=True)
        log.write_jsonl(str(tmp_path / f"trace.{i}"))
    groups = group_rotated([str(tmp_path / "trace.0"),
                            str(tmp_path / "trace.1")])
    assert groups == [[str(tmp_path / "trace.0")],
                      [str(tmp_path / "trace.1")]]
    # ... and a single .N arg does not vacuum its digit-suffixed siblings
    assert group_rotated([str(tmp_path / "trace.0")]) == [
        [str(tmp_path / "trace.0")]
    ]


def test_report_cli_stitches_rotated_set(tmp_path, capsys):
    from dsort_tpu import cli

    base = str(tmp_path / "s.jsonl")
    log = EventLog(rotate_bytes=250)
    for i in range(10):
        log.emit("probe", worker=i, ok=True)
        log.flush_jsonl(base)
    assert len(rotated_set(base)) > 1
    rc = cli.main(["report", base])
    out = capsys.readouterr().out
    assert rc == 0
    assert out.count("probe") == 10  # every rotated piece rendered, once


# -- SLO-driven admission shedding (satellite) ------------------------------


def _slow_runner(delay):
    def run(data, metrics, job_id=None):
        metrics.event("job_start", mode="runner", n_keys=len(data),
                      tenant="t")
        time.sleep(delay)
        metrics.event("job_done", n_keys=len(data),
                      counters=dict(metrics.counters))
        return np.sort(data)

    return run


def test_slo_shed_rejects_over_target_and_recovers_after_drain():
    from dsort_tpu.config import ServeConfig
    from dsort_tpu.serve import SortService

    tel = Telemetry()
    jl = EventLog()
    svc = SortService(
        runner=_slow_runner(0.06),
        serve=ServeConfig(slo_shed_ms=5.0),
        telemetry=tel, journal=jl,
    )
    data = np.arange(64, dtype=np.int32)
    tickets = []
    for _ in range(4):
        verdict, t = svc.submit(data, tenant="t")
        assert verdict.admitted
        tickets.append(t)
    shed = None
    for _ in range(300):
        verdict, t = svc.submit(data, tenant="t")
        if not verdict.admitted:
            shed = verdict
            break
        tickets.append(t)
        time.sleep(0.02)
    assert shed is not None and shed.reason == "slo_shed"
    for t in tickets:
        t.result(timeout=60)
    time.sleep(0.05)  # queue drained: the next submit must be ADMITTED
    verdict, t = svc.submit(data, tenant="t")
    assert verdict.admitted, verdict
    t.result(timeout=60)
    svc.shutdown()
    # verdict journaled + counted into the per-tenant admission series
    assert any(
        e.type == "job_rejected" and e.fields.get("reason") == "slo_shed"
        for e in jl.events()
    )
    assert tel.snapshot()["admissions"].get("t/slo_shed", 0) >= 1
    parsed = parse_prometheus_text(tel.render_prometheus())
    assert parsed[(
        "dsort_admissions_total",
        (("reason", "slo_shed"), ("tenant", "t")),
    )] >= 1


def test_slo_shed_config_validation_and_conf_key():
    from dsort_tpu.config import ConfigError, ServeConfig, SortConfig

    with pytest.raises(ConfigError, match="slo_shed_ms"):
        ServeConfig(slo_shed_ms=0)
    cfg = SortConfig.from_mapping({"SERVE_SLO_SHED_MS": "250"})
    assert cfg.serve.slo_shed_ms == 250.0
    assert SortConfig.from_mapping({}).serve.slo_shed_ms is None
    assert "slo_shed" in ADMISSION_REASONS


# -- the analyzer on a real ring sort -----------------------------------------


def _analyzed_ring_sort(dist):
    """A ring sort of 2^17 int64 keys with the journal and the memwatch
    tap attached, and the analyzer's verdict on that journal."""
    from dsort_tpu.config import JobConfig
    from dsort_tpu.data.ingest import gen_uniform, gen_zipf
    from dsort_tpu.parallel.mesh import local_device_mesh
    from dsort_tpu.parallel.sample_sort import SampleSort

    n = 1 << 17
    data = (gen_zipf(n, a=1.3, seed=4) if dist == "zipf"
            else gen_uniform(n, dtype=np.int64, seed=0))
    m = Metrics(journal=EventLog())
    MemWatch().attach(m)
    ss = SampleSort(local_device_mesh(8), JobConfig(key_dtype=np.int64))
    out = ss.sort(data, metrics=m, exchange="ring")
    np.testing.assert_array_equal(out, np.sort(data))
    return analyze_records([e.to_dict() for e in m.journal.events()])


@pytest.fixture(scope="module")
def uniform_skew_ratio():
    """The skew ratio the analyzer reads on uniform keys: the floor zipf
    keys must rise above."""
    return _analyzed_ring_sort("uniform")["skew"]["max_mean_ratio"]


@pytest.mark.parametrize("dist", ["uniform", "zipf"])
def test_analyzer_reads_skew_phase_and_hbm_of_a_ring_sort(devices, dist,
                                                          request):
    """The verdict on a real sort's journal names the device program as the
    dominant phase and a nonzero HBM watermark; uniform keys read a skew
    ratio of at least 1 and zipf keys a higher one."""
    verdict = _analyzed_ring_sort(dist)
    assert verdict["dominant_phase"] == "spmd_sort"
    assert verdict["hbm"]["bytes_in_use"] > 0
    skew = verdict["skew"]["max_mean_ratio"]
    if dist == "uniform":
        assert skew >= 1.0
    else:
        assert skew > request.getfixturevalue("uniform_skew_ratio")


# -- ARCHITECTURE §9 schema enforcement --------------------------------------


def test_architecture_documents_introspection_plane():
    """§9's contract is test-enforced like §7's bundle schema and §8's
    admission vocabulary: ledger fields, verdict keys, and the new event
    types all appear verbatim."""
    arch = open(
        os.path.join(REPO, "ARCHITECTURE.md"), encoding="utf-8"
    ).read()
    assert "## 9. Introspection plane" in arch
    for field in LEDGER_EVENT_FIELDS:
        assert f"`{field}`" in arch, f"ledger field {field} undocumented"
    for key in VERDICT_KEYS:
        assert f"`{key}`" in arch, f"verdict key {key} undocumented"
    for etype in ("variant_compiled", "skew_report", "hbm_watermark"):
        assert f"`{etype}`" in arch, f"event {etype} undocumented"
        assert etype in EVENT_TYPES
    for term in ("critical path", "straggler", "--analyze", "--memwatch",
                 "--journal-rotate-mb", "ladder-rung"):
        assert term in arch
