"""Journal-native why-slow analysis: ``dsort report --analyze``.

The journal already records everything a performance verdict needs — phase
spans, job boundaries, queue waits, compile costs (`obs.prof`), skew
reports (`parallel.exchange`), HBM watermarks, wire-byte counters.  This
module replays any journal (single-process or a ``--merge``\\ d multi-host
trace, `obs.merge`) into one structured verdict:

- **phase waterfall + critical path**: per-(process, phase) wall seconds;
  the *critical process* is the one whose last event gates completion of
  the whole span, its largest phase is the *critical phase*, and the
  critical path lists that process's phases by wall share — "which host
  and which phase did the fleet wait on".
- **straggler attribution**: with >= 2 sources, each process's busy time
  (summed phase seconds) is scored against the fleet mean; the max score
  names the straggler, and ``phase_excess_s`` says which phases it lost
  the time in relative to its peers.
- **queue-wait vs execute vs compile split**: queue waits from
  ``job_dequeued`` (the serving layer's measured wait), compile seconds
  from ``variant_compiled``, execute = phase wall minus compile (compiles
  land inside the dispatching phase, so the subtraction attributes them).
- **wire**: bytes the exchange put on the wire (final ``job_done``
  counters) and — when the caller supplies a measured link bandwidth —
  the seconds those bytes *should* have cost.
- **skew**: the worst ``skew_report`` (max/mean bucket ratio + the
  predicted overloaded device).
- **hbm**: the high-water ``hbm_watermark`` and the phase it landed in.
- **recovery**: the failure-posture split (ARCHITECTURE §14) — what the
  session's recoveries COST: the coded-local side (``coded_recover``
  events: recoveries, keys reconstructed from replica slots, replica
  bytes consumed, recovery wall seconds) vs the re-run side (mesh
  re-forms, evictions, keys re-sorted by any resume/repair path), with
  ``path`` naming which posture the session actually took.
- **waves**: out-of-core wave jobs (`models.wave_sort`) — per-wave spans
  from ``wave_start``/``wave_done`` pairs, which wave GATED completion
  (latest ``wave_done``), the slowest wave, and the run-granular resume
  cost (``wave_resume`` missing-run totals).  The wave phases themselves
  (``wave_read``/``wave_sort``/``wave_exchange``/``wave_spill``/``merge``)
  land in the ordinary phase waterfall.
- **plan**: the planner audit (ARCHITECTURE §15) — every ``plan_decision``
  REPLAYED through its pure policy from the journaled inputs
  (`obs.plan.replay_decision`); ``mismatches`` counts decisions whose
  replay disagrees with what the live planner chose (pinned at 0: a
  decision that cannot be reproduced from its recorded inputs is an audit
  failure), plus the ``plan_override`` trail of explicit values that won.
- **conformance**: the trace-contract verdict (ARCHITECTURE §16) — the
  journal replayed against the declared `TRACE_CONTRACTS` grammars
  (`analysis.spec.contracts`): scoped traces checked, and every
  violation named by contract, scope, and the offending event sequence.
  The same engine serves ``dsort report --conform`` and the drill tests'
  ``assert_conformant``.

Every figure is derived from the records alone — the same replay
discipline as `obs.slo`: analyzing a journal twice, or a scrape and a
replay of the same session, must agree exactly.
"""

from __future__ import annotations

from dsort_tpu.obs.prof import ledger_from_journal

#: Top-level verdict keys (schema, test-enforced against ARCHITECTURE §9).
VERDICT_KEYS = (
    "span_s",
    "sources",
    "phases",
    "dominant_phase",
    "critical_src",
    "critical_phase",
    "critical_path",
    "straggler",
    "splits",
    "wire",
    "skew",
    "hbm",
    "jobs",
    "slowest_job",
    "compiles",
    "waves",
    "recovery",
    "plan",
    "conformance",
)


def _src_name(src: int) -> str:
    return f"p{int(src)}"


def analyze_records(
    records: list[dict], link_bytes_per_s: float | None = None
) -> dict:
    """One journal (raw or merged) -> the why-slow verdict dict.

    ``link_bytes_per_s`` (optional, e.g. from a transfer probe) prices the
    wire bytes into expected seconds; without it the wire section carries
    bytes only.
    """
    recs = sorted(
        (r for r in records if isinstance(r.get("mono"), (int, float))),
        key=lambda r: (r["mono"], r.get("seq", 0)),
    )
    if not recs:
        return {k: None for k in VERDICT_KEYS}
    t0 = recs[0]["mono"]
    t1 = recs[-1]["mono"]
    # Per-(src, phase) wall seconds; phase_end carries its own measured
    # ``seconds`` (PhaseTimer), so no start/end pairing is needed and a
    # torn journal missing a phase_start still attributes correctly.
    phase_s: dict[tuple[int, str], float] = {}
    src_end: dict[int, float] = {}
    src_events: dict[int, int] = {}
    waits: list[float] = []
    jobs: dict[tuple[int, object], dict] = {}
    counters_final: dict[tuple[int, object], dict] = {}
    skew_best: dict | None = None
    hbm_best: dict | None = None
    coded_recoveries = 0
    parity_recoveries = 0
    coded_keys = 0
    coded_replica_bytes = 0
    coded_wall_s = 0.0
    coded_budget_exceeded = 0
    straggler_serves = 0
    straggler_serve_keys = 0
    straggler_wall_s = 0.0
    mesh_reforms = 0
    evictions = 0
    wave_start: dict[tuple[int, object], float] = {}
    wave_span: dict[tuple[int, object], float] = {}
    wave_done_at: dict[tuple[int, object], float] = {}
    wave_resumed = 0
    plan_decisions: list[dict] = []
    plan_overrides: list[dict] = []
    for r in recs:
        src = int(r.get("src", 0))
        src_end[src] = r["mono"]
        src_events[src] = src_events.get(src, 0) + 1
        etype = r.get("type")
        if etype == "phase_end":
            sec = r.get("seconds")
            if isinstance(sec, (int, float)):
                key = (src, str(r.get("phase", "?")))
                phase_s[key] = phase_s.get(key, 0.0) + float(sec)
        elif etype == "job_dequeued":
            w = r.get("wait_s")
            if isinstance(w, (int, float)):
                waits.append(float(w))
        elif etype == "job_start":
            key = (src, r.get("job"))
            if key not in jobs:
                jobs[key] = {
                    "src": src,
                    "job": r.get("job"),
                    "tenant": r.get("tenant", "default"),
                    "n_keys": r.get("n_keys"),
                    "start": r["mono"],
                    "duration_s": None,
                }
        elif etype in ("job_done", "job_failed"):
            key = (src, r.get("job"))
            st = jobs.get(key)
            if st is not None and st["duration_s"] is None:
                st["duration_s"] = round(r["mono"] - st["start"], 6)
                st["outcome"] = "done" if etype == "job_done" else "failed"
            c = r.get("counters")
            if isinstance(c, dict):
                counters_final[key] = c
        elif etype == "skew_report":
            ratio = r.get("max_mean_ratio", 0.0)
            if skew_best is None or ratio > skew_best.get("max_mean_ratio", 0.0):
                skew_best = {
                    k: v for k, v in r.items()
                    if k not in ("seq", "t", "mono", "type")
                }
        elif etype in ("coded_recover", "parity_recover"):
            # Both are coded-local reconstructions (ARCHITECTURE §14/§18);
            # parity solves are tallied apart so the verdict can say WHICH
            # premium (full replicas vs XOR/P+Q slots) paid for recovery.
            if etype == "parity_recover":
                parity_recoveries += 1
            else:
                coded_recoveries += 1
            coded_keys += int(r.get("recovered_keys", 0) or 0)
            coded_replica_bytes += int(r.get("replica_bytes", 0) or 0)
            w = r.get("wall_s")
            coded_wall_s += float(w) if isinstance(w, (int, float)) else 0.0
        elif etype == "coded_budget_exceeded":
            coded_budget_exceeded += 1
        elif etype == "coded_straggler_serve":
            straggler_serves += 1
            straggler_serve_keys += int(r.get("recovered_keys", 0) or 0)
            w = r.get("wall_s")
            straggler_wall_s += (
                float(w) if isinstance(w, (int, float)) else 0.0
            )
        elif etype == "mesh_reform":
            mesh_reforms += 1
        elif etype == "job_evicted":
            evictions += 1
        elif etype == "wave_start":
            # Scoped by job ordinal: a session journal (a serve loop, a
            # process sorting many files) holds MANY wave jobs, and wave ids
            # repeat per job — an unscoped key would pair one job's start
            # with another's done.
            wave_start.setdefault((src, r.get("job"), r.get("wave")), r["mono"])
        elif etype == "wave_done":
            key = (src, r.get("job"), r.get("wave"))
            t_start = wave_start.get(key)
            if t_start is not None:
                wave_span[key] = round(r["mono"] - t_start, 6)
            wave_done_at[key] = r["mono"]
        elif etype == "wave_resume":
            m = r.get("missing")
            wave_resumed += int(m) if isinstance(m, (int, float)) else 0
        elif etype == "plan_decision":
            plan_decisions.append(r)
        elif etype == "plan_override":
            plan_overrides.append(r)
        elif etype == "hbm_watermark":
            b = r.get("bytes_in_use", 0)
            if hbm_best is None or b > hbm_best.get("bytes_in_use", 0):
                hbm_best = {
                    "bytes_in_use": b,
                    "max_device_bytes": r.get("max_device_bytes", 0),
                    "phase": r.get("phase", "?"),
                    "edge": r.get("edge", "?"),
                    "src": src,
                }
    srcs = sorted(src_end)
    # -- phase waterfall + critical path ------------------------------------
    phase_totals: dict[str, float] = {}
    for (src, phase), sec in phase_s.items():
        phase_totals[phase] = phase_totals.get(phase, 0.0) + sec
    dominant_phase = (
        max(phase_totals, key=phase_totals.get) if phase_totals else None
    )
    critical_src = max(srcs, key=lambda s: src_end[s])
    crit_phases = {
        phase: sec for (src, phase), sec in phase_s.items()
        if src == critical_src
    }
    critical_phase = (
        max(crit_phases, key=crit_phases.get) if crit_phases else None
    )
    critical_path = [
        {"src": critical_src, "name": _src_name(critical_src),
         "phase": phase, "seconds": round(sec, 6)}
        for phase, sec in sorted(
            crit_phases.items(), key=lambda kv: -kv[1]
        )
    ]
    # -- straggler attribution ----------------------------------------------
    busy = {
        s: sum(sec for (src, _), sec in phase_s.items() if src == s)
        for s in srcs
    }
    straggler = None
    if len(srcs) >= 2:
        mean_busy = sum(busy.values()) / len(busy)
        scores = {
            s: (busy[s] / mean_busy if mean_busy > 0 else 1.0) for s in srcs
        }
        worst = max(scores, key=scores.get)
        others = [s for s in srcs if s != worst]
        excess = {}
        for (src, phase), sec in phase_s.items():
            if src != worst:
                continue
            peer = [phase_s.get((o, phase), 0.0) for o in others]
            peer_mean = sum(peer) / len(peer) if peer else 0.0
            if sec - peer_mean > 0:
                excess[phase] = round(sec - peer_mean, 6)
        straggler = {
            "src": worst,
            "name": _src_name(worst),
            "score": round(scores[worst], 3),
            "busy_s": round(busy[worst], 6),
            "phase_excess_s": dict(
                sorted(excess.items(), key=lambda kv: -kv[1])
            ),
        }
    # -- splits: queue wait vs execute vs compile ---------------------------
    ledger = ledger_from_journal(recs)
    compile_s = round(sum(e["compile_s"] for e in ledger.values()), 6)
    total_phase_s = round(sum(phase_totals.values()), 6)
    splits = {
        "queue_wait_s": round(sum(waits), 6),
        "compile_s": compile_s,
        "execute_s": round(max(total_phase_s - compile_s, 0.0), 6),
        "phase_wall_s": total_phase_s,
    }
    # -- wire ---------------------------------------------------------------
    bytes_on_wire = sum(
        int(c.get("exchange_bytes_on_wire", 0))
        for c in counters_final.values()
    )
    wire = {"bytes_on_wire": bytes_on_wire}
    if link_bytes_per_s and bytes_on_wire:
        wire["expected_transfer_s"] = round(
            bytes_on_wire / float(link_bytes_per_s), 6
        )
    # -- assemble -----------------------------------------------------------
    job_rows = [
        {k: v for k, v in j.items() if k != "start"}
        for j in jobs.values()
    ]
    finished = [j for j in job_rows if j.get("duration_s") is not None]
    slowest_job = (
        max(finished, key=lambda j: j["duration_s"]) if finished else None
    )
    # -- recovery: coded-local vs re-run posture (ARCHITECTURE §14) ---------
    resorted_keys = sum(
        int(c.get(k, 0))
        for c in counters_final.values()
        for k in (
            "shuffle_resort_keys", "wave_resort_keys",
            "multihost_resort_keys",
        )
    )
    recovery = None
    local_recoveries = coded_recoveries + parity_recoveries
    if (
        local_recoveries or coded_budget_exceeded or mesh_reforms
        or evictions or resorted_keys or straggler_serves
    ):
        # A coded recovery re-forms exactly once per loss, so reforms in
        # EXCESS of the coded recoveries — like resume-path re-sorts,
        # budget overruns, or evictions that never completed codedly —
        # mean a re-run recovery also happened this session.  Parity
        # solves count the same as replica merges here (both are
        # coded-local, §18); straggler serves inject NO failure and so
        # never imply a re-run on their own.
        rerun_like = (
            coded_budget_exceeded > 0
            or resorted_keys > 0
            or mesh_reforms > local_recoveries
            or (evictions > 0 and local_recoveries == 0)
        )
        if local_recoveries and rerun_like:
            path = "mixed"
        elif parity_recoveries and coded_recoveries:
            path = "mixed"
        elif parity_recoveries:
            path = "parity_reconstruct"
        elif coded_recoveries:
            path = "coded_reconstruct"
        elif straggler_serves and not (
            mesh_reforms or evictions or resorted_keys
            or coded_budget_exceeded
        ):
            path = "straggler_serve"
        else:
            path = "rerun"
        recovery = {
            "path": path,
            "coded": {
                "recoveries": coded_recoveries,
                "parity_recoveries": parity_recoveries,
                "recovered_keys": coded_keys,
                "replica_bytes": coded_replica_bytes,
                "wall_s": round(coded_wall_s, 6),
                "budget_exceeded": coded_budget_exceeded,
            },
            "straggler": {
                "serves": straggler_serves,
                "served_keys": straggler_serve_keys,
                "wall_s": round(straggler_wall_s, 6),
            },
            "rerun": {
                "mesh_reforms": mesh_reforms,
                "evictions": evictions,
                "resorted_keys": resorted_keys,
            },
        }
    # -- waves: the out-of-core wave pipeline's verdict ---------------------
    waves = None
    if wave_done_at or wave_start or wave_resumed:
        slowest_wave = None
        if wave_span:
            (s_src, s_job, s_wave), s_sec = max(
                wave_span.items(), key=lambda kv: kv[1]
            )
            slowest_wave = {
                "wave": s_wave, "seconds": s_sec, "src": s_src, "job": s_job,
            }
        gating = None
        if wave_done_at:
            (g_src, g_job, g_wave), _ = max(
                wave_done_at.items(), key=lambda kv: kv[1]
            )
            gating = {"wave": g_wave, "src": g_src, "job": g_job}
        waves = {
            "count": len(set(wave_start) | set(wave_done_at)),
            "resumed_runs": wave_resumed,
            "slowest": slowest_wave,
            "gating": gating,
        }
    # -- plan: replay every planner decision from its journaled inputs ------
    plan = None
    if plan_decisions or plan_overrides:
        from dsort_tpu.obs.plan import replay_decision

        replayed = []
        mismatches = 0
        by_policy: dict[str, int] = {}
        for d in plan_decisions:
            policy = str(d.get("policy"))
            inputs = d.get("inputs") or {}
            by_policy[policy] = by_policy.get(policy, 0) + 1
            try:
                rechosen, rejected = replay_decision(policy, inputs)
            except (ValueError, TypeError, KeyError):
                rechosen, rejected = None, []
            match = rechosen == d.get("chosen")
            if not match:
                mismatches += 1
            replayed.append({
                "policy": policy,
                "chosen": d.get("chosen"),
                "replayed": rechosen,
                "match": match,
                "inputs": inputs,
                "rejected": d.get("rejected") or rejected,
            })
        plan = {
            "decisions": len(plan_decisions),
            "overrides": len(plan_overrides),
            "mismatches": mismatches,
            "by_policy": by_policy,
            "replayed": replayed,
            "overridden": [
                {
                    "policy": o.get("policy"),
                    "explicit": o.get("explicit"),
                    "planned": o.get("planned"),
                    "inputs": o.get("inputs") or {},
                }
                for o in plan_overrides
            ],
        }
    # Trace-contract conformance rides every verdict: the analyzer sees
    # the whole record stream anyway, and a non-conformant journal makes
    # every OTHER figure suspect (a trace that lost its job_dequeued also
    # lost that job's queue wait).  Lazy import: the contract engine is
    # stdlib-only, but analyze is importable without the analysis package
    # on odd installs — a missing engine degrades to no verdict, loudly.
    try:
        from dsort_tpu.analysis.spec.contracts import conformance_report
    except ImportError:  # pragma: no cover - partial install
        conformance = None
    else:
        conformance = conformance_report(recs)
    return {
        "span_s": round(t1 - t0, 6),
        "sources": {
            _src_name(s): {
                "events": src_events[s],
                "busy_s": round(busy[s], 6),
                "end_s": round(src_end[s] - t0, 6),
            }
            for s in srcs
        },
        "phases": {
            _src_name(src): {
                phase: round(sec, 6)
                for (s2, phase), sec in sorted(phase_s.items())
                if s2 == src
            }
            for src in srcs
        },
        "dominant_phase": dominant_phase,
        "critical_src": _src_name(critical_src),
        "critical_phase": critical_phase,
        "critical_path": critical_path,
        "straggler": straggler,
        "splits": splits,
        "wire": wire,
        "skew": skew_best,
        "hbm": hbm_best,
        "jobs": job_rows,
        "slowest_job": slowest_job,
        "compiles": ledger,
        "waves": waves,
        "recovery": recovery,
        "plan": plan,
        "conformance": conformance,
    }


def format_analysis(verdict: dict) -> str:
    """The human table behind ``dsort report --analyze``."""
    if not verdict or verdict.get("span_s") is None:
        return "(empty journal: nothing to analyze)\n"
    lines = [f"why-slow verdict over a {verdict['span_s'] * 1e3:.1f} ms span:"]
    crit = verdict.get("critical_phase")
    lines.append(
        f"  critical path : {verdict['critical_src']}"
        + (f" / {crit}" if crit else "")
        + " gated completion"
    )
    if verdict.get("dominant_phase"):
        lines.append(
            f"  dominant phase: {verdict['dominant_phase']} "
            f"({verdict['splits']['phase_wall_s'] * 1e3:.1f} ms phase wall "
            "total)"
        )
    st = verdict.get("straggler")
    if st:
        worst = next(iter(st["phase_excess_s"]), None)
        lines.append(
            f"  straggler     : {st['name']} (busy {st['busy_s'] * 1e3:.1f} "
            f"ms, {st['score']:.2f}x fleet mean"
            + (f"; lost in {worst}" if worst else "")
            + ")"
        )
    sp = verdict["splits"]
    lines.append(
        f"  split         : queue wait {sp['queue_wait_s'] * 1e3:.1f} ms | "
        f"compile {sp['compile_s'] * 1e3:.1f} ms | "
        f"execute {sp['execute_s'] * 1e3:.1f} ms"
    )
    wire = verdict.get("wire") or {}
    if wire.get("bytes_on_wire"):
        exp = wire.get("expected_transfer_s")
        lines.append(
            f"  wire          : {wire['bytes_on_wire']:,} bytes"
            + (f" (~{exp * 1e3:.1f} ms at the probed link)" if exp else "")
        )
    skew = verdict.get("skew")
    if skew:
        lines.append(
            f"  skew          : max/mean bucket ratio "
            f"{skew.get('max_mean_ratio', 0):.2f}"
            + (
                f", heaviest receiver device {skew['recv_argmax']}"
                if "recv_argmax" in skew else ""
            )
        )
    hbm = verdict.get("hbm")
    if hbm:
        lines.append(
            f"  hbm watermark : {hbm['bytes_in_use']:,} bytes in phase "
            f"{hbm['phase']} ({hbm['edge']})"
        )
    rec = verdict.get("recovery")
    if rec:
        c, rr = rec["coded"], rec["rerun"]
        lines.append(
            f"  recovery      : {rec['path']} — coded {c['recoveries']} "
            f"recovery(ies), {c['recovered_keys']:,} keys from "
            f"{c['replica_bytes']:,} replica bytes in "
            f"{c['wall_s'] * 1e3:.1f} ms | re-run {rr['mesh_reforms']} "
            f"reform(s), {rr['resorted_keys']:,} keys re-sorted"
        )
    wv = verdict.get("waves")
    if wv:
        slow = wv.get("slowest") or {}
        gate = wv.get("gating") or {}
        bits = [f"{wv.get('count', 0)} waves"]
        if gate:
            bits.append(f"wave {gate.get('wave')} gated completion")
        if slow:
            bits.append(
                f"slowest wave {slow.get('wave')} "
                f"({(slow.get('seconds') or 0) * 1e3:.1f} ms)"
            )
        if wv.get("resumed_runs"):
            bits.append(f"{wv['resumed_runs']} runs re-sorted on resume")
        lines.append("  waves         : " + ", ".join(bits))
    pl = verdict.get("plan")
    if pl:
        lines.append(
            f"  plan          : {pl['decisions']} decision(s), "
            f"{pl['overrides']} override(s), "
            f"{pl['mismatches']} replay mismatch(es)"
        )
    conf = verdict.get("conformance")
    if conf:
        lines.append(
            f"  conformance   : {conf['checked']} trace(s) against "
            f"{len(conf['contracts'])} contract(s) — "
            + ("OK" if conf["ok"]
               else f"{len(conf['violations'])} VIOLATION(S) "
                    f"({', '.join(sorted({v['contract'] for v in conf['violations']}))})")
        )
    sj = verdict.get("slowest_job")
    if sj:
        lines.append(
            f"  slowest job   : job {sj.get('job')} "
            f"(tenant {sj.get('tenant')}, {sj.get('n_keys')} keys, "
            f"{(sj.get('duration_s') or 0) * 1e3:.1f} ms)"
        )
    lines.append("phase waterfall (per process):")
    for name, phases in sorted((verdict.get("phases") or {}).items()):
        for phase, sec in sorted(phases.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {name:<6} {phase:<16} {sec * 1e3:>12.3f} ms")
    ledger = verdict.get("compiles") or {}
    if ledger:
        lines.append("compiled-variant ledger:")
        for label, e in sorted(ledger.items()):
            lines.append(
                f"  {label:<52} x{e['compiles']}  "
                f"{e['compile_s'] * 1e3:>10.1f} ms  "
                f"{e['flops']:>14.3g} flops  "
                f"{e['peak_hbm_bytes']:>12,} peak B"
            )
    pl = verdict.get("plan")
    if pl:
        # The audit trail: each decision replayed from its own inputs,
        # with the winning reason — why the planner chose what it chose.
        lines.append("planner decisions (replayed from journaled inputs):")
        for d in pl.get("replayed", []):
            chosen = d.get("chosen")
            shown = (
                f"[{len(chosen)} key(s)]"
                if isinstance(chosen, (list, tuple)) else chosen
            )
            inputs = d.get("inputs") or {}
            key_inputs = ", ".join(
                f"{k}={inputs[k]}" for k in sorted(inputs)
                if not isinstance(inputs[k], (list, dict))
            )
            ok = "ok" if d.get("match") else "MISMATCH"
            lines.append(
                f"  {d.get('policy'):<12} -> {shown}  [{ok}]  {key_inputs}"
            )
            for rej in (d.get("rejected") or [])[:2]:
                lines.append(
                    f"    rejected {rej.get('value')}: {rej.get('reason')}"
                )
        for o in pl.get("overridden", []):
            lines.append(
                f"  {o.get('policy'):<12} OVERRIDDEN: explicit "
                f"{o.get('explicit')} beat planned {o.get('planned')}"
            )
    return "\n".join(lines) + "\n"
