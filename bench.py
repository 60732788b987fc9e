"""Benchmark entry point — one JSON line per metric, headline first.

Headline: single-chip sort throughput (keys/sec) on uniform random int32 at
2^24 keys, measured on the framework's own block-bitonic Pallas kernel
(``ops.block_sort`` — fused-pass network, see its module docstring), compared
against the reference system's measured end-to-end throughput of ~4.4e4
keys/s (BASELINE.md: 16,384 int32 in ~374 ms across 4 CPU workers over
localhost TCP — its maximum supported job size).

Secondary lines: the same workload on XLA's built-in ``lax.sort``, the 2^26
size (round 1's "memory cliff"), 2^23 int64 (the lexicographic-planes path),
the TeraSort kv local phase (two-level key + 90 B payload permute, rec/s),
the post-shuffle merge comparison (block_merge_runs vs full re-sort vs the
jnp bitonic tree at the SPMD shape), the BASELINE config ladder (5 configs),
and the device-resident sort with on-device validation.  Every line is a TPU measurement: a run
that finds no TPU exits non-zero instead of measuring something else.

Timing methodology (r4 — reconciling the r3 chain-vs-slope gap): a single
dispatch carries a fixed host<->device round-trip.  So (a) completion is
forced by a tiny device->host slice copy, and (b) ``chain``
data-dependent sorts run inside ONE jitted program (each iteration re-sorts
the previous result XOR the loop index; comparator networks are
data-oblivious, so chaining is distribution-fair).  The r3 artifact divided
one chain's total by its length, which still charges the fixed dispatch
round-trip to the sorts.  r4 headline lines
therefore time TWO chain lengths and report the SLOPE
((T(c2)-T(c1))/(c2-c1)) as the per-sort figure — the fixed overhead cancels
— and carry the chained figure plus the measured per-dispatch overhead in
the same line so both methodologies stay visible.  min over reps, not
median: dispatch jitter is one-sided additive noise.

Env knobs: DSORT_BENCH_N (default 2^24), DSORT_BENCH_REPS (default 3),
DSORT_BENCH_CHAIN (default 48; the short chain is chain//6),
DSORT_BENCH_KERNEL ("block" | "lax" | ...), DSORT_BENCH_SUITE (default 1;
0 = headline lines only).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REFERENCE_KEYS_PER_SEC = 16_384 / 0.374  # BASELINE.md measured, ~4.38e4

# -- artifact schema (VERDICT r5 missing #1 successor: self-parsing) --------
#
# Every artifact this driver emits opens with ONE header line carrying the
# schema version and the line contract; `bench.py --check ARTIFACT`
# round-trips every line against the header it finds (or against the v0
# default below for pre-header artifacts), so a reader — or CI — can verify
# an artifact without knowing which bench revision wrote it.

BENCH_SCHEMA_VERSION = 1
#: Keys every metric line must carry, with their JSON types.
BENCH_SCHEMA_REQUIRED = {"metric": "str", "value": "num", "unit": "str"}
#: Known optional fields: PRESENT fields must match these types; fields not
#: listed here are free-form extras (allowed — lines carry workload context).
BENCH_SCHEMA_FIELD_TYPES = {
    "vs_baseline": "num",
    "chained_value": "num",
    "method": "str",
    "kernel": "str",
    "fixed_overhead_ms_per_dispatch": "num",
    "validated_ok": "bool",
    "bit_identical": "bool",
    # Phase-split rows of earlier suites (the in-tree BENCH_r*.jsonl carry
    # them; the program's ``partition.*``/``assemble.*`` steps time them now):
    "host_fraction": "num",
    "host_fraction_link": "num",
    "host_fraction_code": "num",
    "expected_transfer_s": "num",
    "phases_seconds": "obj",
    "ms_per_merge": "obj",
    "lines": "obj",
    "l": "obj",
    "bytes_on_wire": "num",
    "bytes_on_wire_alltoall": "num",
    "bytes_saved": "num",
    "speedup_vs_alltoall": "num",
    "speedup_vs_relay_e2e": "num",
    "capacity_retries": "num",
    "capacity_retries_alltoall": "num",
    "capacity_retries_ring": "num",
    "mesh_reforms": "num",
    "exchange": "str",
    "error": "str",
    "skipped": "str",
    # Serving-layer mixed-workload row (`dsort bench --serve-mixed`):
    "p95_queue_wait_ms": "num",
    "fairness_p95_ratio": "num",
    "cache_hit_rate": "num",
    "speedup_vs_serial": "num",
    "jobs": "num",
    "tenants": "num",
    "prewarmed": "num",
    "slices": "num",
    # Introspection-plane cost row (`dsort bench --analyze-smoke`, ISSUE 9):
    "overhead_frac": "num",
    "bare_keys_per_sec": "num",
    "journaled_keys_per_sec": "num",
    "dominant_phase": "str",
    "skew_ratio_zipf": "num",
    "skew_ratio_uniform": "num",
    "hbm_watermark_bytes": "num",
    "introspection_ok": "bool",
    # Out-of-core wave-pipeline rows (`dsort bench --external-wave`, ISSUE 10):
    "over_hbm_factor": "num",
    "num_waves": "num",
    "overlap_speedup": "num",
    "resume_fraction": "num",
    "runs_resorted": "num",
    # Fused-ring A/B rows (`dsort bench --exchange-ab` fused arm, ISSUE 11):
    "dispatches_per_exchange": "num",
    "dispatches_per_exchange_ring": "num",
    "ring_keys_per_sec": "num",
    "speedup_vs_ring": "num",
    "fused_launches_per_sort": "num",
    # Federated fleet row (`dsort bench --fleet-mixed`, ISSUE 12):
    "agents": "num",
    "cache_hit_rate_random": "num",
    "speedup_vs_random": "num",
    "rerouted": "num",
    # Health-plane rows (`dsort bench --fleet-mixed` health arm, ISSUE 14):
    "telemetry_overhead_frac": "num",
    "health_verdicts": "num",
    "speedup_vs_locality": "num",
    # Coded-redundancy rows (`dsort bench --coded-ab`, ISSUE 15):
    "throughput_under_failure_ratio": "num",
    "rerun_failure_ratio": "num",
    "replica_overhead_frac": "num",
    "redundancy": "num",
    "coded_recoveries": "num",
    "coded_replica_bytes": "num",
    "recovered_keys": "num",
    "baseline_keys_per_sec": "num",
    "rerun_keys_per_sec": "num",
    # Closed-loop planner A/B rows (`dsort bench --autotune-ab`, ISSUE 16):
    "chosen_exchange": "str",
    "expected_exchange": "str",
    "best_arm": "str",
    "best_keys_per_sec": "num",
    "alltoall_keys_per_sec": "num",
    "autotune_vs_best": "num",
    "plan_decisions": "num",
}

_SCHEMA_TYPE_CHECKS = {
    "str": lambda v: isinstance(v, str),
    "num": lambda v: isinstance(v, (int, float)) and not isinstance(v, bool),
    "bool": lambda v: isinstance(v, bool),
    "obj": lambda v: isinstance(v, dict),
}


def _schema_header() -> dict:
    return {
        "bench_schema": BENCH_SCHEMA_VERSION,
        "required": BENCH_SCHEMA_REQUIRED,
        "field_types": BENCH_SCHEMA_FIELD_TYPES,
    }


def check_artifact(path: str) -> list[str]:
    """Validate one artifact; returns a list of violations (empty = OK).

    Each line must be a JSON object that survives a dumps/loads round trip;
    metric lines must carry the required keys at the required types, and
    any field the schema knows must match its declared type.  A header line
    (``bench_schema``) switches validation to the contract it embeds —
    artifacts written before the header default to the v0 contract (same
    required keys, this file's known-field table).
    """
    errors: list[str] = []
    required = dict(BENCH_SCHEMA_REQUIRED)
    field_types = dict(BENCH_SCHEMA_FIELD_TYPES)
    try:
        with open(path, encoding="utf-8") as f:
            raw_lines = f.readlines()
    except OSError as e:
        return [f"{path}: unreadable: {e}"]
    saw_metric = False
    for lineno, raw in enumerate(raw_lines, 1):
        line = raw.strip()
        if not line:
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError as e:
            errors.append(f"{path}:{lineno}: not JSON: {e}")
            continue
        if not isinstance(obj, dict):
            errors.append(f"{path}:{lineno}: line is not a JSON object")
            continue
        if json.loads(json.dumps(obj)) != obj:
            errors.append(f"{path}:{lineno}: does not round-trip")  # pragma: no cover
            continue
        if "bench_schema" in obj:
            if saw_metric:
                errors.append(
                    f"{path}:{lineno}: schema header after metric lines"
                )
            if not isinstance(obj["bench_schema"], int):
                errors.append(f"{path}:{lineno}: bench_schema not an int")
            if isinstance(obj.get("required"), dict):
                required = obj["required"]
            if isinstance(obj.get("field_types"), dict):
                field_types = obj["field_types"]
            continue
        saw_metric = True
        for key, typ in required.items():
            if key not in obj:
                errors.append(f"{path}:{lineno}: missing required {key!r}")
            elif not _SCHEMA_TYPE_CHECKS.get(typ, lambda v: True)(obj[key]):
                errors.append(
                    f"{path}:{lineno}: {key!r} is not of type {typ!r}"
                )
        for key, typ in field_types.items():
            if key in obj and not _SCHEMA_TYPE_CHECKS.get(
                typ, lambda v: True
            )(obj[key]):
                errors.append(
                    f"{path}:{lineno}: {key!r} is not of type {typ!r}"
                )
    return errors


# -- artifact regression diff (`bench.py --compare OLD NEW`) -----------------
#
# The in-tree BENCH_*.jsonl artifacts are a trajectory; this is the tool
# that reads it.  Metrics match by name; throughput-like units compare as
# new/old ratios and classify on the tolerance ladder below.  Sub-unity
# ratios up to `noise` are expected between sessions (the dispatch-jitter
# doctrine in the module docstring); `regression`/`severe` mean a change
# that needs an explanation in the PR that shipped it.

#: (floor ratio, class) — first floor the ratio clears, scanning down.
COMPARE_LADDER: tuple[tuple[float, str], ...] = (
    (0.95, "ok"),
    (0.80, "noise"),
    (0.50, "regression"),
    (0.0, "severe"),
)

#: Units where value is a rate (higher = better) and a ratio is meaningful.
_RATE_UNITS = {"keys/sec", "rec/sec", "MB/s"}


def classify_ratio(ratio: float) -> str:
    for floor, label in COMPARE_LADDER:
        if ratio >= floor:
            return label
    return "severe"


def _artifact_metrics(path: str) -> dict[str, dict]:
    """Metric lines of one artifact, keyed by metric name (summary/header
    lines dropped; duplicate names keep their first occurrence, matching
    the summary's disambiguation doctrine)."""
    out: dict[str, dict] = {}
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if not isinstance(obj, dict) or "metric" not in obj:
                continue
            if obj["metric"] in ("summary", "compact_summary"):
                continue
            out.setdefault(obj["metric"], obj)
    return out


def compare_artifacts(old_path: str, new_path: str) -> list[dict]:
    """Regression rows for every metric the two artifacts share.

    Each row: ``{"metric", "unit", "old", "new", "ratio", "class"}`` for
    rate units; non-rate units (ratios, counters) report ``class:"info"``.
    Metrics present on only one side report as ``added``/``removed`` —
    silently narrowing coverage is itself a regression signal.
    """
    old, new = _artifact_metrics(old_path), _artifact_metrics(new_path)
    rows: list[dict] = []
    for name in sorted(set(old) | set(new)):
        o, n = old.get(name), new.get(name)
        if o is None or n is None:
            rows.append(
                {"metric": name, "class": "added" if o is None else "removed"}
            )
            continue
        row = {
            "metric": name, "unit": n.get("unit"),
            "old": o.get("value"), "new": n.get("value"),
        }
        # A zero/errored side makes the ratio meaningless — an error line's
        # value is 0.0 by convention; call it out instead of dividing.
        if "error" in o or "error" in n or not o.get("value"):
            row["class"] = "error" if ("error" in o or "error" in n) else "info"
        elif n.get("unit") in _RATE_UNITS and o.get("unit") == n.get("unit"):
            ratio = float(n["value"]) / float(o["value"])
            row["ratio"] = round(ratio, 3)
            row["class"] = classify_ratio(ratio)
        else:
            row["class"] = "info"
        rows.append(row)
    return rows


def _compare_main(argv: list[str]) -> int:
    """``bench.py --compare OLD NEW [--strict]``: print rows, summarize.

    Exit 1 on any ``severe`` row (``--strict``: also on ``regression``);
    the ladder classes in between are reported, not fatal — session noise
    must not turn CI red.  Backend-free, like ``--check``.
    """
    strict = "--strict" in argv
    paths = [a for a in argv if a != "--strict"]
    if len(paths) != 2:
        print(
            "usage: bench.py --compare OLD NEW [--strict]", file=sys.stderr
        )
        return 2
    rows = compare_artifacts(paths[0], paths[1])
    if not rows:
        print(f"no metric lines found in {paths[0]} / {paths[1]}",
              file=sys.stderr)
        return 2
    counts: dict[str, int] = {}
    for row in rows:
        counts[row["class"]] = counts.get(row["class"], 0) + 1
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "metric": "compare_summary",
        "old": paths[0], "new": paths[1],
        "classes": counts,
        "ladder": [[f, c] for f, c in COMPARE_LADDER],
    }), flush=True)
    bad = counts.get("severe", 0) + (counts.get("regression", 0) if strict else 0)
    return 1 if bad else 0


# -- perf trajectory (`bench.py --history [DIR]`) ----------------------------
#
# The in-tree BENCH_r*.jsonl artifacts record one bench session per PR;
# until now the trajectory across them was only reconstructable by hand
# (pairwise --compare runs).  --history consolidates them into ONE
# metric x artifact table, classifying each consecutive step on the same
# tolerance ladder --compare uses.  Backend-free, like --check.

_HISTORY_GLOB = "BENCH_r*.jsonl"


def history_artifacts(root: str) -> list[str]:
    """In-tree ``BENCH_r*.jsonl`` artifacts, oldest first (by the rNN
    number, then name — previews sort with their round)."""
    import glob as _glob
    import re as _re

    def round_of(path):
        m = _re.search(r"BENCH_r(\d+)", os.path.basename(path))
        return (int(m.group(1)) if m else 0, os.path.basename(path))

    return sorted(
        _glob.glob(os.path.join(root, _HISTORY_GLOB)), key=round_of
    )


def history_rows(root: str) -> dict:
    """The consolidated trajectory: ``{"artifacts": [names...],
    "metrics": {metric: {artifact: {"value", "unit"}}},
    "steps": {metric: [{"frm", "to", "ratio", "class"}...]}}``.

    Steps classify CONSECUTIVE appearances of a metric (they may skip
    artifacts — a metric benched in r07 and r12 classifies r07->r12) on
    the `COMPARE_LADDER`, rate units only; non-rate units report
    ``info``.
    """
    paths = history_artifacts(root)
    names = [os.path.basename(p) for p in paths]
    metrics: dict[str, dict] = {}
    for path, name in zip(paths, names):
        for metric, obj in _artifact_metrics(path).items():
            metrics.setdefault(metric, {})[name] = {
                "value": obj.get("value"), "unit": obj.get("unit"),
            }
    steps: dict[str, list] = {}
    for metric, per in metrics.items():
        seen = [n for n in names if n in per]
        for frm, to in zip(seen, seen[1:]):
            o, n = per[frm], per[to]
            row = {"frm": frm, "to": to}
            if (
                n.get("unit") in _RATE_UNITS
                and o.get("unit") == n.get("unit")
                and o.get("value")
            ):
                ratio = float(n["value"]) / float(o["value"])
                row["ratio"] = round(ratio, 3)
                row["class"] = classify_ratio(ratio)
            else:
                row["class"] = "info"
            steps.setdefault(metric, []).append(row)
    return {"artifacts": names, "metrics": metrics, "steps": steps}


def _history_main(argv: list[str]) -> int:
    """``bench.py --history [DIR]``: print the metric x PR trajectory."""
    root = argv[0] if argv else os.path.dirname(os.path.abspath(__file__))
    if len(argv) > 1:
        print("usage: bench.py --history [DIR]", file=sys.stderr)
        return 2
    hist = history_rows(root)
    if not hist["artifacts"]:
        print(f"no {_HISTORY_GLOB} artifacts under {root}", file=sys.stderr)
        return 2
    cols = [n.replace("BENCH_", "").replace(".jsonl", "")
            for n in hist["artifacts"]]
    head = f"{'metric':<52}" + "".join(f"{c:>14}" for c in cols)
    print(head)
    print("-" * len(head))
    worst: dict[str, int] = {}
    for metric in sorted(hist["metrics"]):
        per = hist["metrics"][metric]
        cells = []
        for name in hist["artifacts"]:
            v = per.get(name, {}).get("value")
            cells.append(f"{v:>14.4g}" if isinstance(v, (int, float))
                         else f"{'-':>14}")
        marks = "".join(
            {"ok": "", "info": "", "noise": "~",
             "regression": "!", "severe": "!!"}.get(s["class"], "")
            for s in hist["steps"].get(metric, ())
        )
        print(f"{(metric + (' ' + marks if marks else ''))[:52]:<52}"
              + "".join(cells))
        for s in hist["steps"].get(metric, ()):
            worst[s["class"]] = worst.get(s["class"], 0) + 1
    print(json.dumps({
        "metric": "history_summary",
        "artifacts": hist["artifacts"],
        "metrics": len(hist["metrics"]),
        "classes": worst,
    }), flush=True)
    return 0


_EMITTED: list = []  # every line of this run, for the final summary


def _emit_line(line: dict) -> None:
    _EMITTED.append(line)
    print(json.dumps(line), flush=True)


def _emit(metric: str, value: float, unit: str, baseline: bool = True, **extra) -> None:
    line: dict = {"metric": metric, "value": round(value, 1), "unit": unit}
    if baseline:
        line["vs_baseline"] = round(value / REFERENCE_KEYS_PER_SEC, 2)
    line.update(extra)
    _emit_line(line)


def _emit_summary() -> None:
    """LAST lines of the artifact: every headline, full then compact.

    The driver's artifact capture is a bounded TAIL and its ``parsed``
    field is the final JSON line — r4 lost the block-kernel and int64
    headlines to exactly that truncation (VERDICT r4 missing #2), and the
    r5 full summary itself outgrew the 2,000-byte tail (VERDICT r5 missing
    #1).  So TWO summary lines close the artifact: the full summary
    (value/unit/vs_baseline plus the chained cross-check per metric, for
    humans and the preview file), then a final COMPACT line (`
    _compact_summary`: short keys, rounded values, < ~1,500 bytes) so the
    line the driver's tail parser lands on always fits the capture.
    Emitted from a ``finally`` so a mid-suite crash still summarizes the
    lines that did complete.
    """
    if not _EMITTED:
        return
    head = _EMITTED[0]
    lines = {}
    for ln in _EMITTED:
        entry = {"value": ln["value"], "unit": ln["unit"]}
        for k in ("vs_baseline", "chained_value", "kernel", "fastest",
                  "slowdown_at_end", "mesh_reforms", "host_fraction",
                  "skipped", "error"):
            if k in ln:
                entry[k] = ln[k]
        # Two rows may share a metric label (e.g. ladder variants that
        # differ only in the `kernel` extra) — keying by metric alone would
        # silently overwrite one, the exact truncation failure mode this
        # summary exists to prevent (ADVICE r5).  Disambiguate by kernel,
        # then by index, so every emitted line survives into the summary.
        key = ln["metric"]
        if key in lines and "kernel" in ln:
            key = f"{key}#{ln['kernel']}"
        dup = 2
        while key in lines:
            key = f"{ln['metric']}#{dup}"
            dup += 1
        lines[key] = entry
    out = {
        "metric": "summary",
        # value/unit/vs_baseline mirror the HEADLINE line so a parser that
        # only reads the last line still sees the headline figure.
        "value": head["value"],
        "unit": head["unit"],
        "headline": head["metric"],
        "lines": lines,
    }
    if "vs_baseline" in head:
        out["vs_baseline"] = head["vs_baseline"]
    print(json.dumps(out), flush=True)
    print(json.dumps(_compact_summary(_EMITTED)), flush=True)


#: Tokens dropped outright by `_abbrev` — pure noise in a short key.
_ABBREV_NOISE = frozenset(
    {"sort", "throughput", "keys", "records", "single", "chip", "with",
     "sorted", "runs", "end", "to", "the", "injected", "failure", "phase",
     "split"}
)


def _abbrev(metric: str) -> str:
    """Deterministic short key for one metric name (compact summary).

    Powers of two render as ``2pN``, dtypes shorten (``int32`` → ``i32``),
    ``configN`` → ``cN``, noise words drop, everything else keeps its first
    four letters.  Collisions are resolved by the caller (suffixing) — the
    mapping need not be pretty, only small and stable; the FULL summary
    line directly above carries the unabbreviated names.
    """
    out = []
    for tok in metric.split("_"):
        if tok.isdigit():
            n = int(tok)
            if n >= 256 and n & (n - 1) == 0:
                out.append(f"2p{n.bit_length() - 1}")
            else:
                out.append(tok)
        elif tok.startswith(("uint", "int", "float")) and tok[-1].isdigit():
            out.append(
                tok.replace("uint", "u").replace("int", "i")
                .replace("float", "f")
            )
        elif tok.startswith("config"):
            out.append("c" + tok[len("config"):])
        elif tok in _ABBREV_NOISE:
            continue
        else:
            out.append(tok[:4])
    return "".join(out) or "m"


def _sig3(v):
    """3-significant-digit rounding — compact-line values need no more."""
    if not isinstance(v, (int, float)) or v == 0:
        return v
    from math import floor, log10

    return round(v, -int(floor(log10(abs(v)))) + 2)


def _compact_summary(emitted: list) -> dict:
    """The guaranteed-small final artifact line (VERDICT r5 missing #1).

    Short keys (`_abbrev`, deduped), values rounded to 3 significant
    digits, each entry ``[value]`` or ``[value, vs_baseline]`` — nothing
    else.  ~25 bytes/metric keeps even a 40-metric suite far below the
    driver's 2,000-byte tail capture; ``tests/test_bench_summary.py``
    pins the bound at < 1,800 bytes for a 20-metric suite.
    """
    head = emitted[0]
    lines: dict = {}
    for ln in emitted:
        key = _abbrev(ln["metric"])
        while key in lines:
            key += "x"
        entry = [_sig3(ln["value"])]
        if "vs_baseline" in ln:
            entry.append(_sig3(ln["vs_baseline"]))
        lines[key] = entry
    out = {
        "metric": "compact_summary",
        "value": head["value"],
        "unit": head["unit"],
        "l": lines,
    }
    if "vs_baseline" in head:
        out["vs_baseline"] = head["vs_baseline"]
    return out


def _chain_runner(sort_fn, x):
    """One jitted chain executable with a TRACED length.

    The chain length rides as a runtime argument to ``fori_loop``, so the
    short and long chains of a slope pair share a single executable — one
    Mosaic/XLA compile instead of two.  That matters through the remote
    compile service, whose cold-compile time for the full kernel set swings
    from ~1 min to ~10 min between sessions (measured r4); the loop body
    and therefore the per-iteration cost are identical to a static-bound
    chain (XLA lowers both to the same while loop).
    """
    import jax
    from jax import lax

    f = jax.jit(
        lambda a, c: lax.fori_loop(0, c, lambda i, v: sort_fn(v ^ i), a)
    )
    # np.int32 pins the traced length's dtype: a bare Python int is a WEAK
    # scalar whose aval flips int32 -> int64 when the suite enables x64
    # mid-run, silently recompiling the whole chain executable (minutes
    # through a cold compile service) on the next call.
    y = f(x, np.int32(2))  # compile + warm
    out_head = np.asarray(y[: 1 << 16])  # materialize = warm run completed
    assert (np.diff(out_head) >= 0).all(), "bench output not sorted"
    return f


def _chain_total(f, x, chain: int, reps: int) -> float:
    """Total seconds for one ``chain``-length run of a `_chain_runner` (min
    of reps — dispatch jitter is one-sided additive noise)."""
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        # tiny D2H copy = completion barrier; np.int32: see _chain_runner
        _ = np.asarray(f(x, np.int32(chain))[-1:])
        times.append(time.perf_counter() - t0)
    return float(min(times))


def _slope_of(total_fn, c1: int, c2: int):
    """Two-point slope over any total-seconds-per-chain callable.

    Returns ``(per_op_s, fixed_overhead_s | None, chained_per_op_s)``.  The
    slope cancels the fixed dispatch round-trip; the chained figure
    (T(c2)/c2) still includes overhead/c2.  If dispatch jitter yields a
    non-positive slope, falls back to the chained figure with
    ``fixed = None`` so emitters can label the line honestly.
    """
    t1, t2 = total_fn(c1), total_fn(c2)
    per = (t2 - t1) / (c2 - c1)
    chained = t2 / c2
    if per <= 0:  # noise swamped the short chain; don't report garbage
        return chained, None, chained
    return per, max(t1 - c1 * per, 0.0), chained


def _slope_fields(per, fixed, chained, n_items, c1, c2) -> dict:
    """The shared reporting contract: method + chained figure + overhead."""
    out = {
        "method": f"chain_slope({c1},{c2})" if fixed is not None
        else "chained_fallback",
        "chained_value": round(n_items / chained, 1),
    }
    if fixed is not None:
        out["fixed_overhead_ms_per_dispatch"] = round(fixed * 1e3, 2)
    return out


def _emit_slope(name: str, n_items: int, unit: str, sort_fn, x, c1, c2, reps,
                baseline: bool = True, **extra):
    """Emit one slope-timed line; returns ``(runner, per, fixed, chained)``
    so callers can re-measure the same executable later (drift sensor)."""
    f = _chain_runner(sort_fn, x)
    per, fixed, chained = _slope_of(
        lambda c: _chain_total(f, x, c, reps), c1, c2
    )
    _emit(
        name, n_items / per, unit, baseline=baseline,
        **_slope_fields(per, fixed, chained, n_items, c1, c2), **extra,
    )
    return f, per, fixed, chained


def main() -> None:
    from dsort_tpu.utils.backend import use_compile_cache

    use_compile_cache()  # the Pallas kernel set compiles in ~1 min cold
    # The schema header is the artifact's FIRST line — printed directly
    # (not via _emit_line) so the summary never mistakes it for a metric.
    print(json.dumps(_schema_header()), flush=True)
    try:
        _main_body()
    finally:
        _emit_summary()


def _main_body() -> None:
    import jax

    from dsort_tpu.ops.local_sort import sort_with_kernel

    n = int(os.environ.get("DSORT_BENCH_N", 1 << 24))
    reps = int(os.environ.get("DSORT_BENCH_REPS", 3))
    chain = int(os.environ.get("DSORT_BENCH_CHAIN", 48))
    if chain < 2:
        raise SystemExit("DSORT_BENCH_CHAIN must be >= 2")
    c_short = max(chain // 6, 1)
    chip = jax.devices()[0].platform
    if chip != "tpu":
        # A chip number from the CPU backend or the Pallas interpreter
        # would be a different measurement under the same name.
        raise SystemExit(f"bench.py measures the TPU; JAX found {chip!r}")
    kernel = os.environ.get("DSORT_BENCH_KERNEL", "block")

    rng = np.random.default_rng(0)
    host = rng.integers(-(2**31), 2**31 - 1, n, dtype=np.int64).astype(np.int32)
    x = jax.numpy.asarray(host)

    # Headline: the framework kernel, slope-timed (see module docstring).
    _emit_slope(
        f"sort_throughput_int32_{n}_keys_single_chip_{chip}",
        n, "keys/sec",
        lambda v: sort_with_kernel(v, kernel), x, c_short, chain, reps,
        kernel=kernel,
    )

    if os.environ.get("DSORT_BENCH_SUITE", "1") != "1":
        return

    # The round-1 headline kernel (XLA lax.sort) on the same workload, for a
    # like-for-like speedup record in the same artifact.  The runner is kept
    # and re-measured at suite end as the drift sensor below.
    hbm_sensor = None
    if kernel != "lax":
        hbm_sensor = _emit_slope(
            f"sort_throughput_int32_{n}_keys_single_chip_{chip}_lax_kernel",
            n, "keys/sec",
            lambda v: sort_with_kernel(v, "lax"), x, c_short, chain, reps,
            kernel="lax",
        )

    # 2^26: round 1's memory cliff (lax.sort fell to ~48 Mkeys/s there).
    n26 = 1 << 26
    big = jax.numpy.asarray(
        rng.integers(-(2**31), 2**31 - 1, n26, dtype=np.int64).astype(
            np.int32
        )
    )
    _emit_slope(
        f"sort_throughput_int32_{n26}_keys_single_chip_{chip}",
        n26, "keys/sec",
        lambda v: sort_with_kernel(v, kernel), big,
        max(chain // 24, 1), max(chain // 4, 2), reps,
        kernel=kernel,
    )
    del big

    from dsort_tpu.utils.compat import set_x64

    set_x64(True)  # int64/uint64 lines + config3; via the compat shim (DS501)

    # 2^23 int64 — the lexicographic (hi, lo)-planes path (README's 2.2x-lax
    # claim, now artifact-recorded each round: VERDICT r3 #3).
    import jax.numpy as jnp

    n64 = 1 << 23
    h64 = rng.integers(-(2**62), 2**62, n64, dtype=np.int64)
    x64 = jnp.asarray(h64)
    _, per64_blk, fixed64_blk, chained64_blk = _emit_slope(
        f"sort_throughput_int64_{n64}_keys_single_chip_{chip}",
        n64, "keys/sec",
        lambda v: sort_with_kernel(v, kernel), x64, c_short, chain, reps,
        kernel=kernel,
    )
    _, per64_lax, fixed64_lax, chained64_lax = _emit_slope(
        f"sort_throughput_int64_{n64}_keys_single_chip_{chip}_lax_kernel",
        n64, "keys/sec",
        lambda v: sort_with_kernel(v, "lax"), x64, c_short, chain, reps,
        kernel="lax",
    )
    # Same-run block/lax int64 ratio as its OWN artifact field (VERDICT
    # r5 weak #3): the margin thinned to 1.10x in r5 and sessions swing
    # ±10%, so the claim "block beats lax on int64" needs a per-artifact
    # guard, not two rows a reader must divide.  Below 1.05 the ratio is
    # inside the session noise — flag it so a future inversion alerts.
    # Like-for-like comparison (same rule as the drift sensor): slope vs
    # slope only when BOTH slopes were valid; if either fell back to the
    # chained figure (fixed is None), compare chained vs chained so the
    # fixed-overhead share cancels instead of inflating one side.
    if fixed64_blk is not None and fixed64_lax is not None:
        ratio = per64_lax / per64_blk if per64_blk > 0 else 0.0
        ratio_method = "chain_slope"
    else:
        ratio = (
            chained64_lax / chained64_blk if chained64_blk > 0 else 0.0
        )
        ratio_method = "chained_fallback"
    drift = ratio < 1.05
    if drift:
        print(
            f"WARNING: int64 block/lax ratio {ratio:.3f} < 1.05 — the "
            "block kernel's int64 edge is inside session noise this run",
            file=sys.stderr,
        )
    # _emit_line, not _emit: the 1-decimal value rounding there would
    # flatten 1.048 to 1.0 — exactly the precision this guard needs.
    _emit_line(
        {
            "metric": f"int64_block_vs_lax_ratio_{n64}",
            "value": round(ratio, 3),
            "unit": "ratio",
            "kernel": kernel,
            "method": ratio_method,
            **({"drift_warning": True} if drift else {}),
        }
    )
    del x64

    # TeraSort kv local phase: two-level key (uint64 primary + int32
    # secondary) + 90 B payload permute — the exact per-chip work of
    # `_kv_shard_body`'s phase 1 (lax.sort multi-operand carries the
    # permutation; the payload rides one gather).  rec/s, slope-timed.
    import jax.numpy as jnp

    from dsort_tpu.ops.local_sort import _apply_perm

    nrec = 1 << 22
    kq = jnp.asarray(rng.integers(0, 2**63, nrec, dtype=np.uint64))
    sq = jnp.asarray(rng.integers(0, 2**16, nrec).astype(np.int32))
    vq = jnp.asarray(rng.integers(0, 255, (nrec, 90), dtype=np.uint8))
    idx = jnp.arange(nrec, dtype=jnp.int32)

    def kv_local(carry, i):
        k, s, v = carry
        ok, os_, perm = jax.lax.sort(
            (k, s, idx), dimension=-1, num_keys=2, is_stable=False
        )
        return (ok ^ i.astype(jnp.uint64), os_, _apply_perm(v, perm, 0))

    # Traced chain length: both slope points share one executable (see
    # _chain_runner).
    fkv = jax.jit(
        lambda k, s, v, c: jax.lax.fori_loop(
            0, c, lambda i, cr: kv_local(cr, i), (k, s, v)
        )
    )
    # np.int32 chain length: see _chain_runner (pins the aval across
    # the x64 flip; bare ints are weak scalars and would recompile).
    np.asarray(fkv(kq, sq, vq, np.int32(2))[2][-1:, -1:])  # warm

    def _kv_chain_total(c: int) -> float:
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            r = fkv(kq, sq, vq, np.int32(c))
            np.asarray(r[2][-1:, -1:])  # completion barrier
            times.append(time.perf_counter() - t0)
        return float(min(times))

    ck1, ck2 = 2, 10
    per, fixed, chained = _slope_of(_kv_chain_total, ck1, ck2)
    _emit(
        f"terasort_local_phase_{nrec}_records_kv",
        nrec / per, "rec/sec", baseline=False,
        **_slope_fields(per, fixed, chained, nrec, ck1, ck2),
        payload_bytes=90,
    )
    del kq, sq, vq

    # Post-shuffle merge comparison at the SPMD shape (P=8 runs of one
    # block): block_merge_runs (enter the network at level 2*run_len) vs the
    # full block_sort re-sort vs the jnp bitonic tree (VERDICT r3 #2).  The
    # `+ i` chain keeps rows sorted (comparator networks are data-oblivious,
    # so the rare int32 wraparound cannot affect timing); correctness is
    # asserted once un-chained.
    import jax.numpy as jnp

    from dsort_tpu.ops.bitonic import merge_sorted_runs
    from dsort_tpu.ops.block_sort import block_merge_runs, block_sort

    p_runs, run_len = 8, 1 << 17
    nm = p_runs * run_len
    base = np.sort(
        rng.integers(-(2**31), 2**31 - 1, (p_runs, run_len), dtype=np.int64)
        .astype(np.int32),
        axis=1,
    )
    runs = jnp.asarray(base)
    ref = np.sort(base.reshape(-1))
    assert (np.asarray(block_merge_runs(runs)) == ref).all()

    def _rows_runner(fn_flat):
        f = jax.jit(
            lambda a, c: jax.lax.fori_loop(
                0, c,
                lambda i, v: fn_flat(v).reshape(v.shape) + i,
                a,
            )
        )
        # np.int32: see _chain_runner (pin the aval across the x64 flip)
        np.asarray(f(runs, np.int32(2))[-1:, -1:])  # warm + materialize
        return f

    def _rows_chain_total(f, c: int) -> float:
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(f(runs, np.int32(c))[-1:, -1:])
            times.append(time.perf_counter() - t0)
        return float(min(times))

    import functools

    # us-scale ops need long chains or dispatch jitter swamps the slope
    # (a 24/144 pair once measured the full re-sort at an impossible
    # 0.028 ms/merge); traced lengths make long chains compile-free.
    cm1, cm2 = 96, 576
    variants = {
        "block_merge": lambda v: block_merge_runs(v),
        "full_resort": lambda v: block_sort(v.reshape(-1)),
        "bitonic_jnp": lambda v: merge_sorted_runs(v),
    }
    per_variant = {}
    for name, fn in variants.items():
        f = _rows_runner(fn)
        per, _, _ = _slope_of(functools.partial(_rows_chain_total, f), cm1, cm2)
        per_variant[name] = per
    best = min(per_variant, key=per_variant.get)
    _emit(
        f"merge_phase_{p_runs}x{run_len}_sorted_runs",
        nm / per_variant["block_merge"], "keys/sec", baseline=False,
        method=f"chain_slope({cm1},{cm2})",
        ms_per_merge={
            k: round(v * 1e3, 3) for k, v in per_variant.items()
        },
        fastest=best,
    )
    del runs

    # BASELINE config ladder (5 lines) — end-to-end host->host timings of the
    # public SampleSort API, so these *include* the dispatch round-trip.
    import argparse

    from dsort_tpu import cli as _cli

    _cli._bench_suite(argparse.Namespace(reps=reps, emit=_emit_line))

    from dsort_tpu.config import JobConfig
    from dsort_tpu.data.ingest import gen_uniform
    from dsort_tpu.parallel.mesh import local_device_mesh
    from dsort_tpu.parallel.sample_sort import SampleSort

    ss = SampleSort(local_device_mesh(), JobConfig(local_kernel=kernel))

    # Device-resident e2e + on-device validation (VERDICT r5 next #5): the
    # path a real pipeline stage uses — the sorted array STAYS sharded on
    # the mesh (`keep_on_device` -> DeviceSortResult), and `dsort validate`
    # semantics (order + FNV multiset checksum) run as jitted shard_map
    # reductions with only scalars crossing to the host.
    try:
        from dsort_tpu.models.validate import _multiset

        u1m = gen_uniform(1 << 20, seed=9)
        h = ss.sort(u1m, keep_on_device=True)  # warm the sort program
        h.validate_on_device()                 # warm the validator
        st, vt = [], []
        rep_v = None
        for _ in range(reps):
            t0 = time.perf_counter()
            h = ss.sort(u1m, keep_on_device=True)
            st.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            rep_v = h.validate_on_device()
            vt.append(time.perf_counter() - t0)
        dt, dtv = float(min(st)), float(min(vt))
        ok = bool(
            rep_v.sorted_ok
            and rep_v.records == len(u1m)
            and rep_v.checksum == _multiset(u1m, len(u1m), u1m.dtype.itemsize)
        )
        _emit(
            f"sort_e2e_device_resident_1M_{chip}",
            (1 << 20) / dt, "keys/sec", validated_ok=ok,
        )
        # The on-device validate cost as its own metric: what `dsort
        # validate` semantics cost when nothing relays to the host.
        _emit(
            f"validate_on_device_1M_{chip}",
            (1 << 20) / dtv, "keys/sec", baseline=False, validated_ok=ok,
        )
    except Exception as e:  # the no-relay lines must never sink the artifact
        _emit(
            f"sort_e2e_device_resident_1M_{chip}", 0.0, "keys/sec",
            baseline=False,
            error=(str(e).splitlines() or [repr(e)])[0][:200],
        )

    # HBM drift sentinel: lax.sort is HBM-pass-bound (the VMEM-resident
    # block kernel is not), so re-measuring the SAME lax chain that
    # opened the suite flags whether later lines were taken in a degraded
    # window (observed r4: one window measured every chained program
    # 20-30x slow).  slowdown_at_end > ~1.5 means read the lines between
    # with suspicion; ~1.0 means the artifact is one coherent session.
    if hbm_sensor is not None:
        f_lax, per0, fixed0, chained0 = hbm_sensor
        per1, fixed1, chained1 = _slope_of(
            lambda c: _chain_total(f_lax, x, c, reps), c_short, chain
        )
        # Compare like with like: slope-vs-slope when both slopes are
        # valid, else chained-vs-chained (the fallback fires exactly in
        # the degraded windows this sensor exists to flag, and a chained
        # figure still carries overhead/c2 the slope cancels).
        if fixed0 is not None and fixed1 is not None:
            slowdown = per1 / per0
        else:
            slowdown = chained1 / chained0
        _emit(
            "drift_sensor_lax_int32", n / per1, "keys/sec",
            baseline=False,
            **_slope_fields(per1, fixed1, chained1, n, c_short, chain),
            start_of_suite_keys_per_sec=round(n / per0, 1),
            slowdown_at_end=round(slowdown, 3),
        )


def _check_main(paths: list[str]) -> int:
    """``bench.py --check ARTIFACT...``: validate artifacts, report, exit.

    Needs no accelerator backend (and must not touch one: the checker runs
    in tier-1 CI against the in-tree ``BENCH_*.jsonl`` artifacts).
    """
    if not paths:
        print("usage: bench.py --check ARTIFACT [ARTIFACT...]", file=sys.stderr)
        return 2
    bad = 0
    for p in paths:
        errs = check_artifact(p)
        for e in errs:
            print(e, file=sys.stderr)
        print(f"{p}: {'OK' if not errs else f'{len(errs)} schema violations'}")
        bad += bool(errs)
    return 1 if bad else 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--check":
        sys.exit(_check_main(sys.argv[2:]))
    if len(sys.argv) > 1 and sys.argv[1] == "--compare":
        sys.exit(_compare_main(sys.argv[2:]))
    if len(sys.argv) > 1 and sys.argv[1] == "--history":
        sys.exit(_history_main(sys.argv[2:]))
    sys.exit(main())
