"""Event-journal tests: ordering, thread-safety, the two consumers
(Chrome-trace export, `dsort report`), the counter registry, and the
`dsort run --journal` -> `dsort report` round trip on a healthy job.
"""

import json
import os
import re
import threading

import numpy as np
import pytest

from dsort_tpu.utils.events import (
    COUNTERS,
    EVENT_TYPES,
    EventLog,
    format_report,
    to_chrome_trace,
)


def test_emit_orders_and_stamps():
    log = EventLog()
    log.emit("job_start", mode="spmd", n_keys=10)
    log.emit("worker_dead", worker=3)
    log.emit("job_done", n_keys=10)
    evs = log.events()
    assert [e.type for e in evs] == ["job_start", "worker_dead", "job_done"]
    assert [e.seq for e in evs] == [0, 1, 2]
    # monotonic stamps never go backwards; fields ride verbatim
    assert evs[0].mono <= evs[1].mono <= evs[2].mono
    assert evs[1].fields == {"worker": 3}


def test_emit_rejects_unregistered_type():
    with pytest.raises(ValueError, match="unregistered"):
        EventLog().emit("made_up_event")


def test_thread_safety_unique_seqs():
    log = EventLog()
    n_threads, per = 8, 200

    def emitter(w):
        for _ in range(per):
            log.emit("probe", worker=w, ok=True)

    ts = [threading.Thread(target=emitter, args=(w,)) for w in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    evs = log.events()
    assert len(evs) == n_threads * per
    assert sorted(e.seq for e in evs) == list(range(n_threads * per))
    # every thread's events all landed
    for w in range(n_threads):
        assert sum(e.fields["worker"] == w for e in evs) == per


def test_jsonl_round_trip(tmp_path):
    log = EventLog()
    log.emit("job_start", mode="taskpool", n_keys=4)
    log.emit("reassign", shard=1, frm=0, to=2)
    log.emit("job_done", n_keys=4, counters={"reassignments": 1})
    path = str(tmp_path / "j.jsonl")
    log.write_jsonl(path)
    records = EventLog.read_jsonl(path)
    assert [r["type"] for r in records] == ["job_start", "reassign", "job_done"]
    assert records[1]["frm"] == 0 and records[1]["to"] == 2
    assert records[2]["counters"] == {"reassignments": 1}


def test_flush_jsonl_appends_only_new_events(tmp_path):
    """The per-job REPL persist: each flush writes only the delta, the
    first flush truncates, and the file always equals the full journal."""
    path = str(tmp_path / "session.jsonl")
    log = EventLog()
    log.emit("job_start", mode="spmd", n_keys=1)
    log.flush_jsonl(path)
    log.emit("job_done", n_keys=1)
    log.flush_jsonl(path)
    log.flush_jsonl(path)  # nothing new: no-op, no duplicates
    records = EventLog.read_jsonl(path)
    assert [r["type"] for r in records] == ["job_start", "job_done"]
    assert [r["seq"] for r in records] == [0, 1]
    # a fresh log's first flush truncates a stale session file
    log2 = EventLog()
    log2.emit("job_start", mode="spmd", n_keys=2)
    log2.flush_jsonl(path)
    assert [r["type"] for r in EventLog.read_jsonl(path)] == ["job_start"]


def test_chrome_trace_export():
    log = EventLog()
    log.emit("phase_start", phase="partition")
    log.emit("worker_dead", worker=5)
    log.emit("phase_end", phase="partition", seconds=0.25)
    trace = to_chrome_trace([e.to_dict() for e in log.events()])
    evs = trace["traceEvents"]
    assert [e["ph"] for e in evs] == ["B", "i", "E"]
    assert evs[0]["name"] == "dsort:partition"
    assert evs[1]["args"] == {"worker": 5}
    assert evs[0]["ts"] <= evs[1]["ts"] <= evs[2]["ts"]
    json.dumps(trace)  # must serialize


def test_format_report_tables():
    log = EventLog()
    log.emit("job_start", mode="spmd", n_keys=100)
    log.emit("phase_start", phase="partition")
    log.emit("phase_end", phase="partition", seconds=0.5)
    log.emit("mesh_reform", survivors=7)
    log.emit("job_done", n_keys=100, counters={"mesh_reforms": 1})
    text = format_report([e.to_dict() for e in log.events()])
    assert "job_start" in text and "mesh_reform" in text
    assert "partition" in text and "500.000 ms" in text
    assert "mesh_reforms" in text  # counter table with registry description
    assert COUNTERS["mesh_reforms"] in text


def test_counter_registry_is_exhaustive():
    """Every `Metrics.bump` name in the package is a documented counter —
    the registry (shared by journal and README) cannot drift."""
    root = os.path.join(os.path.dirname(os.path.dirname(__file__)), "dsort_tpu")
    bumped = set()
    for dirpath, _, names in os.walk(root):
        for name in names:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(dirpath, name), encoding="utf-8") as f:
                bumped |= set(re.findall(r"\.bump\(\s*\"([a-z0-9_]+)\"", f.read()))
    assert bumped, "no counters found — did the scan break?"
    unregistered = bumped - set(COUNTERS)
    assert not unregistered, (
        f"counters bumped but not in utils.events.COUNTERS: {unregistered}"
    )


def test_event_registry_covers_issue_schema():
    """The minimum schema the observability spec names must stay registered."""
    required = {
        "attempt_start", "heartbeat_lapse", "probe", "worker_dead",
        "reassign", "mesh_reform", "capacity_retry", "checkpoint_persist",
        "checkpoint_restore", "phase_start", "phase_end", "job_done",
        "job_failed",
    }
    assert required <= set(EVENT_TYPES)


def test_phase_timer_emits_phase_events():
    from dsort_tpu.utils.metrics import Metrics, PhaseTimer

    log = EventLog()
    m = Metrics(journal=log)
    with PhaseTimer(m).phase("merge"):
        pass
    assert log.types() == ["phase_start", "phase_end"]
    end = log.events()[1]
    assert end.fields["phase"] == "merge"
    assert end.fields["seconds"] >= 0


def test_capacity_retry_journaled(mesh8):
    """The capacity-retry fault path lands on the journal: all-equal keys
    overflow one bucket at capacity_factor=1, the retry resizes, and the
    journal shows capacity_retry between attempt phases."""
    from dsort_tpu.config import JobConfig
    from dsort_tpu.parallel.sample_sort import SampleSort
    from dsort_tpu.utils.metrics import Metrics

    data = np.full(40_000, 7, np.int32)
    log = EventLog()
    m = Metrics(journal=log)
    out = SampleSort(mesh8, JobConfig(capacity_factor=1.0)).sort(data, metrics=m)
    np.testing.assert_array_equal(out, data)
    assert m.counters.get("capacity_retries", 0) >= 1
    types = log.types()
    assert "capacity_retry" in types
    ev = [e for e in log.events() if e.type == "capacity_retry"][0]
    assert ev.fields["cap_pair"] > 0 and ev.fields["observed"] > 0


def test_cli_run_journal_report_round_trip(tmp_path, capsys):
    """The acceptance path: `dsort run --journal out.jsonl` on a healthy job,
    then `dsort report out.jsonl` renders the timeline + tables, and
    `--chrome-trace` exports a loadable trace_event file."""
    from dsort_tpu import cli

    inp = tmp_path / "in.txt"
    rng = np.random.default_rng(3)
    inp.write_text("\n".join(str(x) for x in rng.integers(0, 10**6, 3000)))
    out = tmp_path / "out.txt"
    journal = tmp_path / "run.jsonl"
    trace = tmp_path / "trace.json"
    assert cli.main(["run", str(inp), "-o", str(out), "--journal",
                     str(journal)]) == 0
    assert journal.exists()
    records = EventLog.read_jsonl(str(journal))
    types = [r["type"] for r in records]
    assert types[0] == "job_start"
    assert "job_done" in types
    assert "phase_start" in types and "phase_end" in types
    # the sorted output really is sorted (the journal describes a real job)
    got = np.array([int(x) for x in out.read_text().split()])
    assert (np.diff(got) >= 0).all()
    assert cli.main(["report", str(journal), "--chrome-trace",
                     str(trace)]) == 0
    text = capsys.readouterr().out
    assert "timeline:" in text and "job_done" in text and "phases:" in text
    loaded = json.loads(trace.read_text())
    assert loaded["traceEvents"], "chrome trace must carry events"


def test_native_coord_event_line_parser():
    """runtime/native.py parses the C++ coordinator's compact event lines
    into journal-shaped records, skipping malformed lines."""
    from dsort_tpu.runtime.native import parse_coord_events

    text = (
        "t=12.500000 ev=worker_join w=0\n"
        "t=12.600000 ev=attempt_start w=0 task=3\n"
        "garbage line without fields\n"
        "t=12.700000 ev=worker_dead w=0\n"
        "t=12.800000 ev=reassign w=0 task=3\n"
        "t=12.900000 ev=unknown_kind w=1\n"
    )
    recs = parse_coord_events(text)
    assert [r["type"] for r in recs] == [
        "worker_join", "attempt_start", "worker_dead", "reassign",
    ]
    assert recs[1]["task"] == 3 and recs[1]["worker"] == 0
    # parsed records ingest into a journal under registered types
    log = EventLog()
    for r in recs:
        fields = {k: v for k, v in r.items() if k not in ("type", "t", "mono")}
        log.ingest(r["t"], r["mono"], r["type"], **fields)
    assert log.types() == [r["type"] for r in recs]
