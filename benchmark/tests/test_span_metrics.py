"""The readers of the program's phase steps (``pad_ms``, ``h2d_ms``,
``d2h_ms``, ``land_ms``) and the idle-gap labels under a step span.  Run:
``python -m pytest benchmark/tests -q``."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run, trace

MS = 1_000_000  # ns
READS = {"pad_ms": ["partition.pad"], "h2d_ms": ["partition.put", "spmd_sort.h2d"],
         "d2h_ms": ["assemble.d2h"], "land_ms": ["assemble.land"]}


def _reader(name):
    return run.load_module(os.path.join(run.HERE, "metrics", name + ".py"))


def _run(phases):
    jobs = [run.Job(0.0, 1.0, 400, True, p) for p in phases]
    return run.Run(jobs, 0.0, None, 100, 1, 4, "TPU v5 lite")


HOST_JOBS = [
    {"partition": 0.30, "partition.pad": 0.10, "partition.put": 0.19,
     "spmd_sort": 0.20, "spmd_sort.h2d": 0.05, "spmd_sort.program": 0.15,
     "assemble": 0.50, "assemble.d2h": 0.30, "assemble.land": 0.20},
    {"partition": 0.34, "partition.pad": 0.14, "partition.put": 0.19,
     "spmd_sort": 0.22, "spmd_sort.h2d": 0.07, "spmd_sort.program": 0.15,
     "assemble": 0.52, "assemble.d2h": 0.36, "assemble.land": 0.16},
]


@pytest.mark.parametrize("name", sorted(READS))
def test_each_reader_is_the_mean_per_job_of_its_steps(name):
    want = sum(j[k] for j in HOST_JOBS for k in READS[name]) / len(HOST_JOBS) * 1e3
    assert _reader(name).read(_run(HOST_JOBS)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["d2h_ms", "land_ms"])
def test_a_cell_without_the_step_reads_nothing(name):
    """The resident sink has no ``assemble``."""
    resident = [{k: v for k, v in j.items() if not k.startswith("assemble")} for j in HOST_JOBS]
    assert _reader(name).read(_run(resident)) is None


@pytest.mark.parametrize("name", sorted(READS))
def test_a_program_without_steps_reads_nothing(name):
    """A program whose phases have no steps (an older tree) gives no number."""
    phases_only = [{k: v for k, v in j.items() if "." not in k} for j in HOST_JOBS]
    assert _reader(name).read(_run(phases_only)) is None


def test_a_step_span_labels_the_gap_under_it():
    """In the recorded form: ``dsort:partition.pad`` nested in
    ``dsort:partition`` takes the idle time under it; the rest of the
    parent, its self time, stays ``dsort:partition``."""
    spans = [["bench:sort_host", 0, 100], ["dsort:partition", 0, 40],
             ["dsort:partition.pad", 0, 25], ["dsort:partition.put", 25, 10],
             ["dsort:spmd_sort", 40, 60], ["dsort:spmd_sort.h2d", 40, 10],
             ["dsort:spmd_sort.program", 50, 50]]
    ops = [["fusion.1", "fusion", 60, 30]]
    ms = lambda evs: [[*e[:-2], e[-2] * MS, e[-1] * MS] for e in evs]  # noqa: E731
    s = trace.reduce({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "spans", "events": ms(spans)}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": ms([["jit__sample_sort_shard(1)", 60, 30]])},
            {"name": "XLA Ops", "events": ms(ops)}]}]})
    assert dict(s.idle_gaps()) == pytest.approx({
        "dsort:partition.pad": 0.025, "dsort:partition.put": 0.010,
        "dsort:partition": 0.005, "dsort:spmd_sort.h2d": 0.010,
        "dsort:spmd_sort.program": 0.020})


def test_rehearsal_reports_the_four_step_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "int32-uniform.host",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    got = out["metrics"]
    assert set(READS) <= set(got) and all(got[k]["unit"] == "ms" for k in READS)
    assert got["pad_ms"]["value"] <= got["partition_ms"]["value"]
    assert got["d2h_ms"]["value"] + got["land_ms"]["value"] <= got["assemble_ms"]["value"]
