"""CLI + checkpoint/recovery tests."""

import os

import numpy as np
import pytest

from dsort_tpu.checkpoint import ShardCheckpoint
from dsort_tpu.cli import main as cli_main
from dsort_tpu.config import JobConfig
from dsort_tpu.data.ingest import gen_uniform, read_ints_file, write_ints_file
from dsort_tpu.scheduler import DeviceExecutor, FaultInjector, JobFailedError, Scheduler
from dsort_tpu.utils.metrics import Metrics


def test_cli_run_roundtrip(tmp_path):
    inp, outp = tmp_path / "in.txt", tmp_path / "out.txt"
    data = gen_uniform(5_000, seed=31)
    write_ints_file(inp, data)
    rc = cli_main(["run", str(inp), "-o", str(outp), "--mode", "spmd"])
    assert rc == 0
    np.testing.assert_array_equal(read_ints_file(outp), np.sort(data))


def test_cli_gen_and_run_taskpool(tmp_path):
    inp, outp = tmp_path / "g.txt", tmp_path / "o.txt"
    assert cli_main(["gen", "3000", "-o", str(inp), "--dist", "zipf"]) == 0
    assert cli_main(["run", str(inp), "-o", str(outp), "--mode", "taskpool",
                     "--dtype", "int64"]) == 0
    data = read_ints_file(inp, dtype=np.int64)
    np.testing.assert_array_equal(read_ints_file(outp, dtype=np.int64), np.sort(data))


def test_cli_serve_repl(tmp_path, monkeypatch, capsys):
    # The reference REPL workflow: two jobs then 'exit' (server.c:160-167).
    inp1, inp2, outp = tmp_path / "a.txt", tmp_path / "b.txt", tmp_path / "out.txt"
    d1, d2 = gen_uniform(100, seed=1), gen_uniform(200, seed=2)
    write_ints_file(inp1, d1)
    write_ints_file(inp2, d2)
    lines = iter([str(inp1), "not_a_file.txt", str(inp2), "exit"])
    monkeypatch.setattr("builtins.input", lambda *_: next(lines))
    rc = cli_main(["serve", "-o", str(outp), "--mode", "local"])
    assert rc == 0  # the bad job must not kill the server
    np.testing.assert_array_equal(read_ints_file(outp), np.sort(d2))


def test_shard_checkpoint_roundtrip(tmp_path):
    ck = ShardCheckpoint(str(tmp_path), "job1")
    assert not ck.has(0)
    arr = np.arange(10, dtype=np.int64)
    ck.save(0, arr)
    ck.save(3, arr * 2)
    assert ck.has(0) and ck.has(3) and not ck.has(1)
    np.testing.assert_array_equal(ck.load(3), arr * 2)
    assert ck.completed_shards() == [0, 3]
    ck.write_manifest(4, np.int64, 40)
    assert ck.manifest()["num_shards"] == 4
    ck.clear()
    assert ck.completed_shards() == []


def test_shard_checkpoint_namespaces_clear_independently(tmp_path):
    """`clear_shards` drops ONLY the shard namespace and `clear_ranges` only
    the ranges — the multihost recovery paths rely on that separation (the
    kv payload halves and the resume publish channel live in shards; a
    stale-clear must drop both but a range rewrite must not touch a
    concurrent reader's shards)."""
    ck = ShardCheckpoint(str(tmp_path), "jobns")
    ck.save(0, np.arange(4, dtype=np.int32))
    ck.save_range(1, np.arange(6, dtype=np.int32))
    ck.clear_shards()
    assert ck.completed_shards() == []
    assert ck.completed_ranges() == [1]
    ck.save(2, np.arange(3, dtype=np.int32))
    ck.clear_ranges()
    assert ck.completed_ranges() == []
    assert ck.completed_shards() == [2]


def test_shard_checkpoint_mmap_reads(tmp_path):
    """`load_mmap` / `load_range_mmap` return mmap-backed arrays equal to
    their np.load twins — the O(chunk) restore path depends on them."""
    ck = ShardCheckpoint(str(tmp_path), "jobmm")
    a = np.arange(1000, dtype=np.int64)
    ck.save(0, a)
    ck.save_range(2, a[::-1].copy())
    m = ck.load_mmap(0)
    r = ck.load_range_mmap(2)
    assert isinstance(m, np.memmap) and isinstance(r, np.memmap)
    np.testing.assert_array_equal(np.asarray(m), a)
    np.testing.assert_array_equal(np.asarray(r), a[::-1])
    # Slices materialize only the touched region (basic contract check).
    np.testing.assert_array_equal(np.asarray(r[10:20]), a[::-1][10:20])


def test_merge_split_and_slice_parts():
    """The resume path's rank-bisection merge slicing is exact on ragged
    parts with duplicate keys across the split boundary."""
    from dsort_tpu.parallel.distributed import (
        _CatParts,
        _merge_slice,
        _merge_split,
    )

    rng = np.random.default_rng(7)
    a_flat = np.sort(rng.integers(0, 50, 155).astype(np.int32))
    a_parts = []  # split the sorted stream into ragged consecutive parts
    off = 0
    for n in (17, 0, 80, 58):
        a_parts.append(a_flat[off : off + n])
        off += n
    b = np.sort(rng.integers(0, 50, 71).astype(np.int32))
    a = _CatParts(a_parts)
    merged = np.sort(np.concatenate([a_flat, b]))
    total = len(merged)
    for start, stop in [(0, total), (0, 0), (13, 13), (1, total - 1),
                        (total // 3, 2 * total // 3)]:
        got = _merge_slice(a, _CatParts([b]), start, stop)
        np.testing.assert_array_equal(got, merged[start:stop])
    for k in (0, 1, total // 2, total):
        i, j = _merge_split(a, _CatParts([b]), k)
        assert i + j == k


def test_global_fingerprint_empty_host_layout_stable():
    """An EMPTY-ingest host must compute the same dtype tag (and row
    layout) as its peers — widths come from metadata, never inferred from
    the data — or resume control flow diverges across processes and the
    barriers deadlock (r5 review finding)."""
    from dsort_tpu.parallel.distributed import _global_fingerprint

    k = np.arange(5, dtype=np.uint64)
    v = np.zeros((5, 92), np.uint8)
    fp_full, total_full = _global_fingerprint(k, payload=v)
    fp_empty, total_empty = _global_fingerprint(k[:0], payload=v[:0])
    # fp format is "total:dt:checksum" — the dt segment must match.
    assert fp_full.split(":")[1] == fp_empty.split(":")[1]
    assert total_full == 5 and total_empty == 0
    # Keys-only path too.
    fk, _ = _global_fingerprint(k)
    fe, _ = _global_fingerprint(k[:0])
    assert fk.split(":")[1] == fe.split(":")[1]


def test_job_recovery_skips_completed_shards(tmp_path):
    """Fail a job midway, then re-run: only lost shards are re-sorted."""
    data = gen_uniform(8_000, seed=33)
    job = JobConfig(
        settle_delay_s=0.01, checkpoint_dir=str(tmp_path), heartbeat_timeout_s=5.0
    )
    inj = FaultInjector()
    sched = Scheduler(DeviceExecutor(injector=inj), job)
    w = sched.executor.num_workers
    # Run 1: workers 4..7 dead AND worker 0 dies after 3 successful shards —
    # engineered instead: kill everything so some shards fail after others
    # complete.  Simplest deterministic split: fail shards on workers >= 2 by
    # killing 2..7; shards 0,1 complete and checkpoint, rest reassign to 0/1
    # and also complete... so instead kill ALL after first exchange: use
    # one-shot failures on workers 2..7 and permanent kill on 0..1 swapped.
    for i in range(2, w):
        inj.kill(i)
    out1 = sched.run_job(data, job_id="jobA")  # completes via reassignment
    np.testing.assert_array_equal(out1, np.sort(data))
    # Run 2 of the same job: every shard restores from checkpoint; even with
    # ALL workers dead the job succeeds without any compute.
    inj2 = FaultInjector()
    for i in range(w):
        inj2.kill(i)
    sched2 = Scheduler(DeviceExecutor(injector=inj2), job)
    m = Metrics()
    out2 = sched2.run_job(data, metrics=m, job_id="jobA")
    np.testing.assert_array_equal(out2, np.sort(data))
    assert m.counters["shards_restored"] == w
    # Without the checkpoint the same scheduler fails cleanly.
    with pytest.raises(JobFailedError):
        sched2.run_job(data, job_id="jobB")


def test_cli_terasort_binary_roundtrip(tmp_path):
    from dsort_tpu.data.ingest import read_terasort_file

    inp, outp = tmp_path / "t.bin", tmp_path / "t_out.bin"
    assert cli_main(["gen", "2000", "-o", str(inp), "--dist", "terasort"]) == 0
    assert cli_main(["terasort", str(inp), "-o", str(outp), "--workers", "8"]) == 0
    from dsort_tpu.data.ingest import terasort_secondary

    k_in, v_in = read_terasort_file(inp)
    k_out, v_out = read_terasort_file(outp)
    np.testing.assert_array_equal(k_out, np.sort(k_in))
    # output is ordered by the FULL 10-byte key (secondary breaks prefix ties)
    s_out = terasort_secondary(v_out)
    lex_ok = (k_out[1:] > k_out[:-1]) | (
        (k_out[1:] == k_out[:-1]) & (s_out[1:] >= s_out[:-1])
    )
    assert lex_ok.all()
    # full records preserved as a multiset
    assert sorted(zip(k_out.tolist(), map(bytes, v_out))) == sorted(
        zip(k_in.tolist(), map(bytes, v_in))
    )


def test_multihost_initialize_noop_without_env(monkeypatch):
    from dsort_tpu.parallel.distributed import initialize_multihost

    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    assert initialize_multihost() is False


def test_global_worker_mesh():
    from dsort_tpu.parallel.distributed import global_worker_mesh

    mesh = global_worker_mesh()
    assert mesh.shape["w"] >= 8


def test_cli_subprocess_enables_x64(tmp_path):
    """64-bit CLI paths must work in a fresh process (no conftest x64).

    Regression: `dsort external --dtype int64` / `dsort terasort` crashed
    outside the test harness because nothing enabled jax_enable_x64 before
    configs were built — the CLI must do it itself.
    """
    import os
    import subprocess
    import sys

    big = tmp_path / "big.bin"
    out = tmp_path / "big_sorted.bin"
    data = np.random.default_rng(7).integers(
        -(2**63), 2**63 - 1, 20_000, dtype=np.int64
    )
    data.tofile(big)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env.pop("JAX_ENABLE_X64", None)
    env["JAX_PLATFORMS"] = "cpu"
    subprocess.run(
        [sys.executable, "-m", "dsort_tpu.cli", "external", str(big),
         "-o", str(out), "--dtype", "int64"],
        check=True, env=env, timeout=300,
    )
    np.testing.assert_array_equal(
        np.fromfile(out, dtype=np.int64), np.sort(data)
    )


def test_cli_run_with_checkpoint_resume(tmp_path):
    """dsort run --checkpoint-dir: first run persists ranges under the
    input-derived job id; a re-run takes the full-restore path."""
    from dsort_tpu import cli
    from dsort_tpu.data.ingest import write_ints_file

    rng = np.random.default_rng(31)
    data = rng.integers(0, 10**6, 50_000).astype(np.int32)
    src = tmp_path / "ck_input.txt"
    out = tmp_path / "out.txt"
    write_ints_file(src, data)
    ck = tmp_path / "ck"
    argv = ["run", str(src), "-o", str(out), "--mode", "spmd",
            "--checkpoint-dir", str(ck)]
    assert cli.main(argv) == 0
    job_dir = ck / "ck_input.txt"
    assert job_dir.is_dir() and any(
        n.startswith("range_") for n in os.listdir(job_dir)
    )
    got1 = np.loadtxt(out, dtype=np.int64).astype(np.int32)
    np.testing.assert_array_equal(got1, np.sort(data))
    # wipe the output; the re-run restores from the checkpoint and rewrites
    out.unlink()
    assert cli.main(argv) == 0
    got2 = np.loadtxt(out, dtype=np.int64).astype(np.int32)
    np.testing.assert_array_equal(got2, np.sort(data))
    # changed data under the same filename: stale state cleared, still exact
    data2 = rng.integers(0, 10**6, 50_000).astype(np.int32)
    write_ints_file(src, data2)
    assert cli.main(argv) == 0
    got3 = np.loadtxt(out, dtype=np.int64).astype(np.int32)
    np.testing.assert_array_equal(got3, np.sort(data2))


def test_cli_taskpool_checkpoint_flag(tmp_path):
    from dsort_tpu import cli
    from dsort_tpu.data.ingest import write_ints_file

    rng = np.random.default_rng(33)
    data = rng.integers(0, 1000, 9_000).astype(np.int32)
    src = tmp_path / "tp_in.txt"
    out = tmp_path / "tp_out.txt"
    write_ints_file(src, data)
    argv = ["run", str(src), "-o", str(out), "--mode", "taskpool",
            "--checkpoint-dir", str(tmp_path / "ck2"), "--job-id", "tpjob"]
    assert cli.main(argv) == 0
    assert any(
        n.startswith("shard_")
        for n in os.listdir(tmp_path / "ck2" / "tpjob")
    )
    got = np.loadtxt(out, dtype=np.int64).astype(np.int32)
    np.testing.assert_array_equal(got, np.sort(data))


def test_cli_job_id_path_escape_rejected(tmp_path):
    """'..' or separator job ids must be refused, not resolved (a '..' id
    plus the stale-state clear() would rmtree the checkpoint PARENT)."""
    from dsort_tpu import cli

    src = tmp_path / "x.txt"
    write_ints_file(src, np.arange(10, dtype=np.int32))
    for bad in ("..", ".", "a/b", "a\\b", "..."):
        with pytest.raises(SystemExit):
            cli.main([
                "run", str(src), "-o", str(tmp_path / "o.txt"),
                "--checkpoint-dir", str(tmp_path / "ck"), "--job-id", bad,
            ])
    with pytest.raises(ValueError):
        ShardCheckpoint(str(tmp_path / "ck"), "..")


def test_cli_conf_plus_flag_keeps_conf_settings(tmp_path):
    """A CLI override must not silently drop unrelated conf-file settings."""
    from dsort_tpu import cli

    conf = tmp_path / "c.conf"
    conf.write_text("OVERSAMPLE=64\nCAPACITY_FACTOR=3.0\nOUTPUT_PATH=zz.txt\n")

    class A:
        pass

    a = A()
    a.conf = str(conf)
    a.workers = None
    a.dtype = None
    a.kernel = None
    a.checkpoint_dir = str(tmp_path / "ck")
    cfg = cli._load_config(a)
    assert cfg.job.oversample == 64
    assert cfg.job.capacity_factor == 3.0
    assert cfg.output_path == "zz.txt"
    assert cfg.job.checkpoint_dir == str(tmp_path / "ck")


def test_cli_batch_sorts_many_files(tmp_path):
    """dsort batch: many files through ONE (dp, w) batched SPMD program."""
    from dsort_tpu import cli

    rng = np.random.default_rng(37)
    paths = []
    datas = []
    for i, n in enumerate((5_000, 12_345, 17)):
        d = rng.integers(-1000, 1000, n).astype(np.int32)
        p = tmp_path / f"job{i}.txt"
        write_ints_file(p, d)
        paths.append(str(p))
        datas.append(d)
    outdir = tmp_path / "sorted"
    assert cli.main(
        ["batch", *paths, "--outdir", str(outdir), "--dp", "2", "--workers", "4"]
    ) == 0
    for p, d in zip(paths, datas):
        got = read_ints_file(outdir / os.path.basename(p))
        np.testing.assert_array_equal(got, np.sort(d))


def test_cli_batch_rejects_duplicate_basenames(tmp_path):
    from dsort_tpu import cli

    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    for d in ("a", "b"):
        write_ints_file(tmp_path / d / "same.txt", np.arange(5, dtype=np.int32))
    with pytest.raises(SystemExit, match="duplicate"):
        cli.main([
            "batch", str(tmp_path / "a" / "same.txt"),
            str(tmp_path / "b" / "same.txt"), "--outdir", str(tmp_path / "o"),
        ])


def test_cli_batch_overcommit_clean_error(tmp_path):
    from dsort_tpu import cli

    src = tmp_path / "x.txt"
    write_ints_file(src, np.arange(10, dtype=np.int32))
    with pytest.raises(SystemExit, match="devices"):
        cli.main([
            "batch", str(src), "--outdir", str(tmp_path / "o"),
            "--dp", "2", "--workers", "8",
        ])


def test_cli_batch_checkpoint_resume(tmp_path):
    """`dsort batch --checkpoint-dir`: a re-run restores every completed
    file from its checkpoint instead of re-sorting (VERDICT r3 #7)."""
    import dsort_tpu.parallel.sample_sort as ssmod

    ins = []
    datas = []
    for i, n in enumerate((4_000, 1_000, 7_000)):
        p = tmp_path / f"b{i}.txt"
        d = gen_uniform(n, seed=40 + i)
        write_ints_file(p, d)
        ins.append(str(p))
        datas.append(d)
    outdir, ck = str(tmp_path / "out"), str(tmp_path / "ck")
    rc = cli_main(["batch", *ins, "--outdir", outdir, "--checkpoint-dir", ck])
    assert rc == 0
    for i, d in enumerate(datas):
        np.testing.assert_array_equal(
            read_ints_file(os.path.join(outdir, f"b{i}.txt")), np.sort(d)
        )
    # Second run: every job restores; no bucket program executes.
    calls = []
    orig = ssmod.BatchSampleSort._run_bucket
    ssmod.BatchSampleSort._run_bucket = (
        lambda self, ks, vs, cap, m: calls.append(cap) or orig(self, ks, vs, cap, m)
    )
    try:
        rc = cli_main(
            ["batch", *ins, "--outdir", outdir, "--checkpoint-dir", ck]
        )
    finally:
        ssmod.BatchSampleSort._run_bucket = orig
    assert rc == 0
    assert calls == []
    for i, d in enumerate(datas):
        np.testing.assert_array_equal(
            read_ints_file(os.path.join(outdir, f"b{i}.txt")), np.sort(d)
        )
