"""Out-of-core wave pipeline (ISSUE 10, ARCHITECTURE §10): correctness,
(wave, run)-granular resume, the fault matrix (mid-ring device loss,
process kill between waves, stale manifests), the TeraSort record waves,
CLI/conf wiring, the analyzer's wave verdict, and the §10 schema."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from dsort_tpu.models.wave_sort import (
    DIE_AFTER_WAVE_ENV,
    ExternalWaveSort,
    ExternalWaveTeraSort,
    sample_global_splitters,
)
from dsort_tpu.utils.events import EventLog
from dsort_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh(n=8):
    from dsort_tpu.parallel.mesh import local_device_mesh

    return local_device_mesh(n)


def _metered():
    return Metrics(journal=EventLog())


# -- correctness -------------------------------------------------------------


@pytest.mark.parametrize(
    "n,wave,p",
    [(0, 64, 8), (1, 64, 8), (1000, 300, 8), (20000, 4096, 8),
     (5000, 777, 4), (4096, 4096, 8)],
)
def test_wave_matches_oracle(tmp_path, devices, n, wave, p):
    rng = np.random.default_rng(n + wave)
    data = rng.integers(-(2**31), 2**31 - 1, n, dtype=np.int64).astype(np.int32)
    s = ExternalWaveSort(
        _mesh(p), wave_elems=wave, spill_dir=str(tmp_path), job_id=f"w{n}_{p}"
    )
    np.testing.assert_array_equal(s.sort(data), np.sort(data))


def test_wave_matches_oracle_zipf_int64(tmp_path, devices):
    from dsort_tpu.data.ingest import gen_zipf

    data = gen_zipf(30000, a=1.3, dtype=np.int64, seed=3)
    s = ExternalWaveSort(
        _mesh(8), wave_elems=5000, spill_dir=str(tmp_path), job_id="wz"
    )
    m = _metered()
    np.testing.assert_array_equal(s.sort(data, metrics=m), np.sort(data))
    # Every wave planned a ring schedule against the measured histogram.
    assert m.counters["waves_sorted"] == 6
    assert m.counters["exchange_ring_steps"] == 6 * 7
    assert "skew_report" in m.journal.types()


def test_wave_float_nan_keys(tmp_path, devices):
    rng = np.random.default_rng(9)
    data = rng.standard_normal(12000).astype(np.float32)
    data[::211] = np.nan
    data[::301] = -0.0
    s = ExternalWaveSort(
        _mesh(8), wave_elems=2500, spill_dir=str(tmp_path), job_id="wf"
    )
    out = s.sort(data)
    expect = np.sort(data)
    # NaNs sort last like np.sort; -0.0/+0.0 keep value equality.
    np.testing.assert_array_equal(
        out.view(np.uint32), expect.view(np.uint32)
    )


def test_wave_sentinel_valued_keys(tmp_path, devices):
    sent = np.iinfo(np.int32).max
    rng = np.random.default_rng(4)
    data = rng.integers(-100, 100, 3000).astype(np.int32)
    data[::17] = sent  # real max-valued keys must survive the pad trims
    s = ExternalWaveSort(
        _mesh(8), wave_elems=512, spill_dir=str(tmp_path), job_id="ws"
    )
    np.testing.assert_array_equal(s.sort(data), np.sort(data))


def test_wave_no_overlap_matches(tmp_path, devices):
    rng = np.random.default_rng(5)
    data = rng.integers(0, 10**6, 16000).astype(np.int32)
    s = ExternalWaveSort(
        _mesh(8), wave_elems=3000, spill_dir=str(tmp_path), job_id="wno",
        overlap=False,
    )
    np.testing.assert_array_equal(s.sort(data), np.sort(data))


def test_wave_binary_file_roundtrip_memmap(tmp_path, devices):
    rng = np.random.default_rng(6)
    data = rng.integers(-(2**31), 2**31 - 1, 20000, dtype=np.int64).astype(
        np.int32
    )
    in_path, out_path = str(tmp_path / "in.bin"), str(tmp_path / "out.bin")
    data.tofile(in_path)
    s = ExternalWaveSort(
        _mesh(8), wave_elems=4096, spill_dir=str(tmp_path / "spill"),
        job_id="wfile",
    )
    s.sort_binary_file(in_path, out_path, dtype=np.int32)
    np.testing.assert_array_equal(
        np.fromfile(out_path, dtype=np.int32), np.sort(data)
    )


def test_splitters_are_deterministic_and_sorted(devices):
    rng = np.random.default_rng(7)
    data = rng.integers(-(10**6), 10**6, 50000).astype(np.int32)
    s1 = sample_global_splitters(data, len(data), 8)
    s2 = sample_global_splitters(data, len(data), 8)
    np.testing.assert_array_equal(s1, s2)
    assert len(s1) == 7 and (np.diff(s1) >= 0).all()


# -- resume contract: (wave, run) granularity --------------------------------


def test_wave_full_resume_restores_every_run(tmp_path, devices):
    rng = np.random.default_rng(8)
    data = rng.integers(-(10**6), 10**6, 24000).astype(np.int32)
    s1 = ExternalWaveSort(
        _mesh(8), wave_elems=4000, spill_dir=str(tmp_path), job_id="wr"
    )
    m1 = _metered()
    np.testing.assert_array_equal(s1.sort(data, metrics=m1), np.sort(data))
    assert m1.counters["runs_sorted"] == 6 * 8
    s2 = ExternalWaveSort(
        _mesh(8), wave_elems=4000, spill_dir=str(tmp_path), job_id="wr"
    )
    m2 = _metered()
    np.testing.assert_array_equal(s2.sort(data, metrics=m2), np.sort(data))
    assert m2.counters["runs_resumed"] == 6 * 8
    assert m2.counters.get("runs_sorted", 0) == 0
    # resume=False clears and redoes the work.
    s3 = ExternalWaveSort(
        _mesh(8), wave_elems=4000, spill_dir=str(tmp_path), job_id="wr",
        resume=False,
    )
    m3 = _metered()
    np.testing.assert_array_equal(s3.sort(data, metrics=m3), np.sort(data))
    assert m3.counters["runs_sorted"] == 6 * 8


def test_wave_partial_resume_redoes_only_missing_runs(tmp_path, devices):
    """Deleting two runs of one wave re-sorts exactly those two runs — the
    (wave, run) granularity the manifest contract promises."""
    rng = np.random.default_rng(10)
    data = rng.integers(-(10**6), 10**6, 24000).astype(np.int32)
    s = ExternalWaveSort(
        _mesh(8), wave_elems=4000, spill_dir=str(tmp_path), job_id="wp"
    )
    s.sort(data)
    os.remove(str(tmp_path / "wp" / "aux_w00002_00003.npy"))
    os.remove(str(tmp_path / "wp" / "aux_w00002_00005.npy"))
    s2 = ExternalWaveSort(
        _mesh(8), wave_elems=4000, spill_dir=str(tmp_path), job_id="wp"
    )
    m = _metered()
    np.testing.assert_array_equal(s2.sort(data, metrics=m), np.sort(data))
    assert m.counters["wave_runs_resorted"] == 2
    assert m.counters["runs_resumed"] == 6 * 8 - 2
    assert m.counters["wave_resort_keys"] < len(data)
    ev = [e for e in m.journal.events() if e.type == "wave_resume"]
    assert len(ev) == 1 and ev[0].fields["wave"] == 2
    assert ev[0].fields["missing"] == 2 and ev[0].fields["present"] == 6


def test_wave_stale_manifest_detection(tmp_path, devices):
    """Same job_id, different data / different wave layout: the store is
    cleared instead of serving another job's runs — at (wave, run)
    granularity nothing survives a layout change."""
    rng = np.random.default_rng(11)
    data = rng.integers(-(10**6), 10**6, 12000).astype(np.int32)
    s = ExternalWaveSort(
        _mesh(8), wave_elems=3000, spill_dir=str(tmp_path), job_id="wstale"
    )
    s.sort(data)
    flipped = data.copy()
    flipped[0] ^= 1
    s2 = ExternalWaveSort(
        _mesh(8), wave_elems=3000, spill_dir=str(tmp_path), job_id="wstale"
    )
    m2 = _metered()
    np.testing.assert_array_equal(s2.sort(flipped, metrics=m2), np.sort(flipped))
    assert "runs_resumed" not in m2.counters  # cleared, not trusted
    # Changed wave layout (same data) is equally stale.
    s3 = ExternalWaveSort(
        _mesh(8), wave_elems=2000, spill_dir=str(tmp_path), job_id="wstale"
    )
    m3 = _metered()
    np.testing.assert_array_equal(s3.sort(flipped, metrics=m3), np.sort(flipped))
    assert "runs_resumed" not in m3.counters


# -- the fault matrix --------------------------------------------------------


@pytest.mark.parametrize("n,wave,fail_at", [
    (24000, 4000, 3), (1 << 16, 1 << 13, 5),
], ids=["6_waves", "8_waves"])
def test_wave_mid_ring_device_loss_repairs_in_flight(tmp_path, devices, n,
                                                     wave, fail_at):
    """A device lost inside wave k's ring (the fault_hook seam, same
    injection point as the scheduler's mid-ring drill) repairs at run
    granularity IN FLIGHT: that wave's runs re-sort on the host, later
    waves keep using the mesh, and the output stays bit-identical."""
    from dsort_tpu.scheduler.fault import WorkerFailure

    rng = np.random.default_rng(12)
    data = rng.integers(-(10**6), 10**6, n).astype(np.int32)
    num_waves = n // wave
    s = ExternalWaveSort(
        _mesh(8), wave_elems=wave, spill_dir=str(tmp_path), job_id="wfault"
    )
    calls = {"n": 0}

    def hook():
        calls["n"] += 1
        if calls["n"] == fail_at:
            raise WorkerFailure("injected mid-ring device loss")

    s.fault_hook = hook
    m = _metered()
    np.testing.assert_array_equal(s.sort(data, metrics=m), np.sort(data))
    assert m.counters["wave_runs_resorted"] == 8  # one wave's runs
    # the rest stayed on the mesh
    assert m.counters["waves_sorted"] == num_waves - 1
    types = m.journal.types()
    assert "wave_resume" in types
    # resume_fraction contract: one wave's runs of all the waves' runs.
    assert (m.counters["wave_runs_resorted"] / (num_waves * 8)
            <= 1 / num_waves + 1 / 64)


def test_wave_process_kill_between_waves_resumes(tmp_path, devices):
    """The restart-resume drill: a process killed after wave 1 persisted
    leaves waves 0-1 durable; the re-run restores them and sorts only the
    remaining waves — resume_fraction ≤ 1/num_waves + one wave's slack
    over the INTERRUPTED portion, and the final output is bit-identical."""
    rng = np.random.default_rng(13)
    data = rng.integers(-(10**6), 10**6, 24000).astype(np.int32)
    in_path = str(tmp_path / "in.bin")
    data.tofile(in_path)
    script = (
        "import numpy as np, jax\n"
        "jax.config.update('jax_enable_x64', True)\n"
        "from dsort_tpu.parallel.mesh import local_device_mesh\n"
        "from dsort_tpu.models.wave_sort import ExternalWaveSort\n"
        "s = ExternalWaveSort(local_device_mesh(8), wave_elems=4000,\n"
        f"    spill_dir={str(tmp_path)!r}, job_id='wkill')\n"
        f"s.sort_binary_file({in_path!r}, {str(tmp_path / 'out.bin')!r},\n"
        "    dtype=np.int32)\n"
    )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        **{DIE_AFTER_WAVE_ENV: "1"},
    )
    r = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=560,
    )
    assert r.returncode == 17, r.stderr[-2000:]
    done = {
        name for name in os.listdir(tmp_path / "wkill")
        if name.startswith("aux_w")
    }
    # Waves 0 and 1 persisted all 8 runs each; later waves never ran.
    assert len(done) == 16, sorted(done)
    s2 = ExternalWaveSort(
        _mesh(8), wave_elems=4000, spill_dir=str(tmp_path), job_id="wkill"
    )
    m = _metered()
    np.testing.assert_array_equal(s2.sort(data, metrics=m), np.sort(data))
    assert m.counters["runs_resumed"] == 16
    assert m.counters["runs_sorted"] == 4 * 8  # only the unfinished waves
    # No partial wave here, so the run-granular repair path stayed idle...
    assert "wave_runs_resorted" not in m.counters
    # ...and the resumed fraction of the whole job is exactly 4/6 waves.
    assert m.counters["runs_sorted"] / (6 * 8) == pytest.approx(4 / 6)


# -- TeraSort records through the wave pipeline ------------------------------


def _tera_oracle(raw):
    from dsort_tpu.data.ingest import _pack_be64, terasort_secondary

    order = np.lexsort(
        (terasort_secondary(raw[:, 8:10]), _pack_be64(raw[:, :8]))
    )
    return raw[order]


def test_wave_terasort_matches_oracle(tmp_path, devices):
    from dsort_tpu.data.ingest import gen_terasort_file

    in_path = str(tmp_path / "in.bin")
    out_path = str(tmp_path / "out.bin")
    gen_terasort_file(in_path, 20000, seed=14)
    t = ExternalWaveTeraSort(
        _mesh(8), wave_recs=4096, spill_dir=str(tmp_path / "spill"),
        job_id="tw",
    )
    m = _metered()
    t.sort_file(in_path, out_path, metrics=m)
    raw = np.fromfile(in_path, np.uint8).reshape(-1, 100)
    got = np.fromfile(out_path, np.uint8).reshape(-1, 100)
    np.testing.assert_array_equal(got, _tera_oracle(raw))
    assert m.counters["waves_sorted"] == 5  # mesh-parallel run generation


def test_wave_terasort_partial_resume(tmp_path, devices):
    from dsort_tpu.data.ingest import gen_terasort_file

    in_path = str(tmp_path / "in.bin")
    out_path = str(tmp_path / "out.bin")
    gen_terasort_file(in_path, 12000, seed=15)
    t = ExternalWaveTeraSort(
        _mesh(8), wave_recs=3000, spill_dir=str(tmp_path), job_id="twp"
    )
    t.sort_file(in_path, out_path)
    os.remove(str(tmp_path / "twp" / "aux_w00001_00004.npy"))
    t2 = ExternalWaveTeraSort(
        _mesh(8), wave_recs=3000, spill_dir=str(tmp_path), job_id="twp"
    )
    m = _metered()
    t2.sort_file(in_path, out_path, metrics=m)
    raw = np.fromfile(in_path, np.uint8).reshape(-1, 100)
    got = np.fromfile(out_path, np.uint8).reshape(-1, 100)
    np.testing.assert_array_equal(got, _tera_oracle(raw))
    assert m.counters["wave_runs_resorted"] == 1
    assert m.counters["runs_resumed"] == 4 * 8 - 1


# -- CLI / conf --------------------------------------------------------------


def test_cli_external_mesh_wave_with_journal_and_analyze(tmp_path, devices, capsys):
    from dsort_tpu import cli
    from dsort_tpu.obs.analyze import analyze_records
    from dsort_tpu.utils.events import EventLog as EL

    rng = np.random.default_rng(16)
    data = rng.integers(-(2**31), 2**31 - 1, 16000, dtype=np.int64).astype(
        np.int32
    )
    in_path, out_path = str(tmp_path / "in.bin"), str(tmp_path / "out.bin")
    jpath = str(tmp_path / "journal.jsonl")
    data.tofile(in_path)
    rc = cli.main([
        "external", in_path, "-o", out_path, "--mesh", "8",
        "--wave-elems", "4000", "--spill-dir", str(tmp_path / "spill"),
        "--journal", jpath,
    ])
    assert rc == 0
    np.testing.assert_array_equal(
        np.fromfile(out_path, dtype=np.int32), np.sort(data)
    )
    # --journal parity with `dsort run`: the wave events landed, and the
    # analyzer renders the wave plane from them.
    records = EL.read_jsonl(jpath)
    types = {r["type"] for r in records}
    assert {"wave_start", "wave_done", "skew_report"} <= types
    verdict = analyze_records(records)
    assert verdict["waves"] is not None
    assert verdict["waves"]["count"] == 4
    assert verdict["waves"]["gating"] is not None
    assert verdict["waves"]["slowest"]["seconds"] >= 0
    # The wave phases land in the ordinary waterfall.
    assert "wave_exchange" in (verdict["phases"].get("p0") or {})
    # And `dsort report --analyze` renders it end to end.
    rc = cli.main(["report", jpath, "--analyze"])
    out = capsys.readouterr().out
    assert rc == 0 and "waves" in out


def test_external_conf_keys_and_flag_precedence(tmp_path, devices):
    from dsort_tpu.config import ConfigError, ExternalConfig, SortConfig

    conf = tmp_path / "ext.conf"
    conf.write_text(
        "EXTERNAL_WAVE_ELEMS=5000\nEXTERNAL_RUN_ELEMS=2048\nEXTERNAL_MESH=4\n"
    )
    cfg = SortConfig.from_conf_file(str(conf))
    assert cfg.external.wave_elems == 5000
    assert cfg.external.run_elems == 2048
    assert cfg.external.mesh == 4
    assert SortConfig().external.mesh is None
    with pytest.raises(ConfigError):
        ExternalConfig(wave_elems=1)
    # Flag precedence over conf (same contract as SERVE_*): the CLI runs
    # the wave path with the conf mesh but the flag's wave size.
    from dsort_tpu import cli

    rng = np.random.default_rng(17)
    data = rng.integers(0, 1 << 20, 8000).astype(np.int32)
    in_path, out_path = str(tmp_path / "in.bin"), str(tmp_path / "o.bin")
    data.tofile(in_path)
    jpath = str(tmp_path / "j.jsonl")
    rc = cli.main([
        "external", in_path, "-o", out_path, "--conf", str(conf),
        "--wave-elems", "2000", "--spill-dir", str(tmp_path / "sp"),
        "--journal", jpath,
    ])
    assert rc == 0
    np.testing.assert_array_equal(
        np.fromfile(out_path, dtype=np.int32), np.sort(data)
    )
    from dsort_tpu.utils.events import EventLog as EL

    waves = [
        r for r in EL.read_jsonl(jpath) if r["type"] == "wave_start"
    ]
    assert len(waves) == 4  # 8000 keys / flag's 2000, on the conf's mesh


def test_cli_terasort_external_mesh(tmp_path, devices):
    from dsort_tpu import cli
    from dsort_tpu.data.ingest import gen_terasort_file

    in_path = str(tmp_path / "in.bin")
    out_path = str(tmp_path / "out.bin")
    gen_terasort_file(in_path, 8000, seed=18)
    rc = cli.main([
        "terasort", in_path, "-o", out_path, "--external", "--mesh", "8",
        "--run-recs", "2000", "--spill-dir", str(tmp_path / "spill"),
        "--job-id", "twcli",
    ])
    assert rc == 0
    raw = np.fromfile(in_path, np.uint8).reshape(-1, 100)
    got = np.fromfile(out_path, np.uint8).reshape(-1, 100)
    np.testing.assert_array_equal(got, _tera_oracle(raw))


def test_wave_file_eight_times_the_budget(tmp_path, devices):
    """A key file 8x the per-wave device budget sorts from a memmap into
    a caller's buffer, with and without the overlap."""
    from dsort_tpu.data.ingest import gen_uniform

    n, num_waves = 1 << 16, 8
    data = gen_uniform(n, seed=3)
    data.tofile(tmp_path / "in.bin")
    keys = np.memmap(tmp_path / "in.bin", dtype=np.int32, mode="r")
    for overlap in (True, False):
        s = ExternalWaveSort(
            _mesh(8), wave_elems=n // num_waves,
            spill_dir=str(tmp_path / f"spill{overlap}"), job_id="w8x",
            resume=False, overlap=overlap,
        )
        m = _metered()
        out = np.empty(n, np.int32)
        s.sort(keys, out=out, metrics=m)
        np.testing.assert_array_equal(out, np.sort(data))
        assert m.counters["waves_sorted"] == num_waves


def test_architecture_documents_wave_plane():
    """§10's contract is test-enforced like §7/§8/§9: the wave state
    machine's event names, the manifest schema fields, the run-file
    pattern, and the resume vocabulary all appear verbatim."""
    from dsort_tpu.utils.events import COUNTERS, EVENT_TYPES

    arch = open(
        os.path.join(REPO, "ARCHITECTURE.md"), encoding="utf-8"
    ).read()
    assert "## 10. Out-of-core wave plane" in arch
    for etype in ("wave_start", "wave_done", "wave_resume"):
        assert f"`{etype}`" in arch, f"event {etype} undocumented"
        assert etype in EVENT_TYPES
    for counter in ("waves_sorted", "wave_runs_resorted", "wave_resort_keys"):
        assert f"`{counter}`" in arch, f"counter {counter} undocumented"
        assert counter in COUNTERS
    for field in ("num_waves", "num_ranges", "wave_elems", "splitters",
                  "fingerprint", "storage_dtype"):
        assert f"`{field}`" in arch, f"manifest field {field} undocumented"
    for term in ("aux_w", "resume_fraction", "--wave-elems", "--mesh",
                 "over_hbm_factor", "DSORT_WAVE_DIE_AFTER_WAVE",
                 "EXTERNAL_WAVE_ELEMS"):
        assert term in arch, f"{term} missing from §10"
    # The analyzer's wave verdict is part of the §9 contract too.
    assert "`waves`" in arch
