"""Fused Pallas ring kernel (`ops.ring_kernel`, ISSUE 11): bit-identical
output across the full dtype/distribution/kv/fault matrix, the single-launch
dispatch model, the kv single-gather wire-byte contract, and the fused wave
pipeline composing with (wave, run) resume.

The acceptance bar mirrors the lax ring's (tests/test_exchange.py), tightened
where the kernel is structurally different: ``exchange="fused"`` must be
bit-identical to ``exchange="ring"`` AND ``np.sort`` everywhere (same plan,
same measured caps, same tag plane — the merged permutation is identical, so
even kv payload buffers compare with ``array_equal``), the whole exchange
must be ONE kernel launch (`DISPATCHES_PER_FUSED_EXCHANGE`), and payload
bytes must be counted (and moved) exactly once per step.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from dsort_tpu.config import ConfigError, JobConfig
from dsort_tpu.data.ingest import gen_terasort, gen_uniform, gen_zipf
from dsort_tpu.parallel.exchange import (
    dispatches_per_exchange,
    ring_wire_bytes,
)
from dsort_tpu.parallel.sample_sort import BatchSampleSort, SampleSort
from dsort_tpu.utils.events import EventLog
from dsort_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _metered():
    return Metrics(journal=EventLog())


# ---- the dispatch model ----------------------------------------------------


def test_dispatches_per_exchange_model():
    # The structural headline: P-1 ppermute dispatches become ONE launch.
    from dsort_tpu.ops.ring_kernel import DISPATCHES_PER_FUSED_EXCHANGE

    assert DISPATCHES_PER_FUSED_EXCHANGE == 1
    assert dispatches_per_exchange("ring", 8) == 7
    assert dispatches_per_exchange("fused", 8) == 1
    assert dispatches_per_exchange("alltoall", 8) == 1
    assert dispatches_per_exchange("ring", 7) == 6


def test_fused_mesh_folds_unit_batch_axis(mesh8):
    from dsort_tpu.ops.ring_kernel import fused_mesh

    fm = fused_mesh(mesh8, "w")
    assert fm.axis_names == ("w",)
    assert int(fm.shape["w"]) == 8
    # A REAL batch axis has no 1-axis view — the batched driver falls back.
    mesh2d = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "w"))
    with pytest.raises(ValueError, match="fused"):
        fused_mesh(mesh2d, "w")


# ---- bit-identical: dtype / distribution matrix ----------------------------


@pytest.mark.parametrize("n", [64, 5000, 100_003])
def test_fused_uniform_bit_identical(mesh8, n):
    ss = SampleSort(mesh8)
    rng = np.random.default_rng(11)
    data = rng.integers(-(10**6), 10**6, n).astype(np.int32)
    r = ss.sort(data, exchange="ring")
    m = _metered()
    f = ss.sort(data, metrics=m, exchange="fused")
    np.testing.assert_array_equal(r, f)
    np.testing.assert_array_equal(f, np.sort(data))
    assert m.counters["fused_exchange_launches"] == 1
    assert m.counters["fused_exchange_steps"] == 7
    assert m.counters.get("capacity_retries", 0) == 0


def test_fused_zipf_bit_identical_int64(mesh8):
    z = gen_zipf(1 << 17, a=1.3, seed=4)
    ss = SampleSort(mesh8, JobConfig(key_dtype=np.int64))
    np.testing.assert_array_equal(
        ss.sort(z, exchange="ring"), ss.sort(z, exchange="fused")
    )


def test_fused_all_equal_keys(mesh8):
    # Degenerate skew: one destination owns everything; every step's cap is
    # the whole shard and most received slots are pure sentinel.
    ss = SampleSort(mesh8)
    data = np.full(20_000, 7, np.int32)
    np.testing.assert_array_equal(ss.sort(data, exchange="fused"), data)


def test_fused_sentinel_valued_keys(mesh8):
    ss = SampleSort(mesh8)
    rng = np.random.default_rng(3)
    data = rng.integers(-100, 100, 9000).astype(np.int32)
    data[:200] = np.iinfo(np.int32).max
    np.testing.assert_array_equal(
        ss.sort(data, exchange="fused"), np.sort(data)
    )


def test_fused_float_keys_nan(mesh8):
    ss = SampleSort(mesh8)
    rng = np.random.default_rng(6)
    data = rng.normal(size=20_000).astype(np.float32)
    data[::97] = np.nan
    got = ss.sort(data, exchange="fused")
    expect = np.sort(data)  # numpy: NaNs last
    k = len(data) - np.isnan(data).sum()
    np.testing.assert_array_equal(got[:k], expect[:k])
    assert np.isnan(got[k:]).all()


def test_fused_on_7_device_mesh():
    # Non-power-of-two P (the post-re-form mesh shape): the step offsets,
    # the merge tower's final fold and the semaphore arrays must not
    # assume pow2 P.
    mesh7 = Mesh(np.array(jax.devices()[:7]), ("w",))
    ss = SampleSort(mesh7)
    rng = np.random.default_rng(5)
    data = rng.integers(-(10**6), 10**6, 70_001).astype(np.int32)
    m = _metered()
    f = ss.sort(data, metrics=m, exchange="fused")
    np.testing.assert_array_equal(f, ss.sort(data, exchange="ring"))
    assert m.counters["fused_exchange_steps"] == 6


def test_fused_empty_bucket_and_tiny_input(mesh8):
    # Few distinct keys over 8 devices: several destinations own EMPTY
    # ranges, so whole receive slots are sentinel-only.
    ss = SampleSort(mesh8)
    rng = np.random.default_rng(9)
    data = rng.integers(0, 3, 4000).astype(np.int32)
    np.testing.assert_array_equal(
        ss.sort(data, exchange="fused"), np.sort(data)
    )
    # Tiny input: caps bottom out at the 8-element rung.
    small = rng.integers(0, 100, 40).astype(np.int32)
    np.testing.assert_array_equal(
        ss.sort(small, exchange="fused"), np.sort(small)
    )


def test_fused_single_worker_and_empty():
    ss1 = SampleSort(Mesh(np.array(jax.devices()[:1]), ("w",)))
    data = np.random.default_rng(1).integers(0, 100, 999).astype(np.int32)
    # P=1 resolves to the all_to_all short-circuit — no kernel exists.
    np.testing.assert_array_equal(
        ss1.sort(data, exchange="fused"), np.sort(data)
    )
    ss = SampleSort(Mesh(np.array(jax.devices()[:2]), ("w",)))
    assert len(ss.sort(np.empty(0, np.int32), exchange="fused")) == 0


# ---- the eager merge tower after the fused transfer -------------------------
#
# On the CPU mesh `merge_kernel="auto"` resolves to the flat re-sort, which
# the fused path defers to one combine (the lax ring's doctrine).  Forcing a
# run-merge kernel exercises the eager tower over the landed slots: the
# unequal-length final fold and the kv (key, tag) pair merge.


def test_fused_eager_tower_bitonic(mesh8):
    ss = SampleSort(mesh8, JobConfig(merge_kernel="bitonic"))
    data = gen_uniform(30_000, seed=61)
    np.testing.assert_array_equal(
        ss.sort(data, exchange="fused"), np.sort(data)
    )


def test_fused_eager_tower_bitonic_7_devices():
    from dsort_tpu.parallel.mesh import local_device_mesh

    ss = SampleSort(local_device_mesh(7), JobConfig(merge_kernel="bitonic"))
    data = gen_uniform(10_000, seed=62)
    np.testing.assert_array_equal(
        ss.sort(data, exchange="fused"), np.sort(data)
    )


def test_fused_eager_tower_kv_duplicate_and_sentinel_keys(mesh8):
    # The (key, tag) pair merge: duplicates keep every payload,
    # and real keys equal to the padding sentinel keep theirs (the global
    # tag plane orders them ahead of pads).
    sent = np.iinfo(np.int32).max
    rng = np.random.default_rng(12)
    keys = rng.integers(0, 50, 5000).astype(np.int32)
    keys[:300] = sent
    vals = np.arange(5000, dtype=np.int32).reshape(-1, 1)
    ss = SampleSort(mesh8, JobConfig(payload_bytes=4, merge_kernel="bitonic"))
    ks, vs = ss.sort_kv(keys, vals, exchange="fused")
    np.testing.assert_array_equal(ks, np.sort(keys))
    np.testing.assert_array_equal(np.sort(vs[:, 0]), np.arange(5000))
    np.testing.assert_array_equal(keys[vs[:, 0]], ks)


# ---- kv records: payload moves (and is counted) once -----------------------


def test_fused_kv_records_payload_identical(mesh8):
    # The fused tag plane is the lax ring's verbatim, so not just the record
    # multiset — the exact payload permutation matches.
    tk, tv = gen_terasort(30_000, seed=3)
    ss = SampleSort(
        mesh8, JobConfig(key_dtype=np.uint64, payload_bytes=tv.shape[1])
    )
    kr, vr = ss.sort_kv(tk, tv, exchange="ring")
    m = _metered()
    kf, vf = ss.sort_kv(tk, tv, metrics=m, exchange="fused")
    np.testing.assert_array_equal(kr, kf)
    np.testing.assert_array_equal(vr, vf)
    np.testing.assert_array_equal(kf, np.sort(tk))
    assert m.counters["fused_exchange_launches"] == 1


def test_fused_kv_wire_bytes_count_payload_once(mesh8):
    """ISSUE 11 satellite: the kv wire-byte model charges each payload row
    ONCE per step — `ring_wire_bytes` at (key + payload-row) slot bytes over
    the planned caps, exactly what the single per-step DMA ships; the
    payload is placed by one gather after the merge, so there is no second
    shipment to account for."""
    tk, tv = gen_terasort(20_000, seed=7)
    ss = SampleSort(
        mesh8, JobConfig(key_dtype=np.uint64, payload_bytes=tv.shape[1])
    )
    m = _metered()
    ss.sort_kv(tk, tv, metrics=m, exchange="fused")
    slot_bytes = tk.dtype.itemsize + tv.shape[1] * tv.dtype.itemsize
    steps = [
        e for e in m.journal.events() if e.type == "fused_exchange_step"
    ]
    assert len(steps) == 7
    caps = [0] + [e.fields["cap"] for e in steps]  # step 0 never ships
    expect = ring_wire_bytes(caps, slot_bytes, 8)
    assert m.counters["exchange_bytes_on_wire"] == expect
    # Each step's journaled bytes price key+payload once, and they sum to
    # the counter — no payload double-charge anywhere.
    assert sum(e.fields["bytes"] for e in steps) == expect


def test_fused_kv_secondary_falls_back(mesh8, caplog):
    from dsort_tpu.data.ingest import terasort_secondary

    tk, tv = gen_terasort(8000, seed=7)
    sec = terasort_secondary(tv)
    ss = SampleSort(
        mesh8, JobConfig(key_dtype=np.uint64, payload_bytes=tv.shape[1])
    )
    ka, va = ss.sort_kv(tk, tv, secondary=sec)
    with caplog.at_level("WARNING", logger="dsort.sample_sort"):
        kf, vf = ss.sort_kv(tk, tv, secondary=sec, exchange="fused")
    np.testing.assert_array_equal(ka, kf)
    np.testing.assert_array_equal(va, vf)


def test_fused_batch_falls_back_to_ring(devices, caplog):
    # The batched 2-D (dp, w) mesh has no 1-axis view for the kernel's
    # logical device ids; the batch keeps the lax ring, outputs unchanged.
    mesh = Mesh(np.array(devices[:8]).reshape(2, 4), ("dp", "w"))
    bs = BatchSampleSort(mesh, JobConfig())
    rng = np.random.default_rng(7)
    jobs = [rng.integers(0, 10**6, n).astype(np.int32) for n in (5000, 801)]
    m = _metered()
    with caplog.at_level("WARNING", logger="dsort.sample_sort"):
        outs = bs.sort(jobs, metrics=m, exchange="fused")
    for j, o in zip(jobs, outs):
        np.testing.assert_array_equal(o, np.sort(j))
    assert m.counters["exchange_ring_steps"] > 0
    assert "fused_exchange_launches" not in m.counters


def test_fused_config_and_cli_vocabulary():
    from dsort_tpu.config import SortConfig
    from dsort_tpu.parallel.exchange import resolve_exchange

    assert JobConfig(exchange="fused").exchange == "fused"
    with pytest.raises(ConfigError, match="exchange"):
        JobConfig(exchange="bogus")
    cfg = SortConfig.from_mapping({"EXCHANGE": "fused"})
    assert cfg.job.exchange == "fused"
    assert resolve_exchange(None, "fused", 8) == "fused"
    assert resolve_exchange("fused", "alltoall", 8) == "fused"
    assert resolve_exchange(None, "fused", 1) == "alltoall"
    with pytest.raises(ValueError, match="fused"):
        resolve_exchange("mesh", "fused", 8)


# ---- observability contract ------------------------------------------------


def test_fused_plan_keeps_ring_observability(mesh8):
    """The fused run rides the SAME accounting as the lax ring —
    skew_report, exchange_step, wire/saved byte counters — plus the fused
    plane: one fused_exchange_launch (dispatches_replaced = P-1) and one
    fused_exchange_step per transfer step, byte-for-byte equal."""
    z = gen_zipf(1 << 17, a=1.3, seed=4)
    ss = SampleSort(mesh8, JobConfig(key_dtype=np.int64))
    m = _metered()
    ss.sort(z, metrics=m, exchange="fused")
    types = m.journal.types()
    assert "skew_report" in types
    assert types.count("exchange_step") == 7
    assert types.count("fused_exchange_step") == 7
    assert m.counters["exchange_bytes_on_wire"] > 0
    assert m.counters["exchange_bytes_saved"] > 0
    launch = next(
        e for e in m.journal.events() if e.type == "fused_exchange_launch"
    )
    assert launch.fields["dispatches"] == 1
    assert launch.fields["dispatches_replaced"] == 7
    ring_steps = {
        e.fields["step"]: e.fields["bytes"]
        for e in m.journal.events() if e.type == "exchange_step"
    }
    fused_steps = {
        e.fields["step"]: e.fields["bytes"]
        for e in m.journal.events() if e.type == "fused_exchange_step"
    }
    assert ring_steps == fused_steps


# ---- fault matrix ----------------------------------------------------------


def test_fused_mid_ring_device_loss_reforms_and_matches():
    """A device lost between the fused plan and exchange dispatches (the
    same `fault_hook` seam as the lax ring) invalidates the exchange; the
    mesh re-forms over the survivors and the job re-runs there with a
    FRESH plan — verified down to a sorted, checksum-matching output and
    a 7-device second launch."""
    from dsort_tpu.models.validate import _multiset
    from dsort_tpu.scheduler import FaultInjector, SpmdScheduler

    inj = FaultInjector()
    sched = SpmdScheduler(
        job=JobConfig(settle_delay_s=0.01, exchange="fused"), injector=inj
    )
    z = gen_zipf(1 << 17, a=1.3, seed=5)
    np.testing.assert_array_equal(sched.sort(z), np.sort(z))  # warm

    inj.fail_once(3, "ring")
    m = _metered()
    out = sched.sort(z, metrics=m)
    assert (np.diff(out) >= 0).all() and len(out) == len(z)
    assert _multiset(out, len(out), out.dtype.itemsize) == _multiset(
        z, len(z), z.dtype.itemsize
    )
    assert m.counters["mesh_reforms"] == 1
    types = m.journal.types()
    assert types.index("worker_dead") < types.index("mesh_reform")
    assert "fused_exchange_launch" in types[types.index("mesh_reform"):]
    assert types[-1] == "job_done"
    # 8-device first attempt + 7-device re-run: 2 launches, 7+6 steps.
    assert m.counters["fused_exchange_launches"] == 2
    assert m.counters["fused_exchange_steps"] == 13


def test_fused_keep_on_device_validates(mesh8):
    from dsort_tpu.scheduler import SpmdScheduler

    sched = SpmdScheduler(job=JobConfig(exchange="fused"))
    data = gen_uniform(1 << 16, seed=9)
    h = sched.sort(data, keep_on_device=True)
    rep = h.validate_on_device()
    assert rep.sorted_ok and rep.records == len(data)
    np.testing.assert_array_equal(h.to_host(), np.sort(data))


# ---- the fused wave pipeline -----------------------------------------------


def _mesh(n):
    from dsort_tpu.parallel.mesh import local_device_mesh

    return local_device_mesh(n)


def test_fused_wave_matches_oracle(tmp_path, devices):
    from dsort_tpu.models.wave_sort import ExternalWaveSort

    rng = np.random.default_rng(21)
    data = rng.integers(-(10**6), 10**6, 24000).astype(np.int32)
    s = ExternalWaveSort(
        _mesh(8), wave_elems=4000, spill_dir=str(tmp_path),
        job_id="wfused", exchange="fused",
    )
    m = _metered()
    np.testing.assert_array_equal(s.sort(data, metrics=m), np.sort(data))
    # One kernel launch per wave: the wave never leaves the device between
    # partition and spill.
    assert m.counters["fused_exchange_launches"] == 6
    assert m.counters["waves_sorted"] == 6


def test_fused_wave_exchange_from_job_config(tmp_path, devices):
    # JobConfig.exchange="fused" reaches the wave plane through the one
    # resolver seam — no per-call override needed.
    from dsort_tpu.models.wave_sort import ExternalWaveSort

    s = ExternalWaveSort(
        _mesh(8), wave_elems=4000, spill_dir=str(tmp_path),
        job_id="wconf", job=JobConfig(exchange="fused"),
    )
    assert s.exchange == "fused"
    data = np.random.default_rng(3).integers(0, 10**6, 9000).astype(np.int32)
    np.testing.assert_array_equal(s.sort(data), np.sort(data))


def test_fused_wave_mid_ring_loss_repairs_in_flight(tmp_path, devices):
    """Mid-ring device loss inside a FUSED wave repairs at run granularity
    in flight (host re-sort of that wave only), later waves keep launching
    the kernel on the mesh, output bit-identical — the fused path composes
    with the wave plane's fault contract unchanged."""
    from dsort_tpu.models.wave_sort import ExternalWaveSort
    from dsort_tpu.scheduler.fault import WorkerFailure

    rng = np.random.default_rng(12)
    data = rng.integers(-(10**6), 10**6, 24000).astype(np.int32)
    s = ExternalWaveSort(
        _mesh(8), wave_elems=4000, spill_dir=str(tmp_path),
        job_id="wfault_fused", exchange="fused",
    )
    calls = {"n": 0}

    def hook():
        calls["n"] += 1
        if calls["n"] == 3:
            raise WorkerFailure("injected mid-ring device loss")

    s.fault_hook = hook
    m = _metered()
    np.testing.assert_array_equal(s.sort(data, metrics=m), np.sort(data))
    assert m.counters["wave_runs_resorted"] == 8  # one wave's runs
    assert m.counters["waves_sorted"] == 5  # the rest stayed on the mesh
    assert "wave_resume" in m.journal.types()


def test_fused_wave_process_kill_resumes_at_run_granularity(tmp_path, devices):
    """The restart-resume drill THROUGH the fused path: a process killed
    after wave 1's runs are durable restores waves 0-1 for free and sorts
    only the rest — the fused exchange composes with (wave, run) resume."""
    from dsort_tpu.models.wave_sort import DIE_AFTER_WAVE_ENV, ExternalWaveSort

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
        f"    spill_dir={str(tmp_path)!r}, job_id='wkill_fused',\n"
        "    exchange='fused')\n"
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
        name for name in os.listdir(tmp_path / "wkill_fused")
        if name.startswith("aux_w")
    }
    assert len(done) == 16, sorted(done)  # waves 0-1 durable, rest never ran
    s2 = ExternalWaveSort(
        _mesh(8), wave_elems=4000, spill_dir=str(tmp_path),
        job_id="wkill_fused", exchange="fused",
    )
    m = _metered()
    np.testing.assert_array_equal(s2.sort(data, metrics=m), np.sort(data))
    assert m.counters["runs_resumed"] == 16
    assert m.counters["runs_sorted"] == 4 * 8  # only the unfinished waves
    assert m.counters["fused_exchange_launches"] == 4  # one per fresh wave


# ---- slow full-scale case --------------------------------------------------


@pytest.mark.slow  # 1M interpret-mode kernel launches: keep tier-1 fast
def test_fused_1m_zipf_bit_identical(mesh8):
    z = gen_zipf(1 << 20, a=1.3, seed=4)
    ss = SampleSort(mesh8, JobConfig(key_dtype=np.int64))
    m = _metered()
    f = ss.sort(z, metrics=m, exchange="fused")
    np.testing.assert_array_equal(f, ss.sort(z, exchange="ring"))
    assert m.counters.get("capacity_retries", 0) == 0
    assert m.counters["fused_exchange_launches"] == 1


# ---- the CLI --------------------------------------------------------------


def test_cli_run_with_fused_exchange(tmp_path):
    """`dsort run --exchange fused` sorts a file through the fused kernel
    (checkpointing routes around the small-job single-device path, so the
    exchange actually runs at this size)."""
    from dsort_tpu import cli

    rng = np.random.default_rng(23)
    inp = tmp_path / "in.txt"
    inp.write_text("\n".join(str(x) for x in rng.integers(0, 10**6, 4000)))
    out = tmp_path / "out.txt"
    journal = tmp_path / "run.jsonl"
    rc = cli.main([
        "run", str(inp), "-o", str(out), "--exchange", "fused",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--journal", str(journal),
    ])
    assert rc == 0
    got = np.loadtxt(out, dtype=np.int64)
    np.testing.assert_array_equal(got, np.sort(np.loadtxt(inp, dtype=np.int64)))
    types = [r["type"] for r in EventLog.read_jsonl(str(journal))]
    assert "fused_exchange_launch" in types


def test_cli_external_mesh_fused_wave(tmp_path, devices):
    """`dsort external --mesh 8 --exchange fused` drives the fused wave
    pipeline end to end from the CLI."""
    from dsort_tpu import cli

    rng = np.random.default_rng(29)
    data = rng.integers(-(10**6), 10**6, 20_000).astype(np.int32)
    inp = tmp_path / "in.bin"
    data.tofile(inp)
    outp = tmp_path / "out.bin"
    journal = tmp_path / "wave.jsonl"
    rc = cli.main([
        "external", str(inp), "-o", str(outp), "--mesh", "8",
        "--wave-elems", "5000", "--exchange", "fused",
        "--spill-dir", str(tmp_path / "spill"), "--journal", str(journal),
    ])
    assert rc == 0
    got = np.fromfile(outp, dtype=np.int32)
    np.testing.assert_array_equal(got, np.sort(data))
    types = [r["type"] for r in EventLog.read_jsonl(str(journal))]
    assert "fused_exchange_launch" in types
    assert "wave_done" in types


# -- ARCHITECTURE §11 schema enforcement -------------------------------------


def test_architecture_documents_fused_ring():
    """§11's contract is test-enforced like §7-§10: the fused plane's event
    and counter names, the exchange vocabulary, the dispatch-count model,
    the interpreter seam and the CI surface all appear verbatim."""
    from dsort_tpu.utils.events import COUNTERS, EVENT_TYPES

    arch = open(
        os.path.join(REPO, "ARCHITECTURE.md"), encoding="utf-8"
    ).read()
    assert "## 11. Fused ring kernel" in arch
    for etype in ("fused_exchange_launch", "fused_exchange_step"):
        assert f"`{etype}`" in arch, f"event {etype} undocumented"
        assert etype in EVENT_TYPES
    for counter in ("fused_exchange_launches", "fused_exchange_steps"):
        assert f"`{counter}`" in arch, f"counter {counter} undocumented"
        assert counter in COUNTERS
    for term in (
        'exchange="fused"', "--exchange fused", "make_async_remote_copy",
        "dispatches_per_exchange", "ring_caps", "fused_mesh",
        "note_fused_plan", "fault_hook", "interpreter", "ICI-only",
    ):
        assert term in arch, f"{term} missing from §11"
