"""Device-resident result path (the no-relay pipeline contract).

`DeviceSortResult` keeps the sorted global array sharded on the mesh:
``.to_host()`` is the only D2H, ``.consume(fn)`` chains a jitted next stage
with buffer donation, and ``.validate_on_device()`` runs `dsort validate`
semantics (order + FNV-1a multiset checksum, matching `models.validate`'s
host results bit-for-bit) as jitted shard_map reductions.  The scheduler
drill pins the fault contract: a mesh re-form invalidates outstanding
handles and they transparently re-run on the surviving mesh.
"""

import json

import numpy as np
import pytest

from dsort_tpu.config import JobConfig
from dsort_tpu.data.ingest import gen_uniform, gen_zipf
from dsort_tpu.models.validate import _multiset
from dsort_tpu.parallel.device_result import DeviceSortResult
from dsort_tpu.parallel.sample_sort import SampleSort
from dsort_tpu.utils.events import EventLog
from dsort_tpu.utils.metrics import Metrics


def _host_sum(a: np.ndarray) -> int:
    return _multiset(a, len(a), a.dtype.itemsize)


def test_device_result_round_trip(mesh8):
    """The acceptance round trip: validate ok on device, checksum equals the
    host `_multiset` of the same data, and to_host equals np.sort."""
    data = gen_uniform(120_000, seed=3)
    m = Metrics(journal=EventLog())
    h = SampleSort(mesh8).sort(data, metrics=m, keep_on_device=True)
    assert h.valid and len(h) == len(data) and h.num_shards == 8
    rep = h.validate_on_device()
    assert rep.sorted_ok and rep.records == len(data)
    assert rep.checksum == _host_sum(data)  # permutation proof, no relay
    np.testing.assert_array_equal(h.to_host(), np.sort(data))
    assert m.counters["device_handles"] == 1
    assert m.counters["device_validates"] == 1
    types = m.journal.types()
    assert "device_handle" in types and "device_validate" in types
    # offsets metadata recovers the exact global layout
    assert h.offsets[-1] == len(data)
    assert (np.diff(h.offsets) == h.shard_lengths).all()


@pytest.mark.parametrize("dtype", [np.int64, np.uint32])
def test_device_result_dtypes(mesh8, dtype):
    rng = np.random.default_rng(11)
    info = np.iinfo(dtype)
    data = rng.integers(info.min, info.max, 30_000).astype(dtype)
    h = SampleSort(mesh8, JobConfig(key_dtype=dtype)).sort(
        data, keep_on_device=True
    )
    rep = h.validate_on_device()
    assert rep.sorted_ok and rep.checksum == _host_sum(data)
    np.testing.assert_array_equal(h.to_host(), np.sort(data))


def test_device_result_sentinel_keys_and_duplicates(mesh8):
    """Real sentinel-valued keys (dtype max) and heavy duplicates must pass
    on-device validation — pads are excluded by count, not by value."""
    sent = np.iinfo(np.int32).max
    rng = np.random.default_rng(13)
    data = rng.integers(-50, 50, 40_000).astype(np.int32)
    data[::91] = sent
    h = SampleSort(mesh8).sort(data, keep_on_device=True)
    rep = h.validate_on_device()
    assert rep.sorted_ok and rep.records == len(data)
    assert rep.checksum == _host_sum(data)


def test_device_result_skew_capacity_retry(mesh8):
    """A capacity retry mid-dispatch still yields a valid handle."""
    data = np.concatenate(
        [np.full(30_000, 9, np.int32), gen_uniform(8_000, seed=5)]
    )
    m = Metrics()
    h = SampleSort(mesh8, JobConfig(capacity_factor=1.0)).sort(
        data, metrics=m, keep_on_device=True
    )
    assert m.counters.get("capacity_retries", 0) >= 1
    assert h.validate_on_device().sorted_ok
    np.testing.assert_array_equal(h.to_host(), np.sort(data))


def test_device_validate_detects_unsorted_rows():
    """An in-row order break is caught by the plain-jit validator."""
    import jax.numpy as jnp

    rows = np.array([[3, 1, 2, 7], [8, 9, 10, 11]], np.int32)
    h = DeviceSortResult(
        jnp.asarray(rows.reshape(-1)),
        shard_lengths=np.array([4, 4]), n=8,
    )
    rep = h.validate_on_device()
    assert not rep.sorted_ok
    assert rep.records == 8


def test_device_validate_detects_boundary_violation(mesh8):
    """A cross-shard boundary break is caught by the shard_map validator:
    shard 0's keys exceed shard 1's (each shard locally sorted)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows = np.stack(
        [np.arange(100, 116, dtype=np.int32) + 16 * ((7 - i) % 8)
         for i in range(8)]
    )  # every row sorted, rows in DESCENDING key ranges
    arr = jax.device_put(
        rows.reshape(-1), NamedSharding(mesh8, P("w"))
    )
    h = DeviceSortResult(
        arr, shard_lengths=np.full(8, 16), n=128, mesh=mesh8, axis="w",
    )
    rep = h.validate_on_device()
    assert not rep.sorted_ok
    # the multiset checksum is order-independent and still exact
    assert rep.checksum == _host_sum(rows.reshape(-1))


def test_device_validate_corruption_changes_checksum(mesh8):
    """Flipping one key's value flips the checksum — the permutation proof
    has teeth."""
    data = gen_uniform(20_000, seed=7)
    h = SampleSort(mesh8).sort(data, keep_on_device=True)
    rep = h.validate_on_device()
    corrupted = data.copy()
    corrupted[123] ^= 1
    assert rep.checksum == _host_sum(data)
    assert rep.checksum != _host_sum(corrupted)


def test_device_result_consume_chains_jitted_stage(mesh8):
    """consume() runs a jitted next stage over the device buffer (donated)
    and marks the handle consumed — later reads refuse loudly."""
    data = gen_uniform(50_000, seed=17)
    m = Metrics(journal=EventLog())
    h = SampleSort(mesh8).sort(data, metrics=m, keep_on_device=True)
    lengths = h.shard_lengths.copy()
    out = h.consume(lambda x: x ^ 1)
    # the stage saw the sorted padded layout: valid prefix of each shard is
    # np.sort(data)'s interval, xor'd
    got = np.asarray(out)
    cap = got.size // 8
    expect = np.sort(data) ^ 1
    off = 0
    for i in range(8):
        ci = int(lengths[i])
        np.testing.assert_array_equal(
            got[i * cap : i * cap + ci], expect[off : off + ci]
        )
        off += ci
    assert m.counters["device_consumes"] == 1
    assert not h.valid
    with pytest.raises(RuntimeError, match="consumed"):
        h.to_host()
    with pytest.raises(RuntimeError, match="consumed"):
        h.validate_on_device()


def test_device_result_consume_without_donation_keeps_handle(mesh8):
    data = gen_uniform(9_000, seed=19)
    h = SampleSort(mesh8).sort(data, keep_on_device=True)
    h.consume(lambda x: x + 0, donate=False)
    assert h.valid
    np.testing.assert_array_equal(h.to_host(), np.sort(data))


def test_device_result_empty_and_float_refusal(mesh8):
    h = SampleSort(mesh8).sort(np.empty(0, np.int32), keep_on_device=True)
    assert len(h) == 0
    rep = h.validate_on_device()
    assert rep.sorted_ok and rep.records == 0 and rep.checksum == 0
    assert h.to_host().size == 0
    with pytest.raises(TypeError, match="integer keys"):
        SampleSort(mesh8).sort(
            np.zeros(10, np.float32), keep_on_device=True
        )


def test_fused_sort_small_keep_on_device():
    """The single-chip fused path: one H2D + async execute, no fetch; the
    handle validates and assembles lazily."""
    from dsort_tpu.models.pipelines import fused_sort_small

    rng = np.random.default_rng(23)
    data = rng.integers(-(2**31), 2**31 - 1, 10_000).astype(np.int32)
    m = Metrics()
    h = fused_sort_small(data, metrics=m, keep_on_device=True)
    assert h.num_shards == 1 and len(h) == len(data)
    rep = h.validate_on_device()
    assert rep.sorted_ok and rep.checksum == _host_sum(data)
    np.testing.assert_array_equal(h.to_host(), np.sort(data))
    assert m.counters["device_handles"] == 1
    with pytest.raises(TypeError, match="integer keys"):
        fused_sort_small(np.zeros(4, np.float64), keep_on_device=True)


def test_batch_sample_sort_keep_on_device(devices):
    from dsort_tpu.config import MeshConfig
    from dsort_tpu.parallel.mesh import make_mesh
    from dsort_tpu.parallel.sample_sort import BatchSampleSort

    mesh = make_mesh(MeshConfig(num_workers=4, dp=2), devices[:8])
    rng = np.random.default_rng(29)
    jobs = [
        rng.integers(-(10**6), 10**6, n).astype(np.int32)
        for n in (5000, 1, 0, 4096, 777)
    ]
    m = Metrics()
    handles = BatchSampleSort(mesh).sort(jobs, metrics=m, keep_on_device=True)
    assert len(handles) == len(jobs)
    for j, h in zip(jobs, handles):
        np.testing.assert_array_equal(h.to_host(), np.sort(j))
        rep = h.validate_on_device()
        assert rep.sorted_ok and rep.records == len(j)
        if len(j):
            assert rep.checksum == _host_sum(j)
    assert m.counters["device_handles"] == len(jobs)


def test_spmd_scheduler_device_resident_fault_drill(mesh8):
    """The acceptance fault drill: a mesh re-form invalidates an issued
    handle, and the handle transparently re-runs on the surviving mesh."""
    from dsort_tpu.scheduler import FaultInjector, SpmdScheduler

    inj = FaultInjector()
    sched = SpmdScheduler(
        job=JobConfig(settle_delay_s=0.01), injector=inj
    )
    data = gen_uniform(60_000, seed=31)
    m = Metrics(journal=EventLog())
    h = sched.sort(data, metrics=m, keep_on_device=True)
    assert h.valid
    # a later job loses a device -> the mesh re-forms -> the handle's
    # buffers (partly on the reaped device) are invalidated
    inj.fail_once(2, "spmd")
    sched.sort(gen_uniform(8_000, seed=32), metrics=m)
    assert m.counters["mesh_reforms"] == 1
    assert not h.valid
    # next use re-runs on the 7-survivor mesh and heals the handle
    np.testing.assert_array_equal(h.to_host(), np.sort(data))
    assert h.valid
    assert m.counters["device_handle_reruns"] == 1
    rep = h.validate_on_device()
    assert rep.sorted_ok and rep.checksum == _host_sum(data)
    types = m.journal.types()
    assert "device_handle_invalidated" in types
    assert types.index("mesh_reform") < types.index(
        "device_handle_invalidated"
    )


def test_spmd_scheduler_device_resident_survives_injected_failure(mesh8):
    """A device lost DURING the device-resident sort itself: the scheduler
    re-forms and the returned handle is already the re-run's."""
    from dsort_tpu.scheduler import FaultInjector, SpmdScheduler

    inj = FaultInjector()
    sched = SpmdScheduler(job=JobConfig(settle_delay_s=0.01), injector=inj)
    data = gen_zipf(50_000, a=1.2, seed=33)
    inj.fail_once(3, "spmd")
    m = Metrics()
    h = sched.sort(data, metrics=m, keep_on_device=True)
    assert m.counters["mesh_reforms"] == 1
    assert h.validate_on_device().sorted_ok
    np.testing.assert_array_equal(h.to_host(), np.sort(data))


def test_spmd_scheduler_device_resident_skips_checkpoint(tmp_path):
    """keep_on_device + checkpoint config: the job runs (no range persist)
    and warns instead of mixing handles with persisted ranges."""
    from dsort_tpu.scheduler import SpmdScheduler

    sched = SpmdScheduler(
        job=JobConfig(settle_delay_s=0.01, checkpoint_dir=str(tmp_path))
    )
    data = gen_uniform(9_000, seed=35)
    h = sched.sort(data, metrics=Metrics(), job_id="dev", keep_on_device=True)
    np.testing.assert_array_equal(h.to_host(), np.sort(data))
    assert not list(tmp_path.iterdir())  # nothing persisted


def test_spmd_scheduler_device_resident_float_refusal():
    from dsort_tpu.scheduler import SpmdScheduler

    with pytest.raises(TypeError, match="integer keys"):
        SpmdScheduler(job=JobConfig()).sort(
            np.zeros(8, np.float32), keep_on_device=True
        )


# ---- the CLI --------------------------------------------------------------


def test_cli_run_device_resident(tmp_path):
    """`dsort run --device-resident` writes the sorted file and validates on
    device (exit 0)."""
    from dsort_tpu import cli

    rng = np.random.default_rng(37)
    inp = tmp_path / "in.txt"
    inp.write_text("\n".join(str(x) for x in rng.integers(0, 10**6, 4000)))
    out = tmp_path / "out.txt"
    journal = tmp_path / "run.jsonl"
    rc = cli.main([
        "run", str(inp), "-o", str(out), "--device-resident",
        "--journal", str(journal),
    ])
    assert rc == 0
    got = np.array([int(x) for x in out.read_text().split()])
    assert (np.diff(got) >= 0).all() and len(got) == 4000
    types = [r["type"] for r in EventLog.read_jsonl(str(journal))]
    assert "device_handle" in types and "device_validate" in types
