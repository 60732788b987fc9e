"""Distributed sample-sort tests (SURVEY.md §7 step 4) on the simulated mesh."""

import numpy as np
import pytest

from dsort_tpu.config import JobConfig
from dsort_tpu.data.ingest import gen_terasort, gen_uniform, gen_zipf
from dsort_tpu.parallel.sample_sort import SampleSort
from dsort_tpu.utils.metrics import Metrics


@pytest.mark.parametrize("n", [0, 1, 5, 1000, 100_000])
def test_sample_sort_uniform(mesh8, n):
    data = gen_uniform(n, seed=n + 1)
    out = SampleSort(mesh8).sort(data)
    np.testing.assert_array_equal(out, np.sort(data))


def test_sample_sort_zipf_skew(mesh8):
    # Zipf (BASELINE config #5): heavy duplicate skew stresses splitters.
    data = gen_zipf(80_000, a=1.2, seed=9)
    m = Metrics()
    out = SampleSort(mesh8).sort(data, metrics=m)
    np.testing.assert_array_equal(out, np.sort(data))


def test_sample_sort_all_equal_triggers_capacity_retry(mesh8):
    # Worst case: every key identical -> one bucket takes everything; with
    # capacity_factor=1 this must overflow, retry, and still be correct.
    data = np.full(8_000, 123456, dtype=np.int32)
    m = Metrics()
    out = SampleSort(mesh8, JobConfig(capacity_factor=1.0)).sort(data, metrics=m)
    np.testing.assert_array_equal(out, data)
    assert m.counters.get("capacity_retries", 0) >= 1


def test_sample_sort_negative_and_extremes(mesh8):
    data = np.array(
        [-1, 0, 1, -(2**31), 2**31 - 1, 2**31 - 1, -1, 7] * 100, dtype=np.int32
    )
    out = SampleSort(mesh8).sort(data)
    np.testing.assert_array_equal(out, np.sort(data))


def test_sample_sort_int64(mesh8):
    data = gen_uniform(20_000, dtype=np.int64, seed=3)
    out = SampleSort(mesh8, JobConfig(key_dtype=np.int64)).sort(data)
    np.testing.assert_array_equal(out, np.sort(data))


def test_sample_sort_output_is_range_partitioned(mesh8):
    # The distributed contract: device p's keys all <= device p+1's keys —
    # i.e. the output needs NO central merge (unlike server.c:481-524).
    data = gen_uniform(50_000, seed=11)
    out = SampleSort(mesh8).sort(data)
    np.testing.assert_array_equal(out, np.sort(data))  # concat of shards IS sorted


def test_sample_sort_kv_terasort(mesh8):
    keys, payload = gen_terasort(10_000, seed=13)
    sk, sv = SampleSort(mesh8, JobConfig(key_dtype=np.uint64)).sort_kv(keys, payload)
    np.testing.assert_array_equal(sk, np.sort(keys))
    # Payloads must follow their keys: compare as multiset of records.
    def records(k, v):
        return sorted(zip(k.tolist(), map(bytes, v)))

    assert records(sk, sv) == records(keys, payload)


def test_sample_sort_kv_duplicate_keys_keep_payloads(mesh8):
    rng = np.random.default_rng(17)
    keys = rng.integers(0, 50, 5_000).astype(np.int32)  # heavy duplicates
    payload = rng.integers(0, 255, (5_000, 4)).astype(np.uint8)
    sk, sv = SampleSort(mesh8).sort_kv(keys, payload)
    np.testing.assert_array_equal(sk, np.sort(keys))
    assert sorted(zip(sk.tolist(), map(bytes, sv))) == sorted(
        zip(keys.tolist(), map(bytes, payload))
    )


def test_sample_sort_kv_full_10byte_key_order(mesh8):
    # TeraSort's real contract: order by the FULL 10-byte key.  Force heavy
    # 8-byte-prefix collisions so the 2-byte secondary must do the ordering.
    from dsort_tpu.data.ingest import terasort_secondary

    rng = np.random.default_rng(23)
    n = 6_000
    keys = rng.integers(0, 16, n).astype(np.uint64)  # ~375 records per prefix
    payload = rng.integers(0, 256, (n, 92), dtype=np.uint8)
    sec = terasort_secondary(payload)
    sk, sv = SampleSort(mesh8, JobConfig(key_dtype=np.uint64)).sort_kv(
        keys, payload, secondary=sec
    )
    ssec = terasort_secondary(sv)
    # (key, secondary) pairs are globally nondecreasing lexicographically...
    pairs = sk.astype(np.uint64) * (1 << 16) + ssec.astype(np.uint64)
    assert (np.diff(pairs.astype(np.int64)) >= 0).all()
    # ...and the full records are a permutation of the input.
    assert sorted(zip(sk.tolist(), map(bytes, sv))) == sorted(
        zip(keys.tolist(), map(bytes, payload))
    )


def test_sample_sort_kv_secondary_with_capacity_retry(mesh8):
    # All-equal primaries overflow one bucket; the kv2 path must retry and
    # still produce exact (key, secondary) order.
    from dsort_tpu.data.ingest import terasort_secondary

    rng = np.random.default_rng(29)
    n = 4_000
    keys = np.zeros(n, dtype=np.uint64)
    payload = rng.integers(0, 256, (n, 8), dtype=np.uint8)
    sec = terasort_secondary(payload)
    m = Metrics()
    sk, sv = SampleSort(
        mesh8, JobConfig(key_dtype=np.uint64, capacity_factor=1.0)
    ).sort_kv(keys, payload, metrics=m, secondary=sec)
    assert m.counters.get("capacity_retries", 0) >= 1
    assert (np.diff(terasort_secondary(sv).astype(np.int64)) >= 0).all()
    assert sorted(map(bytes, sv)) == sorted(map(bytes, payload))


@pytest.mark.parametrize(
    "dtype",
    [np.uint32, np.float32, np.float64, np.int8, np.uint8, np.int16, np.uint16],
)
def test_sample_sort_more_dtypes(mesh8, dtype):
    rng = np.random.default_rng(41)
    if np.issubdtype(dtype, np.floating):
        data = (rng.standard_normal(10_000) * 1e6).astype(dtype)
    else:
        info = np.iinfo(dtype)
        data = rng.integers(info.min, info.max, 10_000).astype(dtype)
    out = SampleSort(mesh8, JobConfig(key_dtype=dtype)).sort(data)
    assert out.dtype == data.dtype
    np.testing.assert_array_equal(out, np.sort(data))


def test_sample_sort_fuzz_distributions(mesh8):
    # Property sweep: one padded shape (shared compile), many distributions.
    rng = np.random.default_rng(43)
    n = 9_999
    cases = [
        rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32),   # full range
        rng.integers(0, 10, n).astype(np.int32),                 # tiny alphabet
        np.sort(rng.integers(0, 10**6, n)).astype(np.int32),     # presorted
        np.sort(rng.integers(0, 10**6, n))[::-1].astype(np.int32),  # reversed
        np.concatenate([np.zeros(n // 2), rng.integers(0, 100, n - n // 2)]).astype(np.int32),  # half zeros
    ]
    sorter = SampleSort(mesh8)
    for i, data in enumerate(cases):
        out = sorter.sort(data)
        np.testing.assert_array_equal(out, np.sort(data), err_msg=f"case {i}")


def test_sample_sort_bitonic_merge_kernel(mesh8):
    data = gen_uniform(30_000, seed=61)
    out = SampleSort(mesh8, JobConfig(merge_kernel="bitonic")).sort(data)
    np.testing.assert_array_equal(out, np.sort(data))


def test_sample_sort_bitonic_merge_on_7_device_mesh():
    # Non-power-of-two mesh (post-failure shape): merge tree pads rows.
    import jax

    from dsort_tpu.parallel.mesh import local_device_mesh

    mesh7 = local_device_mesh(7)
    data = gen_uniform(10_000, seed=62)
    out = SampleSort(mesh7, JobConfig(merge_kernel="bitonic")).sort(data)
    np.testing.assert_array_equal(out, np.sort(data))


def test_sample_sort_kv_bitonic_merge_kernel(mesh8):
    # merge_kernel applies to the kv path too (bitonic kv merge tree of the
    # received sorted runs) and must keep every record.
    from dsort_tpu.data.ingest import gen_terasort

    keys, payload = gen_terasort(8_000, seed=23)
    job = JobConfig(key_dtype=np.uint64, merge_kernel="bitonic")
    sk, sv = SampleSort(mesh8, job).sort_kv(keys, payload)
    np.testing.assert_array_equal(sk, np.sort(keys))
    assert sorted(zip(sk.tolist(), map(bytes, sv))) == sorted(
        zip(keys.tolist(), map(bytes, payload))
    )


@pytest.mark.slow  # interpret-mode block merge: ~20-35 s on CPU
def test_sample_sort_block_merge_kernel(mesh8):
    # The block-kernel merge entry (VERDICT r3 #2): received sorted runs are
    # merged from level 2*cap up instead of fully re-sorted.
    data = gen_uniform(30_000, seed=63)
    out = SampleSort(mesh8, JobConfig(merge_kernel="block_merge")).sort(data)
    np.testing.assert_array_equal(out, np.sort(data))


@pytest.mark.slow  # interpret-mode block merge: ~20-35 s on CPU
def test_sample_sort_block_merge_on_7_device_mesh():
    # Non-power-of-two mesh (post-failure shape): merge pads sentinel rows.
    from dsort_tpu.parallel.mesh import local_device_mesh

    mesh7 = local_device_mesh(7)
    data = gen_uniform(10_000, seed=64)
    out = SampleSort(mesh7, JobConfig(merge_kernel="block_merge")).sort(data)
    np.testing.assert_array_equal(out, np.sort(data))


@pytest.mark.slow  # interpret-mode block merge: ~20-35 s on CPU
def test_merge_kernel_auto_resolves_to_block_merge(mesh8, monkeypatch):
    """The default ('auto') must route to block_merge wherever the block
    kernel carries the sort — pinned with local_kernel='block', which
    resolves to 'block' even off-TPU (interpret mode), since on CPU the
    plain default silently takes the 'sort' branch."""
    import dsort_tpu.ops.block_sort as bmod

    calls = []
    real = bmod.block_merge_runs

    def spy(runs, *a, **kw):
        calls.append(runs.shape)
        return real(runs, *a, **kw)

    monkeypatch.setattr(bmod, "block_merge_runs", spy)
    data = gen_uniform(30_000, seed=65)
    job = JobConfig(local_kernel="block", merge_kernel="auto")
    out = SampleSort(mesh8, job).sort(data)
    np.testing.assert_array_equal(out, np.sort(data))
    assert calls, "auto never dispatched to block_merge_runs"


def test_sample_sort_kv_block_merge_kernel(mesh8):
    from dsort_tpu.data.ingest import gen_terasort

    keys, payload = gen_terasort(8_000, seed=24)
    job = JobConfig(key_dtype=np.uint64, merge_kernel="block_merge")
    sk, sv = SampleSort(mesh8, job).sort_kv(keys, payload)
    np.testing.assert_array_equal(sk, np.sort(keys))
    assert sorted(zip(sk.tolist(), map(bytes, sv))) == sorted(
        zip(keys.tolist(), map(bytes, payload))
    )


def test_sample_sort_kv_bitonic_sentinel_keys(mesh8):
    # Real sentinel-valued keys must keep their payloads under all combines.
    sent = np.iinfo(np.int32).max
    rng = np.random.default_rng(29)
    keys = rng.integers(-100, 100, 3_000).astype(np.int32)
    keys[::97] = sent
    payload = rng.integers(0, 255, (3_000, 3)).astype(np.uint8)
    for mk in ("sort", "bitonic", "block_merge"):
        sk, sv = SampleSort(mesh8, JobConfig(merge_kernel=mk)).sort_kv(keys, payload)
        np.testing.assert_array_equal(sk, np.sort(keys))
        assert sorted(zip(sk.tolist(), map(bytes, sv))) == sorted(
            zip(keys.tolist(), map(bytes, payload))
        )


def _mesh_dp2(devices):
    from dsort_tpu.config import MeshConfig
    from dsort_tpu.parallel.mesh import make_mesh

    return make_mesh(MeshConfig(num_workers=4, dp=2), devices[:8])


def test_batch_sample_sort_many_jobs(devices):
    """Public MeshConfig.dp path: a batch of unequal jobs, one SPMD program."""
    from dsort_tpu.parallel.sample_sort import BatchSampleSort

    mesh = _mesh_dp2(devices)
    rng = np.random.default_rng(21)
    jobs = [
        rng.integers(-(10**6), 10**6, n).astype(np.int32)
        for n in (5000, 1, 0, 777, 4096, 9999, 12)
    ]
    outs = BatchSampleSort(mesh).sort(jobs)
    assert len(outs) == len(jobs)
    for j, o in zip(jobs, outs):
        np.testing.assert_array_equal(o, np.sort(j))


def test_batch_sample_sort_float_nan(devices):
    from dsort_tpu.parallel.sample_sort import BatchSampleSort

    mesh = _mesh_dp2(devices)
    rng = np.random.default_rng(22)
    jobs = []
    for n in (1000, 3000):
        x = rng.normal(size=n).astype(np.float32)
        x[::53] = np.nan
        jobs.append(x)
    outs = BatchSampleSort(mesh).sort(jobs)
    for j, o in zip(jobs, outs):
        expect = np.sort(j)
        k = len(j) - np.isnan(j).sum()
        np.testing.assert_array_equal(o[:k], expect[:k])
        assert np.isnan(o[k:]).all()


def test_batch_sample_sort_skew_retry(devices):
    from dsort_tpu.config import JobConfig
    from dsort_tpu.parallel.sample_sort import BatchSampleSort

    mesh = _mesh_dp2(devices)
    zipf = (gen_zipf(4000, a=1.2, seed=23) % 100_000).astype(np.int32)
    jobs = [np.full(4000, 7, np.int32), zipf]
    m = Metrics()
    outs = BatchSampleSort(
        mesh, JobConfig(oversample=4, capacity_factor=1.0)
    ).sort(jobs, metrics=m)
    for j, o in zip(jobs, outs):
        np.testing.assert_array_equal(o, np.sort(j))
    # the all-equal job MUST have overflowed one bucket and retried — pins
    # the batch retry loop as actually exercised
    assert m.counters.get("capacity_retries", 0) >= 1
    # mixed dtypes must be refused, not silently value-cast
    import pytest as _pytest

    with _pytest.raises(TypeError):
        BatchSampleSort(mesh).sort([jobs[0], jobs[1].astype(np.int64)])


def test_batch_size_bucketing_padded_volume(devices):
    """One big job must not make every dp slot pay its layout (VERDICT r1):
    the bucketed padded volume is an order of magnitude below the single-
    layout scheme's batch * w * max_cap."""
    from dsort_tpu.parallel.sample_sort import BatchSampleSort
    from dsort_tpu.utils.metrics import Metrics

    rng = np.random.default_rng(12)
    jobs = [rng.integers(-(2**31), 2**31 - 1, 32_768).astype(np.int32)] + [
        rng.integers(-(2**31), 2**31 - 1, 512).astype(np.int32)
        for _ in range(63)
    ]
    m = Metrics()
    outs = BatchSampleSort(_mesh_dp2(devices)).sort(jobs, metrics=m)
    for j, o in zip(jobs, outs):
        np.testing.assert_array_equal(o, np.sort(j))
    naive = 64 * 4 * 8192  # 64-job batch all padded to the 32K job's layout
    assert m.counters["padded_elems"] <= naive // 8


# ---- VERDICT r2 item 1: P=1 short-circuit, measured capacity, kernel merge ----


def test_batch_checkpoint_restores_completed_jobs(devices, tmp_path):
    """VERDICT r3 #7: a re-run of `BatchSampleSort.sort` with job_ids
    restores completed jobs from disk and re-packs the buckets over only
    the missing/stale ones."""
    from dsort_tpu.parallel.sample_sort import BatchSampleSort

    mesh = _mesh_dp2(devices)
    job = JobConfig(checkpoint_dir=str(tmp_path))
    rng = np.random.default_rng(71)
    jobs = [
        rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
        for n in (5_000, 12_000, 900, 7_000, 3_000)
    ]
    ids = [f"file{i}" for i in range(len(jobs))]
    bss = BatchSampleSort(mesh, job)
    m1 = Metrics()
    outs1 = bss.sort(jobs, metrics=m1, job_ids=ids)
    for j, o in zip(jobs, outs1):
        np.testing.assert_array_equal(o, np.sort(j))
    assert "batch_jobs_restored" not in m1.counters

    # Re-run (the "killed and restarted" case, all jobs complete): every
    # job restores, no bucket is sorted at all.
    bss2 = BatchSampleSort(mesh, job)
    calls = []
    orig = bss2._run_bucket
    bss2._run_bucket = lambda ks, vs, cap, m: calls.append(cap) or orig(ks, vs, cap, m)
    m2 = Metrics()
    outs2 = bss2.sort(jobs, metrics=m2, job_ids=ids)
    for a, b in zip(outs1, outs2):
        np.testing.assert_array_equal(a, b)
    assert m2.counters["batch_jobs_restored"] == len(jobs)
    assert calls == []

    # One file's data changes: only that job re-sorts (fingerprint guard).
    jobs[2] = rng.integers(-(2**31), 2**31 - 1, 900).astype(np.int32)
    m3 = Metrics()
    outs3 = BatchSampleSort(mesh, job).sort(jobs, metrics=m3, job_ids=ids)
    np.testing.assert_array_equal(outs3[2], np.sort(jobs[2]))
    assert m3.counters["batch_jobs_restored"] == len(jobs) - 1


def test_batch_kv_many_jobs(devices):
    """Batched key+payload sorts: payloads follow their keys per job."""
    from dsort_tpu.parallel.sample_sort import BatchSampleSort

    mesh = _mesh_dp2(devices)
    rng = np.random.default_rng(73)
    pairs = []
    for n in (4_000, 1_500, 9_000, 2_500):
        keys = rng.integers(-1000, 1000, n).astype(np.int32)
        payload = rng.integers(0, 255, (n, 3)).astype(np.uint8)
        pairs.append((keys, payload))
    outs = BatchSampleSort(mesh).sort_kv(pairs)
    for (k, v), (sk, sv) in zip(pairs, outs):
        np.testing.assert_array_equal(sk, np.sort(k))
        assert sorted(zip(sk.tolist(), map(bytes, sv))) == sorted(
            zip(k.tolist(), map(bytes, v))
        )


def test_batch_kv_checkpoint_resume(devices, tmp_path):
    from dsort_tpu.parallel.sample_sort import BatchSampleSort

    mesh = _mesh_dp2(devices)
    job = JobConfig(checkpoint_dir=str(tmp_path))
    rng = np.random.default_rng(75)
    pairs = [
        (
            rng.integers(0, 10_000, n).astype(np.int32),
            rng.integers(0, 255, (n, 4)).astype(np.uint8),
        )
        for n in (3_000, 6_000, 1_200)
    ]
    ids = [f"kv{i}" for i in range(len(pairs))]
    outs1 = BatchSampleSort(mesh, job).sort_kv(pairs, job_ids=ids)
    m2 = Metrics()
    outs2 = BatchSampleSort(mesh, job).sort_kv(pairs, metrics=m2, job_ids=ids)
    assert m2.counters["batch_jobs_restored"] == len(pairs)
    for (k1, v1), (k2, v2) in zip(outs1, outs2):
        np.testing.assert_array_equal(k1, k2)
        np.testing.assert_array_equal(v1, v2)


def test_batch_float_jobs_checkpoint_resume(devices, tmp_path):
    """Float batches checkpoint under the mapped ordered-uint dtype and
    still restore correctly (NaNs included)."""
    from dsort_tpu.parallel.sample_sort import BatchSampleSort

    mesh = _mesh_dp2(devices)
    job = JobConfig(checkpoint_dir=str(tmp_path))
    rng = np.random.default_rng(79)
    jobs = []
    for n in (2_000, 5_000):
        a = (rng.standard_normal(n) * 1e6).astype(np.float32)
        a[:: max(n // 7, 1)] = np.nan
        jobs.append(a)
    ids = ["fa", "fb"]
    outs1 = BatchSampleSort(mesh, job).sort(jobs, job_ids=ids)
    m2 = Metrics()
    outs2 = BatchSampleSort(mesh, job).sort(jobs, metrics=m2, job_ids=ids)
    assert m2.counters["batch_jobs_restored"] == 2
    for j, o1, o2 in zip(jobs, outs1, outs2):
        np.testing.assert_array_equal(o1, o2)
        np.testing.assert_array_equal(o1, np.sort(j))  # NaNs last, np-style


def test_batch_kv_float_nan_payloads(devices):
    """Float-keyed batched records ride the ordered-uint mapping like every
    other driver (VERDICT r4 weak #5): payloads follow their keys, NaN-keyed
    records come back LAST with payloads attached, keys canonicalized."""
    from dsort_tpu.ops.float_order import float_to_ordered_uint
    from dsort_tpu.parallel.sample_sort import BatchSampleSort

    mesh = _mesh_dp2(devices)
    rng = np.random.default_rng(81)
    pairs = []
    for n in (2_000, 700):
        k = rng.normal(size=n).astype(np.float32)
        k[::37] = np.nan
        v = rng.integers(0, 255, (n, 3)).astype(np.uint8)
        pairs.append((k, v))
    outs = BatchSampleSort(mesh).sort_kv(pairs)
    for (k, v), (sk, sv) in zip(pairs, outs):
        valid = len(k) - int(np.isnan(k).sum())
        np.testing.assert_array_equal(sk[:valid], np.sort(k)[:valid])
        assert np.isnan(sk[valid:]).all()
        # Key-payload association, NaN-safe: compare multisets under the
        # order-preserving bijection (canonicalizes every NaN one way).
        ku, sku = float_to_ordered_uint(k), float_to_ordered_uint(sk)
        assert (np.diff(sku.astype(np.int64)) >= 0).all()
        assert sorted(zip(ku.tolist(), map(bytes, v))) == sorted(
            zip(sku.tolist(), map(bytes, sv))
        )


def test_batch_kv_mixed_payload_shapes_bucketed(devices):
    """Jobs with different payload widths land in different buckets but one
    call sorts them all."""
    from dsort_tpu.parallel.sample_sort import BatchSampleSort

    mesh = _mesh_dp2(devices)
    rng = np.random.default_rng(77)
    pairs = [
        (
            rng.integers(0, 100, 2_000).astype(np.int32),
            rng.integers(0, 255, (2_000, w)).astype(np.uint8),
        )
        for w in (2, 5, 2)
    ]
    outs = BatchSampleSort(mesh).sort_kv(pairs)
    for (k, v), (sk, sv) in zip(pairs, outs):
        np.testing.assert_array_equal(sk, np.sort(k))
        assert sv.shape == v.shape
        assert sorted(zip(sk.tolist(), map(bytes, sv))) == sorted(
            zip(k.tolist(), map(bytes, v))
        )


def test_p1_sorts_exactly_once():
    """On a single-device mesh the SPMD path must invoke exactly ONE local
    sort — no splitters, no all_to_all, no second (merge) sort."""
    import jax
    from jax.sharding import Mesh

    import dsort_tpu.parallel.sample_sort as ssm

    mesh1 = Mesh(np.array(jax.devices()[:1]), ("w",))
    calls = {"sort_padded": 0}
    real_sp = ssm.sort_padded
    real_sk = ssm.sort_keys

    def counting_sp(*a, **kw):
        calls["sort_padded"] += 1
        return real_sp(*a, **kw)

    def counting_sk(*a, **kw):
        raise AssertionError("merge-phase sort ran on a P=1 mesh")

    ssm.sort_padded = counting_sp
    ssm.sort_keys = counting_sk
    try:
        data = gen_uniform(30_000, seed=42)
        out = SampleSort(mesh1).sort(data)
    finally:
        ssm.sort_padded = real_sp
        ssm.sort_keys = real_sk
    np.testing.assert_array_equal(out, np.sort(data))
    assert calls["sort_padded"] == 1  # traced once: one sort in the program


def test_capacity_retry_sizes_from_measured_bucket(mesh8):
    """A skewed overflow converges in ONE measured-size retry, not a
    doubling ladder."""
    data = np.concatenate([
        np.full(30_000, 7, np.int32),        # 3/4 of keys in one bucket
        gen_uniform(10_000, seed=13),
    ])
    m = Metrics()
    out = SampleSort(mesh8, JobConfig(capacity_factor=1.0)).sort(data, metrics=m)
    np.testing.assert_array_equal(out, np.sort(data))
    assert m.counters.get("capacity_retries") == 1


def test_cap_from_observed_quantizes():
    from dsort_tpu.parallel.sample_sort import cap_from_observed

    n_local, p = 1 << 16, 8
    step = n_local // (8 * p)
    c = cap_from_observed(9_000, n_local, p)
    assert c >= int(9_000 * 1.05) and c % 8 == 0
    assert c % step == 0                      # quantized: bounded recompiles
    assert cap_from_observed(10**9, n_local, p) == n_local  # clamped
    assert cap_from_observed(0, 64, 2) >= 8


def test_merge_kernel_dispatch_is_job_kernel(mesh8, monkeypatch):
    """The post-shuffle 'sort' merge goes through sort_with_kernel with the
    JOB's local kernel — not hardcoded lax (VERDICT r2 item 1).  Patching
    ``ops.local_sort.sort_with_kernel`` observes both call sites: the local
    sort (via `sort_padded`) and `_merge_received`'s in-function import."""
    import dsort_tpu.ops.local_sort as lsm

    seen = []
    real = lsm.sort_with_kernel

    def spy(keys, kernel="auto"):
        seen.append(kernel)
        return real(keys, kernel)

    monkeypatch.setattr(lsm, "sort_with_kernel", spy)
    data = gen_uniform(20_000, seed=14)
    out = SampleSort(mesh8, JobConfig(local_kernel="bitonic")).sort(data)
    np.testing.assert_array_equal(out, np.sort(data))
    # local sorts AND the merge phase both dispatched with the job's kernel
    assert len(seen) >= 2 and all(k == "bitonic" for k in seen)


def test_kv_merge_block_pairs_path(monkeypatch):
    """Force the kv combine down the block_sort_pairs plane path (interpret
    mode on CPU) — payloads must follow their keys exactly."""
    import jax
    from jax.sharding import Mesh

    mesh2 = Mesh(np.array(jax.devices()[:2]), ("w",))
    rng = np.random.default_rng(15)
    n = 2_000
    keys = rng.integers(0, 50, n).astype(np.int32)  # duplicates: perm matters
    payload = rng.integers(0, 256, (n, 4)).astype(np.uint8)
    out_k, out_v = SampleSort(mesh2, JobConfig(local_kernel="block")).sort_kv(
        keys, payload
    )
    np.testing.assert_array_equal(out_k, np.sort(keys))
    # every record's payload still sits next to its key (multiset match per key)
    for v in np.unique(keys):
        got = out_v[out_k == v]
        want = payload[keys == v]
        got_set = {bytes(r) for r in got}
        want_set = {bytes(r) for r in want}
        assert got_set == want_set and len(got) == len(want)


KV_LANDING = [(sec, exch) for sec in (True, False) for exch in ("alltoall", "ring")]


@pytest.mark.parametrize("secondary,exchange", KV_LANDING,
                         ids=[f"{'sec' if s else 'nosec'}-{e}" for s, e in KV_LANDING])
def test_sample_sort_kv_payload_lands_row_major(mesh8, secondary, exchange):
    # Eight devices: the payload comes back as one C-contiguous buffer,
    # landed with no strided copy, records exactly the input's.
    from dsort_tpu.data.ingest import terasort_secondary

    keys, payload = gen_terasort(10_000, seed=31)
    m = Metrics()
    sk, sv = SampleSort(mesh8, JobConfig(key_dtype=np.uint64)).sort_kv(
        keys, payload, metrics=m, exchange=exchange,
        secondary=terasort_secondary(payload) if secondary else None,
    )
    assert sv.shape == payload.shape and sv.flags.c_contiguous
    assert m.counters.get("land_strided_copies", 0) == 0
    np.testing.assert_array_equal(sk, np.sort(keys))
    assert sorted(zip(sk.tolist(), map(bytes, sv))) == sorted(
        zip(keys.tolist(), map(bytes, payload))
    )


def _shard_spec(mesh, shape, dtype):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, PartitionSpec("w")))


def test_land_shards_counts_strided_copies(mesh8):
    # A column-major payload (the layout XLA gives a 2-D u8 output on the
    # TPU) still lands row for row, and each shard's copy is counted.
    import jax
    from jax.experimental.layout import Format, Layout
    from jax.sharding import NamedSharding, PartitionSpec

    from dsort_tpu.parallel.sample_sort import _land_shards
    from dsort_tpu.utils.metrics import PhaseTimer

    p, rows = 8, 64
    host = np.random.default_rng(5).integers(0, 256, (p * rows, 12), dtype=np.uint8)
    col_major = Format(Layout(major_to_minor=(1, 0)), NamedSharding(mesh8, PartitionSpec("w")))
    arr = jax.jit(lambda x: x + 0, out_shardings=col_major)(host)
    assert not np.asarray(arr.addressable_shards[0].data).flags.c_contiguous
    c = np.full(p, rows - 3)
    m = Metrics()
    timer = PhaseTimer(m)
    with timer.phase("assemble"):
        (out,) = _land_shards(timer, [arr], c, int(c.sum()), p)
    np.testing.assert_array_equal(
        out, host.reshape(p, rows, 12)[:, : rows - 3].reshape(-1, 12))
    assert m.counters["land_strided_copies"] == p
    # The same rows flattened on the device land with plain copies.
    flat = jax.jit(lambda x: x.reshape(-1))(jax.device_put(
        host, NamedSharding(mesh8, PartitionSpec("w"))))
    m2 = Metrics()
    timer = PhaseTimer(m2)
    with timer.phase("assemble"):
        (out2,) = _land_shards(timer, [flat], c, int(c.sum()), p, [(12,)])
    np.testing.assert_array_equal(out2, out)
    assert m2.counters.get("land_strided_copies", 0) == 0


@pytest.mark.parametrize("kind", ["spmd_kv", "spmd_kv2", "spmd_ring_kv"])
def test_kv_programs_return_the_payload_flat(mesh8, kind):
    # The payload leaves every record program as a 1-D array (rows x 92
    # bytes): one layout on every backend, so its host copy is row-major.
    p, n_local, width = 8, 256, 92
    ss = SampleSort(mesh8, JobConfig(key_dtype=np.uint64))
    keys = _shard_spec(mesh8, (p * n_local,), np.uint64)
    payload = _shard_spec(mesh8, (p * n_local, width), np.uint8)
    counts = _shard_spec(mesh8, (p,), np.int32)
    if kind == "spmd_ring_kv":
        import jax

        fn = ss._build_ring(n_local, (64,) * p, (width,))
        args = [keys, payload, counts, jax.ShapeDtypeStruct((p - 1,), np.uint64)]
        at = 1
    elif kind == "spmd_kv2":
        fn = ss._build(n_local, 64, (width,), True)
        args = [keys, _shard_spec(mesh8, (p * n_local,), np.uint16), payload, counts]
        at = 2
    else:
        fn = ss._build(n_local, 64, (width,), False)
        args = [keys, payload, counts]
        at = 1
    lowered = fn._fn.lower(*args)
    out = lowered.out_info[at]
    assert out.ndim == 1 and out.dtype == np.uint8 and out.shape[0] % (p * width) == 0
    # The program keeps its shard body's name, which the device trace shows.
    name = "_ring_exchange_kv_shard" if kind == "spmd_ring_kv" else (
        "_sample_sort_kv2_shard" if kind == "spmd_kv2" else "_sample_sort_kv_shard")
    assert f"@jit_{name}" in lowered.as_text()


# ---- BASELINE.md's config ladder -------------------------------------------


@pytest.mark.parametrize("config", [1, 2, 3, 4, 5])
def test_baseline_config_ladder(mesh8, config):
    """BASELINE.md's five configs at the sizes the CPU mesh runs them: the
    reference's own 16,384-key job as one fused program, 2^20 int32 and
    int64 keys, 65,536 TeraSort records by their full 10-byte key, and
    2^20 zipf keys with worker 2 lost and the mesh re-formed."""
    if config == 1:
        from dsort_tpu.models.pipelines import fused_sort_small

        data = gen_uniform(16_384, seed=0)
        np.testing.assert_array_equal(fused_sort_small(data), np.sort(data))
    elif config in (2, 3):
        dtype = np.int32 if config == 2 else np.int64
        data = gen_uniform(1 << 20, dtype=dtype, seed=config - 1)
        out = SampleSort(mesh8, JobConfig(key_dtype=dtype)).sort(data)
        np.testing.assert_array_equal(out, np.sort(data))
    elif config == 4:
        from dsort_tpu.data.ingest import terasort_secondary

        keys, payload = gen_terasort(1 << 16, seed=3)
        sec = terasort_secondary(payload)
        ss = SampleSort(mesh8, JobConfig(key_dtype=np.uint64,
                                         payload_bytes=payload.shape[1]))
        out_k, out_v = ss.sort_kv(keys, payload, secondary=sec)
        order = np.lexsort((sec, keys))
        np.testing.assert_array_equal(out_k, keys[order])
        np.testing.assert_array_equal(out_v, payload[order])
    else:
        from dsort_tpu.scheduler import FaultInjector, SpmdScheduler

        data = gen_zipf(1 << 20, a=1.3, seed=4)
        inj = FaultInjector()
        sched = SpmdScheduler(job=JobConfig(settle_delay_s=0.01), injector=inj)
        inj.fail_once(2, "spmd")
        m = Metrics()
        np.testing.assert_array_equal(sched.sort(data, metrics=m), np.sort(data))
        assert m.counters["mesh_reforms"] >= 1
