"""Ring exchange (`parallel.exchange`): bit-identical output, adaptive
headroom, merge-as-you-receive wiring, and the mid-ring fault contract.

The acceptance bar for the ring schedule is strict: on the same data and
config it must be *bit-identical* to the all_to_all path (both produce each
destination's sorted key-range multiset, and sorted arrays of equal
multisets are equal), ship measurably fewer bytes under skew (the padded
path pays worst-case headroom plus a full re-dispatch on overflow), and
inherit the SPMD fault contract unchanged (a device lost mid-ring re-forms
the mesh and re-runs on the survivors).
"""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from dsort_tpu.config import ConfigError, JobConfig
from dsort_tpu.data.ingest import gen_terasort, gen_uniform, gen_zipf
from dsort_tpu.parallel.exchange import (
    alltoall_wire_bytes,
    dispatches_per_exchange,
    ring_caps,
    ring_step_quantum,
    ring_wire_bytes,
)
from dsort_tpu.parallel.sample_sort import BatchSampleSort, SampleSort, cap_pair_policy
from dsort_tpu.utils.events import EventLog
from dsort_tpu.utils.metrics import Metrics


def _metered():
    return Metrics(journal=EventLog())


# ---- bit-identical vs the all_to_all path ---------------------------------


@pytest.mark.parametrize("n", [64, 5000, 100_003])
def test_ring_uniform_bit_identical(mesh8, n):
    ss = SampleSort(mesh8)
    rng = np.random.default_rng(11)
    data = rng.integers(-(10**6), 10**6, n).astype(np.int32)
    a = ss.sort(data)
    m = _metered()
    r = ss.sort(data, metrics=m, exchange="ring")
    np.testing.assert_array_equal(a, r)
    assert m.counters["exchange_ring_steps"] == 7
    assert m.counters.get("capacity_retries", 0) == 0


def test_ring_zipf_bit_identical_int64(mesh8):
    z = gen_zipf(1 << 17, a=1.3, seed=4)
    ss = SampleSort(mesh8, JobConfig(key_dtype=np.int64))
    a = ss.sort(z)
    r = ss.sort(z, exchange="ring")
    np.testing.assert_array_equal(a, r)


def test_ring_all_equal_keys(mesh8):
    # The degenerate skew: every key identical — one destination owns
    # everything, every step's cap is the whole shard.
    ss = SampleSort(mesh8)
    data = np.full(20_000, 7, np.int32)
    r = ss.sort(data, exchange="ring")
    np.testing.assert_array_equal(r, data)


def test_ring_sentinel_valued_keys(mesh8):
    # Real keys equal to the padding sentinel must survive the ring's
    # sentinel-padded runs exactly as they survive the padded buffer.
    ss = SampleSort(mesh8)
    rng = np.random.default_rng(3)
    data = rng.integers(-100, 100, 9000).astype(np.int32)
    data[:200] = np.iinfo(np.int32).max
    np.testing.assert_array_equal(
        ss.sort(data, exchange="ring"), np.sort(data)
    )


def test_ring_on_7_device_mesh():
    # Non-power-of-two rings (the post-re-form mesh shape): the ppermute
    # shifts and the merge tower's final fold must not assume pow2 P.
    mesh7 = Mesh(np.array(jax.devices()[:7]), ("w",))
    ss = SampleSort(mesh7)
    rng = np.random.default_rng(5)
    data = rng.integers(-(10**6), 10**6, 70_001).astype(np.int32)
    a = ss.sort(data)
    m = _metered()
    r = ss.sort(data, metrics=m, exchange="ring")
    np.testing.assert_array_equal(a, r)
    assert m.counters["exchange_ring_steps"] == 6


def test_ring_float_keys_nan(mesh8):
    # Floats (incl. NaN) ride the ring as order-preserving uints like every
    # other driver path.
    ss = SampleSort(mesh8)
    rng = np.random.default_rng(6)
    data = rng.normal(size=20_000).astype(np.float32)
    data[::97] = np.nan
    got = ss.sort(data, exchange="ring")
    expect = np.sort(data)  # numpy: NaNs last
    k = len(data) - np.isnan(data).sum()
    np.testing.assert_array_equal(got[:k], expect[:k])
    assert np.isnan(got[k:]).all()


def test_ring_kv_records(mesh8):
    # Keys bit-identical; records as a whole the same multiset in the same
    # key order (payload order among equal keys is unspecified on BOTH
    # paths — the local sorts are unstable).
    tk, tv = gen_terasort(30_000, seed=3)
    ss = SampleSort(mesh8, JobConfig(key_dtype=np.uint64, payload_bytes=tv.shape[1]))
    ka, va = ss.sort_kv(tk, tv)
    m = _metered()
    kr, vr = ss.sort_kv(tk, tv, metrics=m, exchange="ring")
    np.testing.assert_array_equal(ka, kr)
    assert m.counters["exchange_ring_steps"] == 7

    def records_sig(k, v):
        order = np.lexsort(tuple(v[:, i] for i in range(v.shape[1])) + (k,))
        return k[order].tobytes() + v[order].tobytes()

    assert records_sig(ka, va) == records_sig(kr, vr)


def test_ring_kv_duplicate_keys_keep_payloads(mesh8):
    ss = SampleSort(mesh8, JobConfig(payload_bytes=4))
    rng = np.random.default_rng(8)
    keys = rng.integers(0, 50, 6000).astype(np.int32)  # heavy duplicates
    vals = np.arange(6000, dtype=np.int32).reshape(-1, 1)
    ks, vs = ss.sort_kv(keys, vals, exchange="ring")
    np.testing.assert_array_equal(ks, np.sort(keys))
    # Every payload appears exactly once, attached to its own key.
    np.testing.assert_array_equal(np.sort(vs[:, 0]), np.arange(6000))
    np.testing.assert_array_equal(keys[vs[:, 0]], ks)


def test_ring_kv_secondary_falls_back(mesh8, caplog):
    # Two-level keys keep the one-shot combine: ring requests warn and use
    # the all_to_all exchange, output unchanged.
    from dsort_tpu.data.ingest import terasort_secondary

    tk, tv = gen_terasort(8000, seed=7)
    sec = terasort_secondary(tv)
    ss = SampleSort(mesh8, JobConfig(key_dtype=np.uint64, payload_bytes=tv.shape[1]))
    ka, va = ss.sort_kv(tk, tv, secondary=sec)
    with caplog.at_level("WARNING", logger="dsort.sample_sort"):
        kr, vr = ss.sort_kv(tk, tv, secondary=sec, exchange="ring")
    np.testing.assert_array_equal(ka, kr)
    np.testing.assert_array_equal(va, vr)


def test_ring_empty_and_single_worker():
    ss1 = SampleSort(Mesh(np.array(jax.devices()[:1]), ("w",)))
    data = np.random.default_rng(1).integers(0, 100, 999).astype(np.int32)
    # P=1 resolves to the all_to_all short-circuit — no ring program exists.
    np.testing.assert_array_equal(ss1.sort(data, exchange="ring"), np.sort(data))
    ss = SampleSort(Mesh(np.array(jax.devices()[:2]), ("w",)))
    out = ss.sort(np.empty(0, np.int32), exchange="ring")
    assert len(out) == 0


def test_ring_batch_bit_identical(devices):
    mesh = Mesh(np.array(devices[:8]).reshape(2, 4), ("dp", "w"))
    bs = BatchSampleSort(mesh, JobConfig())
    rng = np.random.default_rng(7)
    jobs = [
        rng.integers(0, 10**6, n).astype(np.int32)
        for n in (5000, 12_000, 801, 64)
    ]
    outs_a = bs.sort(jobs)
    m = _metered()
    outs_r = bs.sort(jobs, metrics=m, exchange="ring")
    for a, r in zip(outs_a, outs_r):
        np.testing.assert_array_equal(a, r)
    assert m.counters["exchange_ring_steps"] > 0


def test_ring_invalid_exchange_rejected(mesh8):
    ss = SampleSort(mesh8)
    with pytest.raises(ValueError, match="exchange"):
        ss.sort(np.arange(100, dtype=np.int32), exchange="mesh")
    with pytest.raises(ConfigError, match="exchange"):
        JobConfig(exchange="bogus")


def test_config_exchange_from_mapping():
    from dsort_tpu.config import SortConfig

    cfg = SortConfig.from_mapping({"EXCHANGE": "ring"})
    assert cfg.job.exchange == "ring"


# ---- adaptive headroom ----------------------------------------------------


def test_ring_caps_quantized_and_covering():
    p, n_local = 8, 1 << 15
    rng = np.random.default_rng(0)
    hist = rng.integers(0, n_local // p, (p, p)).astype(np.int64)
    caps = ring_caps(hist, n_local, p)
    assert len(caps) == p
    q = ring_step_quantum(n_local, p)
    for k in range(p):
        step_max = max(int(hist[src, (src + k) % p]) for src in range(p))
        assert caps[k] >= step_max          # covers the measured buckets
        assert caps[k] % 8 == 0             # vreg/DMA alignment rule
        assert caps[k] % q == 0 or caps[k] == -(-n_local // 8) * 8
        assert caps[k] - step_max < q       # tight to one quantum


def test_ring_caps_skew_isolates_hot_steps():
    # One hot (src, dst) pair inflates ONLY the step that carries it; the
    # other steps stay at the uniform rung — the per-step resize.
    p, n_local = 8, 1 << 15
    hist = np.full((p, p), 100, np.int64)
    hist[2, 5] = 3000  # shift k = 3
    caps = ring_caps(hist, n_local, p)
    assert caps[3] >= 3000
    assert all(c < 3000 for i, c in enumerate(caps) if i != 3)


def test_ring_caps_bounded_rungs():
    # Quantization bounds the distinct programs a drifting workload compiles.
    p, n_local = 8, 1 << 15
    rungs = set()
    for m in range(0, n_local // p, 37):
        rungs.add(ring_caps(np.full((p, p), m, np.int64), n_local, p))
    assert len(rungs) <= 12


def test_wire_bytes_model():
    caps = (16, 24, 8, 8)
    assert ring_wire_bytes(caps, 4, 4) == (24 + 8 + 8) * 4 * 4
    assert alltoall_wire_bytes(32, 4, 4) == 3 * 32 * 4 * 4


def test_ring_bytes_saved_uniform(mesh8):
    # Uniform data: the ring's measured caps undercut the 1.3x policy
    # headroom; the saved counter records the difference.
    ss = SampleSort(mesh8)
    data = gen_uniform(1 << 17, seed=1)
    m = _metered()
    ss.sort(data, metrics=m, exchange="ring")
    assert m.counters["exchange_bytes_saved"] > 0
    policy = cap_pair_policy(-(-(1 << 17) // 8), 1.3, 8)
    assert m.counters["exchange_bytes_on_wire"] < alltoall_wire_bytes(
        policy, 4, 8
    )


# ---- the zipf capacity regression (satellite) -----------------------------


def test_zipf_1m_padded_retries_ring_does_not(mesh8):
    """The drill the adaptive headroom exists for: on a zipf-skewed 1M
    input the padded all_to_all overflows its policy-sized buffer and
    re-dispatches the whole job (``capacity_retry`` in the journal), while
    the ring path completes with ZERO retries — its per-step buffers were
    sized from the measured histogram, surfacing as ``exchange_resize``
    events instead.  Outputs stay bit-identical, and the ring ships
    measurably fewer bytes than the padded path's two shipments."""
    z = gen_zipf(1 << 20, a=1.3, seed=4)
    ss = SampleSort(mesh8, JobConfig(key_dtype=np.int64))

    m_pad = _metered()
    out_pad = ss.sort(z, metrics=m_pad)
    assert m_pad.counters["capacity_retries"] >= 1
    types_pad = m_pad.journal.types()
    assert "capacity_retry" in types_pad
    # The retry is a whole-job re-dispatch: a second spmd_sort phase opens
    # after the capacity_retry event.
    idx = types_pad.index("capacity_retry")
    assert "phase_start" in types_pad[idx:]

    m_ring = _metered()
    out_ring = ss.sort(z, metrics=m_ring, exchange="ring")
    np.testing.assert_array_equal(out_pad, out_ring)
    assert m_ring.counters.get("capacity_retries", 0) == 0
    types_ring = m_ring.journal.types()
    assert "capacity_retry" not in types_ring
    # The skew that forced the padded retry shows up as per-step resizes.
    assert "exchange_resize" in types_ring
    assert types_ring.count("exchange_step") == 7
    # Measurably fewer wire bytes than the padded path actually shipped
    # (policy-sized attempt + resized re-dispatch).
    assert (
        m_ring.counters["exchange_bytes_on_wire"]
        < m_pad.counters["exchange_bytes_on_wire"]
    )
    assert m_ring.counters["exchange_bytes_saved"] > 0

    # ISSUE 9: every ring plan journals its skew signal (reduced from the
    # histogram it already measured).  The zipf-1M run's max/mean bucket
    # ratio must exceed a same-size uniform run's by a real margin — the
    # analyzer's skew verdict rests on exactly this separation.
    def skew_ratio(journal):
        reports = [e for e in journal.events() if e.type == "skew_report"]
        assert reports, "every ring plan must journal a skew_report"
        return reports[-1].fields["max_mean_ratio"]

    m_uni = _metered()
    ss.sort(
        gen_uniform(1 << 20, dtype=np.int64, seed=0),
        metrics=m_uni, exchange="ring",
    )
    zipf_skew, uni_skew = skew_ratio(m_ring.journal), skew_ratio(m_uni.journal)
    assert zipf_skew > 1.5 * uni_skew, (zipf_skew, uni_skew)


# ---- fault contract -------------------------------------------------------


def test_mid_ring_device_loss_reforms_and_matches():
    """A device lost mid-ring (between the plan and exchange dispatches)
    invalidates the exchange; the mesh re-forms over the survivors and the
    job re-runs there — same contract as the all_to_all path, verified
    down to a sorted, checksum-matching output."""
    from dsort_tpu.models.validate import _multiset
    from dsort_tpu.scheduler import FaultInjector, SpmdScheduler

    inj = FaultInjector()
    sched = SpmdScheduler(
        job=JobConfig(settle_delay_s=0.01, exchange="ring"), injector=inj
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
    # Fault timeline: attempt -> death -> re-form -> fresh ring plan.
    assert types.index("worker_dead") < types.index("mesh_reform")
    assert "exchange_step" in types[types.index("mesh_reform"):]
    assert types[-1] == "job_done"
    # The re-formed 7-device ring ran 6 transfer steps after the first
    # attempt's 7.
    assert m.counters["exchange_ring_steps"] == 13


def test_ring_keep_on_device_validates(mesh8):
    from dsort_tpu.scheduler import SpmdScheduler

    sched = SpmdScheduler(job=JobConfig(exchange="ring"))
    data = gen_uniform(1 << 17, seed=9)
    h = sched.sort(data, keep_on_device=True)
    rep = h.validate_on_device()
    assert rep.sorted_ok and rep.records == len(data)
    np.testing.assert_array_equal(h.to_host(), np.sort(data))


def test_ring_via_scheduler_checkpoint_path(tmp_path):
    # The checkpointed shuffle path (sort_ranges) honors the ring override:
    # ranges persist and a re-run fully restores, exchange schedule intact.
    from dsort_tpu.scheduler import SpmdScheduler

    job = JobConfig(checkpoint_dir=str(tmp_path), exchange="ring")
    sched = SpmdScheduler(job=job)
    data = gen_uniform(1 << 16, seed=2)
    m = _metered()
    out = sched.sort(data, metrics=m, job_id="ringckpt")
    np.testing.assert_array_equal(out, np.sort(data))
    assert m.counters["exchange_ring_steps"] == 7
    m2 = Metrics()
    out2 = sched.sort(data, metrics=m2, job_id="ringckpt")
    np.testing.assert_array_equal(out2, np.sort(data))
    assert m2.counters.get("shuffle_phase_restores") == 1
    # Fully restored: no exchange ran at all.
    assert "exchange_ring_steps" not in m2.counters


# ---- every schedule on every workload -------------------------------------

SCHEDULE_WORKLOADS = ["uniform_int32", "zipf_int32", "uniform_int64", "kv_records"]


@pytest.mark.parametrize("workload", SCHEDULE_WORKLOADS)
@pytest.mark.parametrize("exchange", ["alltoall", "ring", "fused"])
def test_schedule_sorts_workload(mesh8, exchange, workload):
    """Each exchange schedule returns the reference order on each workload
    and charges its wire bytes; the lax ring dispatches P-1 steps with no
    capacity retry, and the fused ring one kernel launch."""
    n = 20_000
    m = Metrics()
    if workload == "kv_records":
        # The ring schedules take no secondary key, so the records are
        # ordered by their 8-byte prefix alone: distinct here, so the
        # reference order is exact.
        keys, payload = gen_terasort(4096, seed=3)
        assert len(np.unique(keys)) == len(keys)
        ss = SampleSort(mesh8, JobConfig(key_dtype=np.uint64,
                                         payload_bytes=payload.shape[1]))
        out_k, out_v = ss.sort_kv(keys, payload, metrics=m, exchange=exchange)
        order = np.argsort(keys)
        np.testing.assert_array_equal(out_k, keys[order])
        np.testing.assert_array_equal(out_v, payload[order])
    else:
        dtype = np.int64 if workload.endswith("int64") else np.int32
        data = (gen_zipf(n, a=1.3, dtype=dtype, seed=4)
                if workload.startswith("zipf") else gen_uniform(n, dtype, seed=0))
        ss = SampleSort(mesh8, JobConfig(key_dtype=dtype))
        np.testing.assert_array_equal(
            ss.sort(data, metrics=m, exchange=exchange), np.sort(data)
        )
    assert m.counters["exchange_bytes_on_wire"] > 0
    p = mesh8.shape["w"]
    if exchange == "ring":
        assert m.counters.get("capacity_retries", 0) == 0
        ring_steps = dispatches_per_exchange("ring", p)
        assert m.counters["exchange_ring_steps"] == ring_steps == p - 1
    if exchange == "fused":
        launches = dispatches_per_exchange("fused", p)
        assert m.counters["fused_exchange_launches"] == launches == 1


def test_cli_run_with_ring_exchange(tmp_path):
    """`dsort run --exchange ring` sorts a file through the ring schedule."""
    from dsort_tpu import cli

    rng = np.random.default_rng(23)
    inp = tmp_path / "in.txt"
    inp.write_text("\n".join(str(x) for x in rng.integers(0, 10**6, 4000)))
    out = tmp_path / "out.txt"
    journal = tmp_path / "run.jsonl"
    rc = cli.main([
        "run", str(inp), "-o", str(out), "--exchange", "ring",
        "--journal", str(journal),
    ])
    assert rc == 0
    got = np.loadtxt(out, dtype=np.int64)
    expect = np.sort(np.loadtxt(inp, dtype=np.int64))
    np.testing.assert_array_equal(got, expect)


# ---- the eager merge tower (the TPU-side merge-as-you-receive path) -------
#
# On the CPU mesh `merge_kernel="auto"` resolves to the flat re-sort, which
# the ring defers to one end-of-ring combine (folding eagerly under a flat
# re-sort would multiply merge work by log P — see `parallel.exchange`).
# Forcing the run-merge kernels exercises the eager tower itself: per-step
# folds, the unequal-length final fold, and the kv (key, tag) folds.


def test_ring_eager_tower_bitonic(mesh8):
    ss = SampleSort(mesh8, JobConfig(merge_kernel="bitonic"))
    data = gen_uniform(30_000, seed=61)
    a = ss.sort(data)
    r = ss.sort(data, exchange="ring")
    np.testing.assert_array_equal(a, r)


def test_ring_eager_tower_bitonic_7_devices():
    # Non-pow2 P: the tower's final fold merges leftover unequal ranks.
    from dsort_tpu.parallel.mesh import local_device_mesh

    ss = SampleSort(local_device_mesh(7), JobConfig(merge_kernel="bitonic"))
    data = gen_uniform(10_000, seed=62)
    np.testing.assert_array_equal(
        ss.sort(data, exchange="ring"), np.sort(data)
    )


@pytest.mark.slow  # interpret-mode block merges: one per tower fold on CPU
def test_ring_eager_tower_block_merge(mesh8):
    ss = SampleSort(mesh8, JobConfig(merge_kernel="block_merge"))
    data = gen_uniform(10_000, seed=63)
    np.testing.assert_array_equal(
        ss.sort(data, exchange="ring"), np.sort(data)
    )


@pytest.mark.slow  # interpret-mode block kv merges per fold on CPU
def test_ring_eager_tower_block_merge_kv(mesh8):
    job = JobConfig(key_dtype=np.uint64, merge_kernel="block_merge",
                    payload_bytes=92)
    ss = SampleSort(mesh8, job)
    keys, payload = gen_terasort(4_000, seed=24)
    sk, sv = ss.sort_kv(keys, payload, exchange="ring")
    np.testing.assert_array_equal(sk, np.sort(keys))
    assert sorted(zip(sk.tolist(), map(bytes, sv))) == sorted(
        zip(keys.tolist(), map(bytes, payload))
    )


def test_ring_kv_sentinel_keys(mesh8):
    # Real keys equal to the padding sentinel keep their payloads through
    # the ring's tagged runs (the `_merge_received_kv` tiebreak invariant).
    sent = np.iinfo(np.int32).max
    rng = np.random.default_rng(12)
    keys = rng.integers(0, 1000, 5000).astype(np.int32)
    keys[:300] = sent
    vals = np.arange(5000, dtype=np.int32).reshape(-1, 1)
    ss = SampleSort(mesh8, JobConfig(payload_bytes=4))
    ks, vs = ss.sort_kv(keys, vals, exchange="ring")
    np.testing.assert_array_equal(ks, np.sort(keys))
    np.testing.assert_array_equal(np.sort(vs[:, 0]), np.arange(5000))
    np.testing.assert_array_equal(keys[vs[:, 0]], ks)


@pytest.mark.slow  # interpret-mode block kv merges per fold on CPU
def test_ring_eager_tower_block_merge_kv_sentinel_keys(mesh8):
    # The block-path tower fold must not let block_merge_runs_kv's internal
    # (local-scale) pad ranks displace real sentinel-keyed records whose
    # GLOBAL tags are larger — the pre-pad in `_merge2_kv` exists for this.
    sent = np.iinfo(np.int32).max
    rng = np.random.default_rng(13)
    keys = rng.integers(0, 1000, 4000).astype(np.int32)
    keys[:250] = sent
    vals = np.arange(4000, dtype=np.int32).reshape(-1, 1)
    ss = SampleSort(mesh8, JobConfig(merge_kernel="block_merge", payload_bytes=4))
    ks, vs = ss.sort_kv(keys, vals, exchange="ring")
    np.testing.assert_array_equal(ks, np.sort(keys))
    np.testing.assert_array_equal(np.sort(vs[:, 0]), np.arange(4000))
    np.testing.assert_array_equal(keys[vs[:, 0]], ks)


def test_exchange_resize_not_faked_by_quantization():
    # Rounding a step cap up to the quantization rung must NOT fire
    # exchange_resize: the event means "the padded path would have
    # overflowed here", so it keys on the MEASURED max, not the cap.
    from dsort_tpu.parallel.exchange import note_ring_plan, ring_caps

    p, n_local = 8, 1024  # policy cap 168, quantum 16
    hist = np.full((p, p), 161, np.int64)  # <=168 measured, quantizes to 176
    caps = ring_caps(hist, n_local, p)
    assert max(caps) > 168  # quantization DID round past the policy cap
    m = _metered()
    note_ring_plan(m, caps, hist, n_local, p, 4, 1.3)
    assert "exchange_resize" not in m.journal.types()
    hist[2, 5] = 500  # a genuinely overflowing bucket (shift k=3)
    m2 = _metered()
    note_ring_plan(m2, ring_caps(hist, n_local, p), hist, n_local, p, 4, 1.3)
    resizes = [e for e in m2.journal.events() if e.type == "exchange_resize"]
    assert [e.fields["step"] for e in resizes] == [3]
    assert resizes[0].fields["observed"] == 500
