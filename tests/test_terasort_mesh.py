"""TeraSort records across a four-device mesh, against a plain reference.

The path under test is the one `dsort terasort --workers 4` takes:
``SampleSort(mesh).sort_kv(prefix, payload, secondary=terasort_secondary(
payload))`` with the default job, so the records cross the all-to-all
(keys, payload rows, bucket lengths and the secondary) and are combined by
the three-key ``lax.sort``.  Every output record is compared byte for byte
with a NumPy reference that imports nothing of the program.
"""

import numpy as np
import pytest

from dsort_tpu.config import JobConfig
from dsort_tpu.data.ingest import terasort_secondary
from dsort_tpu.parallel.exchange import alltoall_wire_bytes
from dsort_tpu.parallel.sample_sort import SampleSort, cap_pair_policy
from dsort_tpu.utils.metrics import Metrics

P = 4
#: Bytes one slot of the exchange carries: the uint64 key prefix, the
#: 92-byte payload row (key bytes 8-9, then the 90-byte value) and the
#: uint16 secondary.
SLOT_BYTES = 8 + 92 + 2


def _gensort(rng, n):
    """gensort-like records in the program's layout: (prefix, payload)."""
    raw = rng.integers(0, 256, size=(n, 100), dtype=np.uint8)
    return raw[:, :8].copy().view(">u8").reshape(-1).astype(np.uint64), raw[:, 8:].copy()


def _collisions(rng, n):
    """Half the records share one of 64 prefixes, with key bytes 8-9 drawn
    from 16 values: splitters fall on repeated prefixes, the secondary
    orders each run, and some full 10-byte keys repeat."""
    prefix, payload = _gensort(rng, n)
    hit = rng.random(n) < 0.5
    common = rng.integers(0, 2**64, size=64, dtype=np.uint64)
    prefix[hit] = common[rng.integers(0, 64, size=hit.sum())]
    payload[hit, 0] = 0
    payload[hit, 1] = rng.integers(0, 16, size=hit.sum())
    return prefix, payload


def _skewed(rng, n):
    """Sixty percent of the records share one prefix: its bucket outgrows
    the policy's capacity on every source, so the job retries."""
    prefix, payload = _gensort(rng, n)
    prefix[rng.random(n) < 0.6] = np.uint64(0x8000_0000_0000_0000)
    return prefix, payload


CASES = {
    "uniform-exact": (_gensort, 8192),
    "uniform-ragged": (_gensort, 8192 + 13),
    "prefix-collisions": (_collisions, 6000),
    "capacity-retry": (_skewed, 4096),
}


def _reference(prefix, payload):
    """Records in order of the 10-byte key (8-byte prefix, then payload
    bytes 0-1), records with equal keys in byte order."""
    cols = [payload[:, j] for j in range(payload.shape[1] - 1, -1, -1)]
    order = np.lexsort(cols + [prefix])
    return prefix[order], payload[order]


def _in_byte_order_within_ties(prefix, payload, key_tail=True):
    """The output with each run of equal 10-byte keys (equal prefixes where
    ``key_tail`` is false) put in byte order."""
    tail = payload[:, 0].astype(np.uint32) << 8 | payload[:, 1] if key_tail else prefix
    cols = [payload[:, j] for j in range(payload.shape[1] - 1, -1, -1)]
    run = np.r_[0, np.cumsum((prefix[1:] != prefix[:-1]) | (tail[1:] != tail[:-1]))]
    order = np.lexsort(cols + [run])
    return prefix[order], payload[order]


class _RetryTap:
    """Collects the capacity of every re-dispatch (``capacity_retry``)."""

    def __init__(self):
        self.caps = []

    def observe(self, etype, fields, mono, metrics):
        if etype == "capacity_retry":
            self.caps.append(fields["cap_pair"])


@pytest.fixture(scope="module")
def mesh4(devices):
    from dsort_tpu.parallel.mesh import local_device_mesh

    return local_device_mesh(P)


@pytest.mark.parametrize("case", list(CASES))
def test_terasort_records_across_four_devices(mesh4, case):
    make, n = CASES[case]
    prefix, payload = make(np.random.default_rng(len(case) * 1009 + n), n)
    before = prefix.copy(), payload.copy()
    tap = _RetryTap()
    m = Metrics(taps=[tap])

    out_p, out_v = SampleSort(mesh4).sort_kv(
        prefix, payload, metrics=m, secondary=terasort_secondary(payload))

    np.testing.assert_array_equal(prefix, before[0])
    np.testing.assert_array_equal(payload, before[1])
    ref_p, ref_v = _reference(prefix, payload)
    assert out_p.dtype == prefix.dtype and out_v.shape == payload.shape
    np.testing.assert_array_equal(out_p, ref_p)
    np.testing.assert_array_equal(out_v[:, :2], ref_v[:, :2])  # the 10-byte keys, in order
    out_p, out_v = _in_byte_order_within_ties(out_p, out_v)
    np.testing.assert_array_equal(out_v, ref_v)

    retries = m.counters.get("capacity_retries", 0)
    assert retries == len(tap.caps)
    if case == "capacity-retry":
        assert retries >= 1
    # Every attempt's wire bytes count the key, the payload row and the
    # secondary of each slot.
    n_local = (-(-n // P) + 7) // 8 * 8  # the largest shard, rounded up to 8
    caps = [cap_pair_policy(n_local, JobConfig().capacity_factor, P)] + tap.caps
    assert m.counters["exchange_bytes_on_wire"] == sum(
        alltoall_wire_bytes(c, SLOT_BYTES, P) for c in caps)


#: (devices, secondary, exchange, case) of the landing cases: a ring asked
#: for on one device runs the all-to-all, and a secondary with the ring
#: runs the all-to-all too, as `dsort terasort` would.
LANDING = [(p, sec, exch, case) for p in (1, P) for sec in (True, False)
           for exch in ("alltoall", "ring")
           for case in ("uniform-ragged", "prefix-collisions")]


@pytest.mark.parametrize(
    "p,secondary,exchange,case", LANDING,
    ids=[f"{p}dev-{'sec' if s else 'nosec'}-{e}-{c}" for p, s, e, c in LANDING])
def test_terasort_payload_lands_row_major(devices, p, secondary, exchange, case):
    """The payload leaves the device in row order: the output is one
    C-contiguous ``(n, 92)`` buffer landed by plain copies, and it is the
    reference's records."""
    from dsort_tpu.parallel.mesh import local_device_mesh

    make, n = CASES[case]
    prefix, payload = make(np.random.default_rng(p * 7919 + n), n)
    m = Metrics()

    out_p, out_v = SampleSort(local_device_mesh(p)).sort_kv(
        prefix, payload, metrics=m, exchange=exchange,
        secondary=terasort_secondary(payload) if secondary else None)

    assert out_v.shape == (n, 92) and out_v.flags.c_contiguous
    assert m.counters.get("land_strided_copies", 0) == 0
    ref_p, ref_v = _reference(prefix, payload)
    np.testing.assert_array_equal(out_p, ref_p)
    if secondary:
        np.testing.assert_array_equal(out_v[:, :2], ref_v[:, :2])
    _, out_v = _in_byte_order_within_ties(out_p, out_v, key_tail=secondary)
    np.testing.assert_array_equal(out_v, ref_v)
