"""Distributed sample sort: splitters + all_to_all shuffle + per-chip merge.

This is the TPU-native replacement for the reference's entire data plane: the
master's paged TCP scatter (``server.c:342-398``), the workers' local sorts
(``client.c:140-173``), the paged gather (``server.c:412-452``), and the
centralized O(N*k) merge (``server.c:481-524``) all collapse into ONE jitted
SPMD program over the device mesh:

  1. each device sorts its local shard (``lax.sort``);
  2. each device contributes ``oversample`` evenly-spaced sample keys;
     an ``all_gather`` + replicated sort picks P-1 splitters (the sample-sort
     analogue of choosing rotation boundaries, SURVEY.md §5.7);
  3. since the local shard is sorted, each destination bucket is a contiguous
     slice; slices are packed into a static ``(P, cap)`` send buffer;
  4. one ``all_to_all`` over ICI redistributes buckets so device p owns the
     p-th global key range — this is where the reference's O(N) master NIC
     bottleneck becomes an O(N/P)-per-link collective;
  5. each device merges its received runs (re-sort of the static buffer).

Shapes are static (XLA requirement): buffers are padded with the dtype
sentinel and carry valid counts.  Skewed inputs can overflow a bucket's
static capacity; overflow is detected on-device and surfaced so the caller
(``SampleSort.sort`` / the scheduler) retries with a larger capacity factor —
the splitter-quality feedback loop SURVEY.md §7 calls out for Zipf inputs.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dsort_tpu.config import JobConfig
from dsort_tpu.data.partition import pad_kv_to_shards, pad_to_layout, pad_to_shards
from dsort_tpu.obs.prof import LEDGER, instrument_jit
from dsort_tpu.parallel.exchange import note_alltoall_attempt
from dsort_tpu.ops.float_order import is_float_key_dtype, sort_float_keys_via_uint
from dsort_tpu.ops.local_sort import sentinel_for, sort_keys, sort_padded
from dsort_tpu.utils.compat import shard_map
from dsort_tpu.utils.logging import get_logger
from dsort_tpu.utils.metrics import Metrics, PhaseTimer

log = get_logger("sample_sort")


def cap_pair_policy(n_local: int, factor: float, num_workers: int) -> int:
    """Static per-(src,dst) bucket capacity: ceil'd, 8-aligned, clamped.

    THE capacity policy — every driver (single-job, batched, multi-host)
    derives its all_to_all buffer size here, so a headroom/alignment tuning
    lands everywhere at once.  Never exceeds ``n_local`` (a bucket cannot
    hold more than the shard's valid keys), never below 8.
    """
    cap = int(np.ceil(factor * n_local / num_workers))
    cap = min(-(-cap // 8) * 8, max(n_local, 8))
    return max(cap, 8)


def cap_from_observed(max_len: int, n_local: int, num_workers: int) -> int:
    """Retry capacity from a *measured* max bucket length, quantized.

    The overflow retry used to blindly double ``capacity_factor``; the shard
    program now reports its largest bucket, so one retry sizes the buffer to
    exactly what the data needs (+5% headroom against nothing).  Quantizing
    up to 1/8 of the ideal bucket size bounds the number of distinct
    compiled programs a skewed workload can demand (<= ~9 steps between the
    ideal and the ``n_local`` clamp) while wasting <= 12.5% padding —
    the VERDICT r2 successor of the blanket 2.0x factor.
    """
    step = max(n_local // (8 * num_workers), 8)
    cap = -(-int(max_len * 1.05 + 1) // step) * step
    cap = min(-(-cap // 8) * 8, max(n_local, 8))
    return max(cap, 8)


def next_cap_pair(
    observed: int, cap_pair: int, n_local: int, num_workers: int
) -> int:
    """The one overflow-retry resize rule, shared by every driver.

    An overflow implies ``observed > cap_pair`` and ``cap_pair < n_local``,
    so the measured resize is strictly larger; the ``max`` makes that
    growth invariant explicit rather than trusted.
    """
    return max(cap_from_observed(observed, n_local, num_workers), cap_pair + 8)


def _choose_splitters(xs_sorted, count, num_workers: int, oversample: int, axis: str):
    """Per-device samples -> all_gather -> P-1 global splitters (replicated)."""
    s = oversample
    n_local = xs_sorted.shape[0]
    sent = sentinel_for(xs_sorted.dtype)
    j = jnp.arange(s, dtype=jnp.float32)
    idx = ((j + 0.5) * count.astype(jnp.float32) / s).astype(jnp.int32)
    idx = jnp.clip(idx, 0, max(n_local - 1, 0))
    samples = jnp.where(count > 0, xs_sorted[idx], sent)
    all_samples = sort_keys(jax.lax.all_gather(samples, axis, tiled=True))
    return all_samples[s * jnp.arange(1, num_workers)]


def _bucket_slices(xs_sorted, count, splitters, cap_pair: int):
    """Contiguous per-destination slices of a sorted shard, as static buffers.

    Returns (gather_index, valid_mask, lens, overflow): index/mask shape
    ``(P, cap_pair)`` selecting each destination's slice, ``lens`` the true
    bucket sizes, ``overflow`` whether any bucket exceeded ``cap_pair``.
    Keys equal to a splitter go to the splitter's right bucket (side='left'),
    so bucket p holds exactly [splitters[p-1], splitters[p]).
    """
    n_local = xs_sorted.shape[0]
    bounds = jnp.clip(
        jnp.searchsorted(xs_sorted, splitters, side="left").astype(jnp.int32),
        0,
        count,
    )
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), bounds])
    ends = jnp.concatenate([bounds, count[None].astype(jnp.int32)])
    lens = jnp.maximum(ends - starts, 0)
    overflow = jnp.any(lens > cap_pair)
    gidx = starts[:, None] + jnp.arange(cap_pair, dtype=jnp.int32)[None, :]
    valid = jnp.arange(cap_pair, dtype=jnp.int32)[None, :] < lens[:, None]
    return jnp.clip(gidx, 0, max(n_local - 1, 0)), valid, lens, overflow


def _resolve_merge_kernel(
    merge_kernel: str, kernel: str, dtype, total: int
) -> str:
    """Resolve ``merge_kernel='auto'``: block_merge wherever the block
    kernel would carry the flat sort, the plain re-sort otherwise.

    Measured on-chip at the SPMD shape (8 runs x 2^17, r4 bench artifact):
    block_merge 0.063 ms vs full block re-sort 0.385 ms vs jnp bitonic
    tree 16.7 ms — the merge entry is ~6x the re-sort because it runs one
    span-resident pass of ~log P levels instead of K1's 153-stage tile
    sort plus the span pass (VERDICT r3 #2).
    """
    if merge_kernel != "auto":
        return merge_kernel
    from dsort_tpu.ops.local_sort import resolve_kernel

    return (
        "block_merge"
        if resolve_kernel(kernel, dtype, total) == "block"
        else "sort"
    )


def _merge_received(recv: jax.Array, merge_kernel: str, kernel: str = "lax") -> jax.Array:
    """Combine the received (P, cap) buffer into one sorted (P*cap,) run.

    Each row arrives sorted with sentinel pads at its tail, so rows ARE
    sorted runs: "block_merge" enters the block-bitonic network at merge
    level ``2*cap`` (`ops.block_sort.block_merge_runs` — only ~log P levels
    run, K1's 153-stage tile sort is skipped; measured 6x the re-sort on
    chip, see `_resolve_merge_kernel`); "bitonic" merges them with a
    pure-jnp O(n log P) bitonic merge tree; "sort" re-sorts flat through the
    job's *local kernel* dispatch (``sort_with_kernel``).  "auto" (the
    default) picks block_merge wherever the block kernel applies.  All
    yield identical output.
    """
    merge_kernel = _resolve_merge_kernel(
        merge_kernel, kernel, recv.dtype, recv.size
    )
    if merge_kernel == "block_merge":
        from dsort_tpu.ops.block_sort import block_merge_runs

        return block_merge_runs(recv)
    if merge_kernel == "bitonic":
        from dsort_tpu.ops.bitonic import _ceil_pow2, merge_sorted_runs

        sent = sentinel_for(recv.dtype)
        p, cap = recv.shape
        out_len = p * cap
        # The bitonic network needs power-of-two lengths on both axes; pad
        # rows (non-pow2 mesh after a failure) and row length (cap is only
        # 8-aligned) with sentinels — padded rows/tails stay sorted.
        cap2 = _ceil_pow2(cap)
        if cap2 != cap:
            recv = jnp.concatenate(
                [recv, jnp.full((p, cap2 - cap), sent, recv.dtype)], axis=1
            )
        r = _ceil_pow2(p)
        if r != p:
            recv = jnp.concatenate(
                [recv, jnp.full((r - p, cap2), sent, recv.dtype)]
            )
        # All valid keys sort ahead of the pads, so trimming to the original
        # total keeps every valid element and matches the "sort" path shape.
        return merge_sorted_runs(recv)[:out_len]
    from dsort_tpu.ops.local_sort import sort_with_kernel

    return sort_with_kernel(recv.reshape(-1), kernel)


def _sample_sort_shard(
    xs, count, *, num_workers, oversample, cap_pair, axis,
    kernel="lax", merge_kernel="sort",
):
    """One device's view of the whole distributed sort (runs under shard_map).

    ``xs``: (n_local,) sentinel-padded keys; ``count``: (1,) valid length.
    Returns (merged, out_count (1,), overflow (1,), max_len (1,)) where
    ``max_len`` is the largest send-bucket length — the measurement the
    host's capacity retry sizes the next buffer from.

    ``num_workers == 1`` short-circuits after phase 1: the local sort IS the
    answer, so the splitter/shuffle/merge phases (which would re-sort the
    same array a second time) vanish from the compiled program entirely.

    Each stage runs under a `jax.named_scope` (``local_sort``,
    ``splitters``, ``pack``, ``exchange``, ``merge``), so a device op's
    ``op_name`` metadata names the stage it belongs to.
    """
    sent = sentinel_for(xs.dtype)
    count = count[0]
    with jax.named_scope("local_sort"):                                      # 1
        xs, _ = sort_padded(xs, count, kernel)
    if num_workers == 1:
        no = jnp.zeros((), bool)
        return xs, count[None].astype(jnp.int32), no[None], count[None].astype(jnp.int32)
    with jax.named_scope("splitters"):                                       # 2
        splitters = _choose_splitters(xs, count, num_workers, oversample, axis)
    with jax.named_scope("pack"):                                            # 3
        gidx, valid, lens, overflow = _bucket_slices(xs, count, splitters, cap_pair)
        send = jnp.where(valid, xs[gidx], sent)
    with jax.named_scope("exchange"):                                        # 4
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0)
        lens_recv = jax.lax.all_to_all(lens[:, None], axis, split_axis=0, concat_axis=0)[:, 0]
    with jax.named_scope("merge"):                                           # 5
        merged = _merge_received(recv, merge_kernel, kernel)
        out_count = jnp.sum(lens_recv).astype(jnp.int32)
    return merged, out_count[None], overflow[None], jnp.max(lens)[None]


def _merge_received_kv(
    flat_k, is_pad, num_workers: int, cap_pair: int, merge_kernel: str,
    kernel: str = "lax",
):
    """Sorted permutation of the received kv buffer: (sorted keys, gather perm).

    Order is lexicographic on ``(key, is_pad, position)`` so real keys equal
    to the sentinel keep their payloads (no reserved key values).  "sort"
    re-sorts flat — through `ops.block_sort.block_sort_pairs` when the job's
    local kernel resolves to the block kernel (the tiebreak value
    ``is_pad * total + position`` rides as a second 32-bit plane and comes
    back as the payload gather permutation), via ``lax.sort`` otherwise.
    "bitonic" exploits that each received row is already a sorted run and
    merges them with the kv bitonic merge tree, carrying the same tiebreak.
    """
    total = num_workers * cap_pair
    idx = jnp.arange(total, dtype=jnp.int32)
    merge_kernel = _resolve_merge_kernel(
        merge_kernel, kernel, flat_k.dtype, total
    )
    if merge_kernel == "block_merge":
        from dsort_tpu.ops.block_sort import block_merge_runs_kv

        tieb = is_pad.astype(jnp.int32) * total + idx  # pads after every real
        out_k, tieb_out = block_merge_runs_kv(
            flat_k.reshape(num_workers, cap_pair),
            tieb.reshape(num_workers, cap_pair),
        )
        return out_k, jnp.where(tieb_out < total, tieb_out, 0)
    if merge_kernel == "bitonic":
        from dsort_tpu.ops.bitonic import _ceil_pow2, merge_sorted_runs_kv

        sent = sentinel_for(flat_k.dtype)
        tieb = is_pad.astype(jnp.int32) * total + idx  # pads after every real entry
        runs_k = flat_k.reshape(num_workers, cap_pair)
        runs_t = tieb.reshape(num_workers, cap_pair)
        cap2 = _ceil_pow2(cap_pair)
        r = _ceil_pow2(num_workers)
        # Pad rows/length with (sentinel, ascending tieb >= 2*total) so every
        # padded row stays sorted by (key, tieb) and pads trim off the tail.
        if cap2 != cap_pair:
            pad_t = 2 * total + jnp.broadcast_to(
                jnp.arange(cap2 - cap_pair, dtype=jnp.int32), (num_workers, cap2 - cap_pair)
            )
            runs_k = jnp.concatenate(
                [runs_k, jnp.full((num_workers, cap2 - cap_pair), sent, flat_k.dtype)], axis=1
            )
            runs_t = jnp.concatenate([runs_t, pad_t], axis=1)
        if r != num_workers:
            pad_t = 3 * total + jnp.broadcast_to(
                jnp.arange(cap2, dtype=jnp.int32), (r - num_workers, cap2)
            )
            runs_k = jnp.concatenate(
                [runs_k, jnp.full((r - num_workers, cap2), sent, flat_k.dtype)]
            )
            runs_t = jnp.concatenate([runs_t, pad_t])
        merged_k, merged_t = merge_sorted_runs_kv(runs_k, runs_t)
        out_k, tieb_out = merged_k[:total], merged_t[:total]
        perm = jnp.where(tieb_out < total, tieb_out % total, 0)
        return out_k, perm
    from dsort_tpu.ops.local_sort import resolve_kernel

    if resolve_kernel(kernel, flat_k.dtype, total) == "block":
        from dsort_tpu.ops.block_sort import block_sort_pairs

        tieb = is_pad.astype(jnp.int32) * total + idx  # pads after every real
        out_k, tieb_out = block_sort_pairs(flat_k, tieb)
        return out_k, jnp.where(tieb_out < total, tieb_out, 0)
    is_pad8 = is_pad.astype(jnp.int8)
    out_k, _, perm = jax.lax.sort(
        (flat_k, is_pad8, idx), dimension=-1, num_keys=2, is_stable=False
    )
    return out_k, perm


def _kv_shard_body(
    keys, payload, sec, count, *, num_workers, oversample, cap_pair, axis,
    merge_kernel="sort", kernel="lax",
):
    """Shared kv shuffle body; ``sec`` is an optional (static) tiebreak array.

    With ``sec=None`` this is the plain key+payload sort; with a secondary
    the record order is ``(key, sec)`` and the secondary rides the shuffle
    next to the payload (the combine then always uses ``lax.sort`` — the
    bitonic kv merge tree carries a single tiebreak channel, which the
    (is_pad, sec, position) triple would overflow).

    ``num_workers == 1`` short-circuits after the local sort — the sorted
    records ARE the answer; no splitters, no exchange, no second sort.

    The stages carry the named scopes of `_sample_sort_shard`; each payload
    row gather (`_apply_perm`) runs under ``payload_gather`` inside its
    stage.
    """
    from dsort_tpu.ops.local_sort import _apply_perm, sort_kv2_padded, sort_kv_padded

    sent = sentinel_for(keys.dtype)
    count = count[0]
    # Unstable local sorts: the shuffle interleaves shards, so the kv output
    # never guaranteed input order among equal keys — take the faster network.
    with jax.named_scope("local_sort"):
        if sec is None:
            keys, payload, _ = sort_kv_padded(keys, payload, count, stable=False)
        else:
            keys, sec, payload, _ = sort_kv2_padded(
                keys, sec, payload, count, stable=False
            )
    if num_workers == 1:
        no = jnp.zeros((), bool)[None]
        cnt = count[None].astype(jnp.int32)
        if sec is None:
            return keys, payload, cnt, no, cnt
        return keys, sec, payload, cnt, no, cnt
    with jax.named_scope("splitters"):
        splitters = _choose_splitters(keys, count, num_workers, oversample, axis)
    with jax.named_scope("pack"):
        gidx, valid, lens, overflow = _bucket_slices(keys, count, splitters, cap_pair)
        send_k = jnp.where(valid, keys[gidx], sent)
        send_v = payload[gidx]  # (P, cap_pair, ...) — invalid rows masked by count downstream
        send_s = None if sec is None else sec[gidx]
    with jax.named_scope("exchange"):
        recv_k = jax.lax.all_to_all(send_k, axis, split_axis=0, concat_axis=0)
        recv_v = jax.lax.all_to_all(send_v, axis, split_axis=0, concat_axis=0)
        lens_recv = jax.lax.all_to_all(lens[:, None], axis, split_axis=0, concat_axis=0)[:, 0]
        if sec is not None:
            recv_s = jax.lax.all_to_all(send_s, axis, split_axis=0, concat_axis=0)
    with jax.named_scope("merge"):
        # Re-derive validity after the exchange, then combine so real keys
        # equal to the sentinel keep their payloads (no reserved keys).
        pos = jnp.arange(cap_pair, dtype=jnp.int32)[None, :]
        is_pad = (pos >= lens_recv[:, None]).reshape(-1)
        flat_k = jnp.where(is_pad, sent, recv_k.reshape(-1))
        flat_v = recv_v.reshape((-1,) + recv_v.shape[2:])
        out_count = jnp.sum(lens_recv).astype(jnp.int32)
        max_len = jnp.max(lens)[None]
        if sec is None:
            out_k, perm = _merge_received_kv(
                flat_k, is_pad, num_workers, cap_pair, merge_kernel, kernel
            )
            out_v = _apply_perm(flat_v, perm, 0)
            return out_k, out_v, out_count[None], overflow[None], max_len
        idx = jnp.arange(num_workers * cap_pair, dtype=jnp.int32)
        out_k, _, out_s, perm = jax.lax.sort(
            (flat_k, is_pad.astype(jnp.int8), recv_s.reshape(-1), idx),
            dimension=-1,
            num_keys=3,
            is_stable=False,
        )
        out_v = _apply_perm(flat_v, perm, 0)
    return out_k, out_s, out_v, out_count[None], overflow[None], max_len


def _sample_sort_kv_shard(keys, payload, count, **kw):
    """Key+payload variant (TeraSort records): payload rides the same shuffle."""
    return _kv_shard_body(keys, payload, None, count, **kw)


def _sample_sort_kv2_shard(keys, sec, payload, count, **kw):
    """Two-level-key variant: records order by ``(key, sec)`` (TeraSort's full
    10-byte key = 8-byte primary + 2-byte secondary; SURVEY.md §6 config #4).

    Splitters come from the primary key only — every record with the same
    primary lands in the same bucket (`_bucket_slices` is side='left'
    consistent), so breaking primary ties by ``sec`` locally inside each
    destination yields the exact global order.
    """
    return _kv_shard_body(keys, payload, sec, count, **kw)


def _shard_rows(arr, p: int):
    """Per-device row accessor for a 1-axis-sharded array, D2H overlapped.

    When every shard is locally addressable, all per-shard device->host
    copies start async TOGETHER (``copy_to_host_async``) so the transfers
    pipeline while the caller lands earlier rows into its output buffer;
    otherwise one bulk fetch.  Rows come back shaped
    ``(global_len // p,) + trailing``.
    """
    if arr.is_fully_addressable and len(arr.addressable_shards) == p:
        shards = sorted(
            arr.addressable_shards, key=lambda s: s.index[0].start or 0
        )
        for s in shards:
            s.data.copy_to_host_async()
        return lambda i: np.asarray(shards[i].data)
    m = np.asarray(arr)
    m = m.reshape((p, m.shape[0] // p) + m.shape[1:])
    return lambda i: m[i]


@contextlib.contextmanager
def _device_program(timer, uploaded=()):
    """The ``spmd_sort`` phase in two steps: wait until ``uploaded`` is on
    the device (``spmd_sort.h2d``; `jax.device_put` returns before its
    transfer ends), then the body: dispatch and completion fetch
    (``spmd_sort.program``)."""
    with timer.phase("spmd_sort"):
        if uploaded:
            with timer.step("h2d"):
                jax.block_until_ready(uploaded)
        with timer.step("program"):
            yield


def _flat_rows(fn, at: int):
    """``fn`` with its output ``at``, a payload of rows, flattened to 1-D.

    XLA gives a 2-D ``u8[rows, 92]`` output the column-major layout
    ``{0,1}`` on the TPU, so its host copy arrives with strides
    ``(1, rows)`` and lands through a transposing copy.  A 1-D array has one
    layout on every backend and no lane padding: its host copy is the rows
    in order, which `_land_shards` reshapes back (``row_shapes``) as a view.
    The wrapper keeps ``fn``'s name, which names the jitted program.
    """

    def flat(*args):
        outs = fn(*args)
        return (*outs[:at], outs[at].reshape(-1), *outs[at + 1:])

    flat.__name__ = flat.__qualname__ = getattr(fn, "func", fn).__name__
    return flat


def _land_shards(
    timer, arrays, c, n: int, p: int, row_shapes=None
) -> list[np.ndarray]:
    """Copy the first ``c[i]`` rows of shard ``i`` of each of ``arrays``
    into one output buffer per array, in shard order.

    An array is a 1-axis-sharded device array or, after a straggler serve,
    a host list of per-shard ranges.  ``row_shapes[j]`` is the row shape of
    a flat ``arrays[j]`` (`_flat_rows`); each shard's host copy is reshaped
    to rows without a copy.  Runs inside the ``assemble`` phase: the waits
    for each shard's host copy are ``assemble.d2h`` (the copies start
    together, `_shard_rows`), the copies into the buffers ``assemble.land``,
    each summed over the shards.  A host copy that is not C-contiguous
    lands through a strided copy and bumps ``land_strided_copies``.
    """
    row_shapes = row_shapes or [()] * len(arrays)
    with timer.step("d2h"):
        rows = [a.__getitem__ if isinstance(a, list) else _shard_rows(a, p)
                for a in arrays]
    outs = [np.empty(n, dtype=a[0].dtype if a else np.int32) if isinstance(a, list)
            else np.empty((n,) + a.shape[1:] + rs, dtype=a.dtype)
            for a, rs in zip(arrays, row_shapes)]
    off = 0
    for i in range(p):
        ci = int(c[i])
        with timer.step("d2h"):
            got = [row(i) for row in rows]
        with timer.step("land"):
            for out, g in zip(outs, got):
                g = g.reshape((-1,) + out.shape[1:])
                if not g.flags.c_contiguous:
                    timer.metrics.bump("land_strided_copies")
                out[off : off + ci] = g[:ci]
        off += ci
    if off != n:  # a short concat was detectable; a torn buffer is not
        raise RuntimeError(f"device range counts sum to {off}, expected {n} rows")
    return outs


class SampleSort:
    """Host-facing driver for the SPMD sample sort over a 1-D worker mesh.

    Handles padding/layout, jit caching per shape, overflow retries with a
    growing capacity factor, and global assembly of the sorted output.
    """

    def __init__(self, mesh: Mesh, job: JobConfig | None = None, axis_name: str = "w"):
        self.mesh = mesh
        self.axis = axis_name
        self.job = job or JobConfig()
        self.num_workers = mesh.shape[axis_name]
        #: Optional callable invoked between the ring plan and exchange
        #: dispatches (the one host-visible seam inside the shuffle) — the
        #: scheduler hangs its fault injector here so the mid-ring
        #: device-loss drill has a real injection point.  Raising
        #: `WorkerFailure` from it aborts the exchange exactly as a device
        #: death surfaced by XLA would.
        self.fault_hook = None
        #: Optional callable () -> int | None naming the CURRENT measured
        #: straggler's mesh position (the health plane's rolling verdict,
        #: or a drill's injected pick).  On a coded dispatch the named
        #: device's range is raced: the owner fetch vs a replica/parity
        #: reconstruction, first finisher wins the exactly-once claim
        #: (`coded_straggler_serve`).  No failure is involved.
        self.straggler_fn = None
        #: Optional callable (position) -> seconds of extra latency the
        #: owner-fetch leg of the straggler race sleeps first — the
        #: simulation stand-in for a slow device's D2H (the injector's
        #: `FaultInjector.delay_for` hangs here).
        self.fetch_delay_fn = None
        #: Owner-fetch threads that LOST their race and were left to
        #: finish in the background (the real system discards a late
        #: straggler response; joining would forfeit the latency win).
        #: `join_stragglers` drains them before a journal is read.
        self._straggler_threads: list = []

    def _resolve_exchange(self, exchange: str | None) -> str:
        from dsort_tpu.parallel.exchange import resolve_exchange

        return resolve_exchange(exchange, self.job.exchange, self.num_workers)

    def _resolve_redundancy(self, redundancy: int | None) -> int:
        from dsort_tpu.parallel.exchange import resolve_redundancy

        return resolve_redundancy(
            redundancy, self.job.redundancy, self.num_workers
        )

    def _resolve_redundancy_mode(self, mode: str | None) -> str:
        from dsort_tpu.parallel.exchange import resolve_redundancy_mode

        return resolve_redundancy_mode(
            mode, getattr(self.job, "redundancy_mode", "replicate")
        )

    def join_stragglers(self) -> None:
        """Drain owner-fetch threads that lost a straggler race — call
        before reading the journal (their late ``coded_owner_fetch``
        lands when the fetch completes, as on real hardware)."""
        while self._straggler_threads:
            self._straggler_threads.pop().join()

    @functools.lru_cache(maxsize=32)
    def _build(
        self, n_local: int, cap_pair: int, kv_trailing: tuple, secondary: bool = False
    ):
        """Compile the shard_map'd program for one (shape, capacity) combo."""
        p = self.num_workers
        kwargs = dict(
            num_workers=p,
            oversample=self.job.oversample,
            cap_pair=cap_pair,
            axis=self.axis,
        )
        if kv_trailing is None:
            fn = functools.partial(
                _sample_sort_shard,
                kernel=self.job.local_kernel,
                merge_kernel=self.job.merge_kernel,
                **kwargs,
            )
            in_specs = (P(self.axis), P(self.axis))
            out_specs = (P(self.axis),) * 4
        elif secondary:
            fn = _flat_rows(functools.partial(
                _sample_sort_kv2_shard, merge_kernel=self.job.merge_kernel,
                kernel=self.job.local_kernel, **kwargs
            ), 2)
            in_specs = (P(self.axis),) * 4
            out_specs = (P(self.axis),) * 6
        else:
            fn = _flat_rows(functools.partial(
                _sample_sort_kv_shard, merge_kernel=self.job.merge_kernel,
                kernel=self.job.local_kernel, **kwargs
            ), 1)
            in_specs = (P(self.axis), P(self.axis), P(self.axis))
            out_specs = (P(self.axis),) * 5
        # Donate the keys buffer on the keys-only path: the merged output
        # (same dtype, >= size) can alias it, halving peak HBM at scale.
        # Not on CPU (XLA CPU ignores donation with a warning per
        # executable), and not for kv (the payload re-upload a retry would
        # then need dwarfs the aliasing win).
        donate = (
            (0,)
            if kv_trailing is None
            and next(iter(self.mesh.devices.flat)).platform != "cpu"
            else ()
        )
        tag = "spmd" if kv_trailing is None else (
            "spmd_kv2" if secondary else "spmd_kv"
        )
        # The introspection ledger's key mirrors `serve.variants.
        # spmd_variant_key` (tag, P, n_local, cap, dtype, kernel, exchange)
        # — the dtype joins at call time, exactly what the jit specializes
        # on (obs.prof).
        return instrument_jit(
            jax.jit(
                shard_map(
                    fn, mesh=self.mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False,
                ),
                donate_argnums=donate,
            ),
            key_fn=lambda *a: (
                tag, p, n_local, cap_pair, str(a[0].dtype),
                self.job.local_kernel, "alltoall",
            ),
        )

    def _cap_pair(self, n_local: int, factor: float) -> int:
        return cap_pair_policy(n_local, factor, self.num_workers)

    # -- ring exchange programs (parallel.exchange) -------------------------

    def _donate_keys(self, kv: bool) -> tuple:
        """Donation rule shared with `_build` (see the comment there)."""
        return (
            (0,)
            if not kv and next(iter(self.mesh.devices.flat)).platform != "cpu"
            else ()
        )

    @functools.lru_cache(maxsize=32)
    def _build_plan(self, n_local: int, kv_trailing: tuple | None = None):
        """Ring plan phase: local sort + splitters + lengths histogram.

        The sorted shard (and payload) stays device-resident; only the
        replicated ``(P, P)`` histogram crosses to the host to size the
        per-step ring buffers.
        """
        from dsort_tpu.parallel.exchange import (
            _ring_plan_kv_shard,
            _ring_plan_shard,
        )

        kwargs = dict(
            num_workers=self.num_workers,
            oversample=self.job.oversample,
            axis=self.axis,
            kernel=self.job.local_kernel,
        )
        if kv_trailing is None:
            fn = functools.partial(_ring_plan_shard, **kwargs)
            in_specs = (P(self.axis), P(self.axis))
            out_specs = (P(self.axis), P(), P())
        else:
            fn = functools.partial(_ring_plan_kv_shard, **kwargs)
            in_specs = (P(self.axis),) * 3
            out_specs = (P(self.axis), P(self.axis), P(), P())
        tag = "spmd_plan" if kv_trailing is None else "spmd_plan_kv"
        return instrument_jit(
            jax.jit(
                shard_map(
                    fn, mesh=self.mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False,
                ),
                donate_argnums=self._donate_keys(kv_trailing is not None),
            ),
            key_fn=lambda *a: (
                tag, self.num_workers, n_local, str(a[0].dtype),
                self.job.local_kernel, "ring",
            ),
        )

    @functools.lru_cache(maxsize=32)
    def _build_ring(
        self, n_local: int, caps: tuple, kv_trailing: tuple | None = None
    ):
        """Ring exchange phase for one measured per-step capacity tuple.

        ``caps`` is quantized (`exchange.ring_caps`), so the number of
        distinct compiled ring programs a skewed workload can demand stays
        bounded — the cache key is the ladder rung, not the raw histogram.
        """
        from dsort_tpu.parallel.exchange import (
            _ring_exchange_kv_shard,
            _ring_exchange_shard,
        )

        kwargs = dict(
            num_workers=self.num_workers,
            caps=caps,
            axis=self.axis,
            merge_kernel=self.job.merge_kernel,
            kernel=self.job.local_kernel,
        )
        if kv_trailing is None:
            fn = functools.partial(_ring_exchange_shard, **kwargs)
            in_specs = (P(self.axis), P(self.axis), P())
            out_specs = (P(self.axis),) * 3
        else:
            fn = _flat_rows(functools.partial(_ring_exchange_kv_shard, **kwargs), 1)
            in_specs = (P(self.axis), P(self.axis), P(self.axis), P())
            out_specs = (P(self.axis),) * 4
        # Same donation policy as `_build`: the sorted keys buffer is dead
        # after this dispatch (no retry exists on the ring path), so donate
        # it on the keys-only non-CPU path — without this the ring would
        # hold xs_sorted live next to the merged output, ~2x the all_to_all
        # path's peak HBM.
        tag = "spmd_ring" if kv_trailing is None else "spmd_ring_kv"
        return instrument_jit(
            jax.jit(
                shard_map(
                    fn, mesh=self.mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False,
                ),
                donate_argnums=self._donate_keys(kv_trailing is not None),
            ),
            key_fn=lambda *a: (
                tag, self.num_workers, n_local, caps, str(a[0].dtype),
                self.job.local_kernel,
            ),
        )

    @functools.lru_cache(maxsize=32)
    def _build_coded(
        self,
        n_local: int,
        caps: tuple,
        redundancy: int,
        mode: str = "replicate",
        kv_trailing: tuple | None = None,
    ):
        """Coded ring exchange: the measured-caps ring schedule PLUS a
        redundancy plane — replica slots (every bucket additionally ships
        to its destination's ``redundancy-1`` ring successors) or parity
        slots (each device retains its own out-buckets zero-wire and ships
        only XOR / GF(256) RAID-6 parity of them to its successors), so a
        lost device's range survives reconstructible off-device
        (`parallel.coded`).  Same plan, same caps ladder as `_build_ring`;
        only built for ``redundancy > 1``.  ``kv_trailing`` selects the
        payload-carrying twins — kv jobs get the same coverage, not a
        silent uncoded downgrade.  No donation yet: the coded plane is
        exercised on the cpu mesh today (XLA CPU ignores donation) —
        revisit the sorted-keys alias with the ICI port."""
        from dsort_tpu.parallel.exchange import (
            _coded_ring_exchange_kv_shard,
            _coded_ring_exchange_shard,
            _parity_ring_exchange_kv_shard,
            _parity_ring_exchange_shard,
        )

        kwargs = dict(
            num_workers=self.num_workers,
            caps=caps,
            axis=self.axis,
            redundancy=redundancy,
            merge_kernel=self.job.merge_kernel,
            kernel=self.job.local_kernel,
        )
        parity = mode == "parity"
        if kv_trailing is None:
            shard_fn = (
                _parity_ring_exchange_shard if parity
                else _coded_ring_exchange_shard
            )
            in_specs = (P(self.axis), P(self.axis), P())
            n_out = 6 if parity else 5
        else:
            shard_fn = (
                _parity_ring_exchange_kv_shard if parity
                else _coded_ring_exchange_kv_shard
            )
            in_specs = (P(self.axis), P(self.axis), P(self.axis), P())
            n_out = 9 if parity else 7
        fn = functools.partial(shard_fn, **kwargs)
        if kv_trailing is not None:
            fn = _flat_rows(fn, 1)
        tag = ("spmd_parity" if parity else "spmd_coded") + (
            "" if kv_trailing is None else "_kv"
        )
        return instrument_jit(
            jax.jit(
                shard_map(
                    fn, mesh=self.mesh,
                    in_specs=in_specs,
                    out_specs=(P(self.axis),) * n_out, check_vma=False,
                ),
            ),
            key_fn=lambda *a: (
                tag, self.num_workers, n_local, caps, redundancy,
                str(a[0].dtype), self.job.local_kernel,
            ),
        )

    @functools.lru_cache(maxsize=32)
    def _build_fused(
        self, n_local: int, caps: tuple, kv_trailing: tuple | None = None
    ):
        """Fused ring exchange (`ops.ring_kernel`): the whole P-1-step
        schedule plus the merge as ONE ``pallas_call`` per device.

        Same plan, same measured ``caps``, same cache-key ladder as
        `_build_ring`; the extra replicated ``hist`` input supplies the
        output counts (the lax ring ppermutes lengths instead), so the
        shard program issues exactly one transfer dispatch.
        """
        from dsort_tpu.ops.ring_kernel import (
            fused_mesh,
            fused_ring_exchange_kv_shard,
            fused_ring_exchange_shard,
        )

        kwargs = dict(
            num_workers=self.num_workers,
            caps=caps,
            axis=self.axis,
            merge_kernel=self.job.merge_kernel,
            kernel=self.job.local_kernel,
        )
        if kv_trailing is None:
            fn = functools.partial(fused_ring_exchange_shard, **kwargs)
            in_specs = (P(self.axis), P(self.axis), P(), P())
            out_specs = (P(self.axis),) * 3
        else:
            fn = _flat_rows(functools.partial(fused_ring_exchange_kv_shard, **kwargs), 1)
            in_specs = (P(self.axis), P(self.axis), P(self.axis), P(), P())
            out_specs = (P(self.axis),) * 4
        # Donation policy matches `_build_ring`: no retry exists past the
        # plan, the sorted keys buffer is dead after this dispatch.
        tag = "spmd_fused" if kv_trailing is None else "spmd_fused_kv"
        return instrument_jit(
            jax.jit(
                shard_map(
                    fn, mesh=fused_mesh(self.mesh, self.axis),
                    in_specs=in_specs, out_specs=out_specs, check_vma=False,
                ),
                donate_argnums=self._donate_keys(kv_trailing is not None),
            ),
            key_fn=lambda *a: (
                tag, self.num_workers, n_local, caps, str(a[0].dtype),
                self.job.local_kernel,
            ),
        )

    @functools.lru_cache(maxsize=32)
    def _build_hier(self, n_local: int, plan):
        """Two-level exchange phase for one planned capacity rung
        (`exchange._hier_exchange_shard`): the intra-host aggregation ring,
        one merged DCN transfer per (src-host, dst-host) pair, the local
        scatter + merge — all in one program.  ``plan`` is a `HierPlan`:
        every cap sits on the same quantization ladder as `_build_ring`'s
        ``caps`` tuple, so the compile cache stays rung-bounded.  Same
        donation policy as `_build_ring` (keys-only path, no retry)."""
        from dsort_tpu.parallel.exchange import _hier_exchange_shard

        fn = functools.partial(
            _hier_exchange_shard,
            num_workers=self.num_workers,
            hosts=plan.hosts,
            agg_cap=plan.agg_cap,
            leg_caps=plan.leg_caps,
            scatter_cap=plan.scatter_cap,
            axis=self.axis,
            merge_kernel=self.job.merge_kernel,
            kernel=self.job.local_kernel,
        )
        return instrument_jit(
            jax.jit(
                shard_map(
                    fn, mesh=self.mesh,
                    in_specs=(P(self.axis), P(self.axis), P()),
                    out_specs=(P(self.axis),) * 3, check_vma=False,
                ),
                donate_argnums=self._donate_keys(False),
            ),
            key_fn=lambda *a: (
                "spmd_hier", self.num_workers, n_local, plan,
                str(a[0].dtype), self.job.local_kernel,
            ),
        )

    def _upload_keys(self, data: np.ndarray, timer):
        """The ``partition`` phase of every keys dispatch: the host ``(P,
        cap)`` layout (``partition.pad``; a read-only view of ``data`` when
        every shard is full, a padded copy otherwise, counted in
        ``partition_views``), then ONE ``device_put`` of the ``(keys,
        counts)`` pytree straight from numpy (``partition.put``; it returns
        before the transfer ends, see `_device_program`).  Returns the host
        layout and the two device arrays."""
        with timer.phase("partition"):
            with timer.step("pad"):
                shards, counts = pad_to_shards(data, self.num_workers)
                if np.shares_memory(shards, data):
                    timer.metrics.bump("partition_views")
            with timer.step("put"):
                xs, cj = jax.device_put(
                    (shards.reshape(-1), counts), NamedSharding(self.mesh, P(self.axis))
                )
        return shards, xs, cj

    def _dispatch_keys_hier(
        self, data: np.ndarray, timer, metrics: Metrics, hosts: int
    ):
        """Hier counterpart of `_dispatch_keys_ring`: plan once, reduce the
        measured (P, P) histogram to the (H, H) host matrix, dispatch the
        three-phase program.  Same no-retry doctrine — every phase's buffer
        was sized from the measured histogram before the exchange ran, so
        overflow is an invariant violation, not a capacity miss.  The flat
        ring caps for the SAME histogram are computed too: they price the
        ``dcn_bytes_saved`` baseline in `note_hier_plan`."""
        from dsort_tpu.parallel.exchange import (
            check_ring_overflow,
            hier_plan,
            note_hier_plan,
            ring_caps,
        )

        p = self.num_workers
        shards, xs, cj = self._upload_keys(data, timer)
        n_local = shards.shape[1]
        planfn = self._build_plan(n_local)
        with _device_program(timer, (xs, cj)):
            xs_sorted, splitters, hist = planfn(xs, cj)
            hist_h = jax.device_get(hist)
        LEDGER.drain_to(metrics)
        caps = ring_caps(hist_h, n_local, p)
        plan = hier_plan(hist_h, n_local, p, hosts)
        note_hier_plan(
            metrics, plan, caps, hist_h, n_local, p, data.dtype.itemsize,
            self.job.capacity_factor,
        )
        if self.fault_hook is not None:
            self.fault_hook()
        with _device_program(timer):
            hierfn = self._build_hier(n_local, plan)
            merged, out_counts, overflow = hierfn(xs_sorted, cj, splitters)
            c, ov = jax.device_get((out_counts, overflow))
        LEDGER.drain_to(metrics)
        check_ring_overflow(ov)
        return merged, out_counts, c

    def _dispatch_keys_ring(
        self, data: np.ndarray, timer, metrics: Metrics, fused: bool = False,
        redundancy: int = 1, mode: str = "replicate",
        allow_straggler: bool = True,
    ):
        """Ring counterpart of `_dispatch_keys`: plan, size, exchange.

        No capacity-retry loop exists here — the plan phase measured the
        real bucket histogram, so every step's buffer is sized *before* the
        exchange runs (`exchange.ring_caps`); the old whole-job re-dispatch
        becomes a per-step capacity choice.  Overflow on this path means
        the exchange ran against a different splitter plan than the one
        that sized its buffers — an invariant violation, raised loudly.

        ``redundancy > 1`` runs the CODED schedule (`_build_coded`): the
        same plan and caps, plus the redundancy plane — replica slots
        (``mode='replicate'``) or XOR/GF(256) parity slots
        (``mode='parity'``, ~1/P the wire premium).  The fault hook then
        fires AFTER the exchange dispatch — plane placement completes
        with the exchange (see `parallel.coded`'s simulation note), so a
        loss tripping there leaves the survivors holding everything a
        local reconstruction needs; the raised `WorkerFailure` carries the
        `CodedExchangeState` snapshot for the caller's recovery path.

        When the health plane names a live-but-slow device
        (`straggler_fn`), the coded plane doubles as a LATENCY shield: the
        straggler's range is raced — owner fetch vs off-device
        reconstruction — and whichever leg finishes first serves it
        (`_serve_straggler_ring`); the dispatch then returns host ranges
        instead of the sharded device array.
        """
        from dsort_tpu.parallel.exchange import (
            check_ring_overflow,
            note_coded_plan,
            note_fused_plan,
            note_ring_plan,
            ring_caps,
        )
        from dsort_tpu.scheduler.fault import WorkerFailure

        p = self.num_workers
        coded = redundancy > 1
        shards, xs, cj = self._upload_keys(data, timer)
        n_local = shards.shape[1]
        planfn = self._build_plan(n_local)
        with _device_program(timer, (xs, cj)):
            xs_sorted, splitters, hist = planfn(xs, cj)
            # The ONE extra host round-trip the adaptive headroom costs: a
            # (P, P) int32 fetch — vs the padded path's worst case of a
            # full re-dispatch when a bucket overflows.
            hist_h = jax.device_get(hist)
        LEDGER.drain_to(metrics)
        caps = ring_caps(hist_h, n_local, p)
        if coded:
            note_coded_plan(
                metrics, caps, hist_h, n_local, p, data.dtype.itemsize,
                self.job.capacity_factor, redundancy, mode=mode,
            )
        else:
            note = note_fused_plan if fused else note_ring_plan
            note(
                metrics, caps, hist_h, n_local, p, data.dtype.itemsize,
                self.job.capacity_factor,
            )
        if not coded and self.fault_hook is not None:
            self.fault_hook()
        with _device_program(timer):
            if coded:
                codedfn = self._build_coded(n_local, caps, redundancy, mode)
                outs = codedfn(xs_sorted, cj, splitters)
                merged, out_counts, overflow = outs[:3]
            elif fused:
                fusedfn = self._build_fused(n_local, caps)
                merged, out_counts, overflow = fusedfn(
                    xs_sorted, cj, splitters, hist
                )
            else:
                ringfn = self._build_ring(n_local, caps)
                merged, out_counts, overflow = ringfn(xs_sorted, cj, splitters)
        if coded and self.fault_hook is not None:
            try:
                self.fault_hook()
            except WorkerFailure as e:
                # The loss surfaced with the redundancy plane already
                # placed: snapshot what the survivors hold so the caller's
                # recovery is a local merge/solve, not a re-run
                # (parallel.coded).
                e.coded_state = self._snapshot_coded(
                    caps, redundancy, len(data), mode, outs
                )
                raise
        if coded and allow_straggler and self.straggler_fn is not None:
            s = self.straggler_fn()
            if s is not None and 0 <= int(s) < p:
                with _device_program(timer):
                    served = self._serve_straggler_ring(
                        int(s), outs, caps, redundancy, len(data), mode,
                        metrics,
                    )
                LEDGER.drain_to(metrics)
                return served
        with _device_program(timer):
            # One fetch = completion barrier + the invariant scalar (same
            # doctrine as the all_to_all path).
            c, ov = jax.device_get((out_counts, overflow))
        LEDGER.drain_to(metrics)
        check_ring_overflow(ov)
        return merged, out_counts, c

    def _snapshot_coded(
        self, caps: tuple, redundancy: int, n: int, mode: str, outs,
        kv: bool = False,
    ):
        """Host snapshot of one coded exchange (`parallel.coded`'s shared
        fetch: survivors' trimmed ranges + the redundancy plane, overflow
        invariant checked first).  ``outs`` is the coded shard program's
        full output tuple — its arity selects the matching snapshot
        (keys/kv x replicate/parity)."""
        from dsort_tpu.parallel import coded

        snap = (
            (coded.snapshot_parity_kv_state if mode == "parity"
             else coded.snapshot_kv_state)
            if kv else
            (coded.snapshot_parity_state if mode == "parity"
             else coded.snapshot_state)
        )
        return snap(self.num_workers, redundancy, caps, n, *outs)

    def _serve_straggler_ring(
        self, s: int, outs, caps: tuple, redundancy: int, n: int,
        mode: str, metrics: Metrics,
    ):
        """Serve the measured straggler's range from whichever source
        finishes first — owner fetch vs coded reconstruction.

        The health plane named mesh position ``s`` live-but-slow; no
        failure exists, so no recovery runs.  Two legs race under one
        `parallel.coded.StragglerClaim` (exactly-once):

        - OWNER: a background thread fetches range ``s`` from its owner,
          after the injected/measured extra latency (`fetch_delay_fn`) —
          the simulation stand-in for a slow device's D2H.  It always
          journals ``coded_owner_fetch`` (won or lost) when the fetch
          completes, which may be AFTER the sort returned
          (`join_stragglers` drains it).
        - HOLDER: runs inline — every OTHER range comes off the coded
          snapshot anyway, so the wait is shared — and reconstructs
          range ``s`` from the replica/parity plane exactly as if ``s``
          were unavailable.

        The winner's copy serves; only a HOLDER win journals the typed
        ``coded_straggler_serve`` (the contract grammar pins at most one
        per (job, range) scope).  Both copies are bit-identical — this
        trades redundant work for tail latency, never correctness.
        Returns ``(ranges, None, c)`` with host ranges, the list-input
        form `_assemble_ranges` accepts.
        """
        from dsort_tpu.parallel.coded import CodedBudgetExceeded, StragglerClaim

        claim = StragglerClaim()
        owner_box = {}

        def owner_leg():
            t0 = time.perf_counter()
            delay = (
                self.fetch_delay_fn(s)
                if self.fetch_delay_fn is not None else None
            )
            if delay:
                time.sleep(float(delay))
            row = np.asarray(jax.device_get(outs[0])).reshape(
                self.num_workers, -1
            )[s]
            won = claim.claim("owner")
            if won:
                owner_box["row"] = row
            metrics.event(
                "coded_owner_fetch", range=int(s), won=bool(won),
                wall_s=round(time.perf_counter() - t0, 6),
            )

        t = threading.Thread(target=owner_leg, daemon=True)
        t.start()
        t0 = time.perf_counter()
        state = self._snapshot_coded(caps, redundancy, n, mode, outs)
        try:
            ranges, info = state.reconstruct([s])
        except CodedBudgetExceeded:
            # The plane cannot cover s off-device (e.g. degenerate tiny
            # mesh) — wait for the owner; its fetch is authoritative.
            t.join()
            ranges = list(state.ranges)
            ranges[s] = owner_box["row"][: len(state.ranges[s])]
            c = np.array([len(r) for r in ranges], np.int64)
            return ranges, None, c
        if claim.claim("holder"):
            metrics.bump("coded_straggler_serves")
            metrics.event(
                "coded_straggler_serve", range=int(s), mode=mode,
                holders=info.get("holders", {}).get(s),
                recovered_keys=int(len(ranges[s])),
                wall_s=round(time.perf_counter() - t0, 6),
            )
            # The owner's late response is discarded on arrival, as on
            # real hardware; the thread drains via join_stragglers.
            self._straggler_threads.append(t)
        else:
            # Owner won the claim — its fetch already completed; serve its
            # copy (bit-identical to the reconstruction by construction).
            t.join()
            ranges[s] = owner_box["row"][: len(ranges[s])]
        c = np.array([len(r) for r in ranges], np.int64)
        return ranges, None, c

    def _dispatch_kv_ring(
        self, xs, vs, cj, n_local: int, trailing: tuple, slot_bytes: int,
        timer, metrics: Metrics, fused: bool = False, redundancy: int = 1,
        mode: str = "replicate", n: int = 0,
    ):
        """kv ring dispatch: plan (kv local sort + histogram), size, exchange.

        The payload stays device-resident between the two dispatches and
        rides the ppermute steps next to its keys; ``slot_bytes`` (key +
        payload row) prices the wire-bytes accounting — the payload rows
        count ONCE per step on both the lax and the fused schedule (on the
        fused path they also move exactly once: one remote DMA per step,
        then one gather by the merged tags).

        ``redundancy > 1`` runs the coded kv schedule: payload rows get
        the SAME replica/parity coverage as their keys (no silent uncoded
        downgrade — ARCHITECTURE §18); the fault hook fires after
        the exchange with the kv snapshot attached to the raised
        `WorkerFailure`, exactly as on the keys path.
        """
        from dsort_tpu.parallel.exchange import (
            check_ring_overflow,
            note_coded_plan,
            note_fused_plan,
            note_ring_plan,
            ring_caps,
        )
        from dsort_tpu.scheduler.fault import WorkerFailure

        p = self.num_workers
        coded = redundancy > 1
        planfn = self._build_plan(n_local, kv_trailing=trailing)
        with _device_program(timer, (xs, vs, cj)):
            ks, vsort, splitters, hist = planfn(xs, vs, cj)
            hist_h = jax.device_get(hist)
        LEDGER.drain_to(metrics)
        caps = ring_caps(hist_h, n_local, p)
        if coded:
            note_coded_plan(
                metrics, caps, hist_h, n_local, p, slot_bytes,
                self.job.capacity_factor, redundancy, mode=mode,
            )
        else:
            note = note_fused_plan if fused else note_ring_plan
            note(
                metrics, caps, hist_h, n_local, p, slot_bytes,
                self.job.capacity_factor,
            )
        if not coded and self.fault_hook is not None:
            self.fault_hook()
        with _device_program(timer):
            if coded:
                codedfn = self._build_coded(
                    n_local, caps, redundancy, mode, trailing
                )
                outs = codedfn(ks, vsort, cj, splitters)
                out_k, out_v, out_counts, overflow = outs[:4]
            elif fused:
                fusedfn = self._build_fused(n_local, caps, kv_trailing=trailing)
                out_k, out_v, out_counts, overflow = fusedfn(
                    ks, vsort, cj, splitters, hist
                )
            else:
                ringfn = self._build_ring(n_local, caps, kv_trailing=trailing)
                out_k, out_v, out_counts, overflow = ringfn(
                    ks, vsort, cj, splitters
                )
        if coded and self.fault_hook is not None:
            try:
                self.fault_hook()
            except WorkerFailure as e:
                e.coded_state = self._snapshot_coded(
                    caps, redundancy, n, mode, outs, kv=True
                )
                raise
        with _device_program(timer):
            c, ov = jax.device_get((out_counts, overflow))
        LEDGER.drain_to(metrics)
        check_ring_overflow(ov)
        return out_k, out_v, c

    def sort(
        self,
        data: np.ndarray,
        metrics: Metrics | None = None,
        keep_on_device: bool = False,
        exchange: str | None = None,
        redundancy: int | None = None,
        redundancy_mode: str | None = None,
    ) -> np.ndarray:
        """Sort a host array; returns the globally sorted host array.

        Float keys (incl. NaN/±0.0/±inf) ride the pipeline as order-preserving
        uints (`ops.float_order`): NaNs sort last like ``np.sort`` and come
        back canonicalized, never trimmed as pads.

        ``keep_on_device=True`` returns a `DeviceSortResult` instead: the
        sorted global array stays sharded on the mesh (no D2H at all —
        the completion fetch carries only the retry scalars), with lazy
        ``.to_host()``, donation-chaining ``.consume(fn)``, and
        ``.validate_on_device()``.  Integer/uint keys only: a float job's
        device-resident representation would be the mapped ordered uints,
        which a next jitted stage must not mistake for values.

        ``exchange`` ("alltoall" | "ring" | "fused") overrides
        `JobConfig.exchange` for this call: "ring" replaces the one-shot
        padded ``all_to_all`` with the adaptive ppermute schedule of
        `parallel.exchange` — bit-identical output, actual-histogram buffer
        sizing, and the merge overlapped with the transfers; "fused" runs
        that same measured schedule as ONE Pallas kernel per device
        (`ops.ring_kernel`: in-kernel async remote DMAs, P-1 dispatches
        collapsed to one launch, then the lax ring's own merge).
        """
        data = np.asarray(data)
        if keep_on_device:
            if is_float_key_dtype(data.dtype):
                raise TypeError(
                    "keep_on_device supports integer keys only (float keys "
                    "ride as mapped ordered uints the consumer would "
                    "misread); use sort() for floats"
                )
            return self._sort_device_impl(
                data, metrics, exchange=exchange, redundancy=redundancy,
                redundancy_mode=redundancy_mode,
            )
        if is_float_key_dtype(data.dtype):
            return sort_float_keys_via_uint(
                self.sort, data, metrics, exchange=exchange,
                redundancy=redundancy, redundancy_mode=redundancy_mode,
            )
        if len(data) == 0:
            return np.asarray(data).copy()
        # The ranges are views into ONE preallocated output buffer laid out
        # in global order, so the buffer IS the sorted array — no
        # np.concatenate re-copy (VERDICT r4 next #1).
        buf, _ = self._sort_ranges_impl(
            data, metrics, exchange=exchange, redundancy=redundancy,
            redundancy_mode=redundancy_mode,
        )
        return buf

    def sort_ranges(
        self, data: np.ndarray, metrics: Metrics | None = None,
        exchange: str | None = None, redundancy: int | None = None,
        redundancy_mode: str | None = None,
    ) -> list[np.ndarray]:
        """Like `sort`, but returns the per-device key ranges separately.

        Range ``i`` holds the ``i``-th interval of the key space (ranges
        concatenate to the sorted output; they are views into one backing
        buffer laid out in that order) — the unit the SPMD scheduler
        persists for shuffle-phase recovery (SURVEY.md §5.4).  Callers
        handle float keys themselves (`SpmdScheduler` maps them to ordered
        uints *before* any checkpointed phase).
        """
        return self._sort_ranges_impl(
            data, metrics, exchange=exchange, redundancy=redundancy,
            redundancy_mode=redundancy_mode,
        )[1]

    def _sort_ranges_impl(
        self, data: np.ndarray, metrics: Metrics | None = None,
        exchange: str | None = None, redundancy: int | None = None,
        redundancy_mode: str | None = None,
    ) -> tuple[np.ndarray, list[np.ndarray]]:
        """Shared core: returns ``(sorted buffer, per-device range views)``.

        Data-plane doctrine (VERDICT r4 next #1 — the rebuilt plane must
        not re-centralize cost into host memcpy the way the reference
        centralized its merge, ``server.c:481-524``):

        - partition: the pad layout is a view of the input when every shard
          is full and a one-pass copy otherwise, then ONE ``device_put`` of the
          ``(keys, counts)`` pytree straight from numpy — no ``jnp.asarray``
          staging hop through the default device.
        - device side: the keys buffer is DONATED (the merged output can
          alias it; halves peak HBM at the 2^26 scale).  A capacity retry
          re-uploads from the host layout it still holds.
        - assemble: per-shard D2H copies start async TOGETHER
          (``copy_to_host_async``), then each lands in its slot of one
          preallocated output buffer; the returned ranges are views into
          it.  No whole-buffer ``np.asarray`` + slice + concat chain.
        """
        data = np.asarray(data)
        if is_float_key_dtype(data.dtype):
            raise TypeError(
                "sort_ranges takes pre-mapped keys; use sort() for floats"
            )
        if len(data) == 0:
            return data.copy(), [data.copy()]
        metrics = metrics if metrics is not None else Metrics()
        timer = PhaseTimer(metrics)
        merged, _, c = self._dispatch_keys(
            data, timer, metrics, exchange, redundancy,
            redundancy_mode=redundancy_mode,
        )
        with timer.phase("assemble"):
            return self._assemble_ranges(
                merged, c, len(data), self.num_workers, timer
            )

    def _dispatch_keys(
        self, data: np.ndarray, timer, metrics: Metrics,
        exchange: str | None = None, redundancy: int | None = None,
        redundancy_mode: str | None = None, allow_straggler: bool = True,
    ):
        """Upload + run the SPMD program with measured-capacity retries.

        The shared dispatch core of the host-returning (`sort_ranges`) and
        device-resident (`keep_on_device`) paths: returns ``(merged,
        out_counts, c)`` — the sharded device output, its device per-shard
        counts, and the host copy of those counts the retry loop already
        fetched (the ONE small device->host fetch that is both the
        completion barrier and every retry scalar).

        A resolved ``redundancy > 1`` forces the lax ring schedule: the
        replica plane rides the ring's ppermute steps (`parallel.coded`) —
        the padded all_to_all has no per-step seam to ship replicas on, and
        the fused kernel carries no replica slots yet.

        With ``job.autotune`` on, the exchange schedule is PLANNED here —
        per dispatch, from a measured skew probe of this job's actual keys
        (obs.plan, ARCHITECTURE §15) — unless the user set it explicitly
        (per-call ``exchange=`` or an ``explicit``-marked config value), in
        which case the explicit value wins and a ``plan_override`` is
        journaled.
        """
        red = self._resolve_redundancy(redundancy)
        mode = self._resolve_redundancy_mode(redundancy_mode)
        if getattr(self.job, "autotune", False):
            from dsort_tpu.obs.plan import planned_exchange
            from dsort_tpu.parallel.exchange import resolve_hier_hosts

            fused_ok = all(
                d.platform == "tpu" for d in self.mesh.devices.flat
            )
            # The planner's measured host topology (obs.plan is
            # backend-free, so the probe happens here): >= 2 hosts with
            # >= 2 devices each arms the two-level "hier" schedule.  Only
            # a REAL topology signal counts — a multi-process launch or a
            # requested hier_hosts grouping; the simulated 2-host default
            # must not re-route every >= 4-device single-slice run
            # through a DCN leg that does not exist.
            want = getattr(self.job, "hier_hosts", 0)
            hosts = (
                resolve_hier_hosts(want, self.num_workers)
                if want or jax.process_count() > 1 else 0
            )
            exchange = planned_exchange(
                self.job, data, self.num_workers, metrics,
                call_value=exchange, fused_ok=fused_ok, redundancy=red,
                hosts=hosts,
            )
        exch = self._resolve_exchange(exchange)
        if red > 1 and exch != "ring":
            log.warning(
                "redundancy=%d needs the lax ring schedule; overriding "
                "exchange=%r to 'ring' for this dispatch", red, exch,
            )
            exch = "ring"
        if exch == "hier":
            from dsort_tpu.parallel.exchange import resolve_hier_hosts

            hosts = resolve_hier_hosts(
                getattr(self.job, "hier_hosts", 0), self.num_workers
            )
            if hosts >= 2:
                return self._dispatch_keys_hier(data, timer, metrics, hosts)
            log.warning(
                "exchange='hier' needs >= 4 workers grouped into >= 2 "
                "hosts (have %d); downgrading to the flat ring schedule",
                self.num_workers,
            )
            exch = "ring"
        if exch in ("ring", "fused"):
            return self._dispatch_keys_ring(
                data, timer, metrics, fused=exch == "fused", redundancy=red,
                mode=mode, allow_straggler=allow_straggler,
            )
        p = self.num_workers
        shards, xs, cj = self._upload_keys(data, timer)
        n_local = shards.shape[1]
        cap_pair = self._cap_pair(n_local, self.job.capacity_factor)
        for attempt in range(self.job.max_capacity_retries + 1):
            if attempt > 0:
                # The previous dispatch consumed (donated) xs; rebuild it
                # from the host layout.  Retries are rare (the resize is
                # measured, one retry converges) and already pay a compile.
                with timer.phase("partition"), timer.step("put"):
                    xs = jax.device_put(
                        shards.reshape(-1), NamedSharding(self.mesh, P(self.axis))
                    )
            fn = self._build(n_local, cap_pair, None)
            with _device_program(timer, (xs, cj)):
                merged, out_counts, overflow, max_len = fn(xs, cj)
                # ONE small device->host fetch both forces completion (it
                # waits on the whole executable) and carries every scalar the
                # retry loop needs; separate block_until_ready + per-array
                # np.asarray calls would cost 2 extra round trips per sort.
                c, ov, ml = jax.device_get((out_counts, overflow, max_len))
            note_alltoall_attempt(metrics, cap_pair, data.dtype.itemsize, p)
            LEDGER.drain_to(metrics)
            if not bool(ov.any()):
                return merged, out_counts, c
            metrics.bump("capacity_retries")
            # Size the retry from the measured max bucket (one retry
            # converges: splitters are deterministic for the same data).
            observed = int(ml.max())
            cap_pair = next_cap_pair(observed, cap_pair, n_local, p)
            metrics.event(
                "capacity_retry", observed=observed, cap_pair=cap_pair
            )
            log.warning(
                "bucket overflow (attempt %d, max bucket %d): retrying with "
                "cap_pair=%d", attempt + 1, observed, cap_pair,
            )
        raise RuntimeError("sample sort bucket overflow after max retries")

    def _sort_device_impl(
        self, data: np.ndarray, metrics: Metrics | None,
        exchange: str | None = None, redundancy: int | None = None,
        redundancy_mode: str | None = None,
    ):
        """`keep_on_device` core: dispatch, then hand out the sharded result.

        No assemble phase exists — the sorted global array stays where the
        SPMD program left it (range-partitioned over the mesh), wrapped in a
        `DeviceSortResult` carrying the per-shard lengths/offsets and the
        device copy of the counts (so on-device validation costs zero H2D).
        """
        from dsort_tpu.parallel.device_result import DeviceSortResult

        metrics = metrics if metrics is not None else Metrics()
        timer = PhaseTimer(metrics)
        if len(data) == 0:
            import jax.numpy as jnp

            handle = DeviceSortResult(
                jnp.zeros((0,), dtype=data.dtype),
                shard_lengths=np.zeros(1, np.int64),
                n=0, metrics=metrics,
            )
        else:
            # Straggler serving returns host ranges — incompatible with a
            # device-resident result, so the race is disabled here; the
            # coded fault plane itself still applies.
            merged, out_counts, c = self._dispatch_keys(
                data, timer, metrics, exchange, redundancy,
                redundancy_mode=redundancy_mode, allow_straggler=False,
            )
            handle = DeviceSortResult(
                merged,
                shard_lengths=c,
                n=len(data),
                mesh=self.mesh,
                axis=self.axis,
                counts_dev=out_counts,
                metrics=metrics,
            )
        metrics.bump("device_handles")
        metrics.event(
            "device_handle", n_keys=handle.n, shards=handle.num_shards
        )
        return handle

    def _assemble_ranges(
        self, merged, c, n: int, p: int, timer
    ) -> tuple[np.ndarray, list[np.ndarray]]:
        """Land per-device ranges into one output buffer, fetches overlapped.

        ``merged`` is either the sharded device array or — after a
        straggler serve — an already-host list of trimmed per-device
        ranges; both land through `_land_shards`.  Returns the buffer and
        its per-device range views.
        """
        (out,) = _land_shards(timer, [merged], c, n, p)
        ends = np.cumsum([int(c[i]) for i in range(p)])
        return out, [out[e - int(c[i]) : e] for i, e in enumerate(ends)]

    def sort_kv(
        self,
        keys: np.ndarray,
        payload: np.ndarray,
        metrics: Metrics | None = None,
        secondary: np.ndarray | None = None,
        exchange: str | None = None,
        redundancy: int | None = None,
        redundancy_mode: str | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """TeraSort-style key+payload sort; payloads follow their keys.

        ``secondary`` (optional, same length as ``keys``) breaks primary-key
        ties, so sort keys wider than one machine word — TeraSort's 10-byte
        key as an 8-byte primary + 2-byte secondary — order exactly instead
        of relying on prefix uniqueness.  With a secondary the combine always
        uses the ``lax.sort`` merge; every other ``JobConfig.merge_kernel``
        ('bitonic', 'block_merge') is ignored on this path (warned below).

        ``redundancy > 1`` runs the coded ring schedule with FULL payload
        coverage: record replicas or parity rows ride the plane next to
        their keys, so a kv job's mid-shuffle loss recovers by local merge
        exactly like a keys job's (v1 silently downgraded kv to uncoded).
        A ``secondary`` key still forces the all_to_all combine, which has
        no coded plane — that one remaining downgrade is warned.
        """
        keys = np.asarray(keys)
        if is_float_key_dtype(keys.dtype):
            return sort_float_keys_via_uint(
                self.sort_kv, keys, payload, metrics, secondary,
                exchange=exchange, redundancy=redundancy,
                redundancy_mode=redundancy_mode,
            )
        exch = self._resolve_exchange(exchange)
        red = self._resolve_redundancy(redundancy)
        mode = self._resolve_redundancy_mode(redundancy_mode)
        if red > 1 and secondary is not None:
            log.warning(
                "redundancy=%d needs the ring schedule, which has no "
                "secondary-key channel; this two-level-key sort runs "
                "uncoded (re-run recovery)", red,
            )
            red = 1
        if red > 1 and exch not in ("ring",):
            log.warning(
                "redundancy=%d needs the lax ring schedule; overriding "
                "exchange=%r to 'ring' for this kv dispatch", red, exch,
            )
            exch = "ring"
        if exch == "hier":
            # The two-level schedule is keys-only today: the payload plane
            # would need tag channels through both the aggregation merge and
            # the scatter merge (ARCHITECTURE §17 scope).
            log.warning(
                "exchange='hier' is keys-only; this kv sort uses the lax "
                "ring schedule",
            )
            exch = "ring"
        if exch in ("ring", "fused") and secondary is not None:
            # The ring's tag plane carries (is_pad, position); adding the
            # secondary would need a third merge channel per fold — the
            # two-level-key job keeps the one-shot lax.sort combine.
            log.warning(
                "exchange=%r does not support a secondary key; using "
                "the all_to_all exchange", exch,
            )
            exch = "alltoall"
        if secondary is not None and self.job.merge_kernel not in ("sort", "auto"):
            log.warning(
                "merge_kernel=%r is not available with a secondary key; "
                "using the lax.sort combine", self.job.merge_kernel,
            )
        metrics = metrics if metrics is not None else Metrics()
        timer = PhaseTimer(metrics)
        p = self.num_workers
        if len(keys) == 0:
            return np.asarray(keys).copy(), np.asarray(payload).copy()
        with timer.phase("partition"):
            with timer.step("pad"):
                sk, sv, counts = pad_kv_to_shards(keys, payload, p)
                host_parts = [
                    sk.reshape(-1), sv.reshape((-1,) + sv.shape[2:]), counts,
                ]
                planes = [(sk, keys), (sv, payload)]
                if secondary is not None:
                    ss = pad_to_layout(secondary, counts, sk.shape[1])
                    host_parts.append(ss.reshape(-1))
                    planes.append((ss, secondary))
                if all(np.shares_memory(a, b) for a, b in planes):
                    metrics.bump("partition_views")
            # ONE device_put of the whole pytree straight from numpy — no
            # jnp.asarray staging hop through the default device, no
            # per-array transfer dispatch (VERDICT r4 next #1).
            with timer.step("put"):
                uploaded = jax.device_put(
                    host_parts, NamedSharding(self.mesh, P(self.axis))
                )
        xs, vs, cj = uploaded[:3]
        n_local = sk.shape[1]
        slot_bytes = keys.dtype.itemsize + int(
            np.prod(sv.shape[2:], dtype=np.int64)
        ) * sv.dtype.itemsize
        if secondary is not None:  # the secondary plane crosses the wire too
            slot_bytes += ss.dtype.itemsize
        if exch in ("ring", "fused"):
            out_k, out_v, c = self._dispatch_kv_ring(
                xs, vs, cj, n_local, tuple(sv.shape[2:]), slot_bytes,
                timer, metrics, fused=exch == "fused", redundancy=red,
                mode=mode, n=len(keys),
            )
        else:
            cap_pair = self._cap_pair(n_local, self.job.capacity_factor)
            for attempt in range(self.job.max_capacity_retries + 1):
                fn = self._build(
                    n_local, cap_pair, tuple(sv.shape[2:]), secondary is not None
                )
                with _device_program(timer, uploaded):
                    if secondary is not None:
                        out_k, _, out_v, out_counts, overflow, max_len = fn(
                            xs, uploaded[3], vs, cj
                        )
                    else:
                        out_k, out_v, out_counts, overflow, max_len = fn(xs, vs, cj)
                    # One fetch = completion barrier + every retry scalar (see
                    # sort_ranges).
                    c, ov, ml = jax.device_get((out_counts, overflow, max_len))
                note_alltoall_attempt(metrics, cap_pair, slot_bytes, p)
                LEDGER.drain_to(metrics)
                if not bool(ov.any()):
                    break
                metrics.bump("capacity_retries")
                observed = int(ml.max())
                cap_pair = next_cap_pair(observed, cap_pair, n_local, p)
                metrics.event(
                    "capacity_retry", observed=observed, cap_pair=cap_pair
                )
            else:
                raise RuntimeError("sample sort bucket overflow after max retries")
        with timer.phase("assemble"):
            keys_out, vals_out = _land_shards(
                timer, [out_k, out_v], c, len(keys), p, [(), tuple(sv.shape[2:])]
            )
        return keys_out, vals_out


class BatchSampleSort:
    """Many independent sort jobs at once over a 2-D ``(dp, w)`` mesh.

    The ``dp`` axis batches whole jobs (each job's keys shard over the ``w``
    worker axis, exactly as in `SampleSort`); one jitted program sorts every
    job in the batch concurrently.  This is the public face of
    ``MeshConfig.dp`` — the analogue of serving the reference's job REPL
    (``server.c:160-167``) many requests at a time instead of one.

    ``sort(jobs)`` takes a list of 1-D host arrays (lengths may differ) and
    returns the list of sorted arrays.
    """

    def __init__(self, mesh: Mesh, job: JobConfig | None = None,
                 axis_name: str = "w", dp_axis_name: str = "dp"):
        self.mesh = mesh
        self.axis = axis_name
        self.dp_axis = dp_axis_name
        self.job = job or JobConfig()
        self.num_workers = mesh.shape[axis_name]
        self.dp = mesh.shape[dp_axis_name]

    @functools.lru_cache(maxsize=32)
    def _build(self, n_local: int, cap_pair: int):
        p = self.num_workers
        shard_fn = functools.partial(
            _sample_sort_shard,
            num_workers=p,
            oversample=self.job.oversample,
            cap_pair=cap_pair,
            axis=self.axis,
            kernel=self.job.local_kernel,
            merge_kernel=self.job.merge_kernel,
        )

        def step(xs_b, counts_b):
            # Per-device block: (jobs_per_dp, n_local) keys + counts.
            return jax.vmap(shard_fn)(xs_b, counts_b)

        return jax.jit(
            shard_map(
                step,
                mesh=self.mesh,
                in_specs=(P(self.dp_axis, self.axis),) * 2,
                out_specs=(P(self.dp_axis, self.axis),) * 4,
                check_vma=False,
            )
        )

    def _bucket_cap(self, n: int) -> int:
        per_shard = max(-(-n // self.num_workers), 1)
        cap = 8
        while cap < per_shard:
            cap *= 2
        return cap

    def _resolve_exchange(self, exchange: str | None) -> str:
        from dsort_tpu.parallel.exchange import resolve_exchange

        return resolve_exchange(exchange, self.job.exchange, self.num_workers)

    @functools.lru_cache(maxsize=32)
    def _build_plan(self, n_local: int):
        """Batched ring plan: every job in the bucket sorts + histograms in
        one vmapped program; the host sizes ONE per-step cap tuple from the
        max over jobs so the bucket still compiles a single exchange."""
        from dsort_tpu.parallel.exchange import _ring_plan_shard

        shard_fn = functools.partial(
            _ring_plan_shard,
            num_workers=self.num_workers,
            oversample=self.job.oversample,
            axis=self.axis,
            kernel=self.job.local_kernel,
        )

        def step(xs_b, counts_b):
            return jax.vmap(shard_fn)(xs_b, counts_b)

        return jax.jit(
            shard_map(
                step,
                mesh=self.mesh,
                in_specs=(P(self.dp_axis, self.axis),) * 2,
                # Per-job splitters/histograms replicate over the worker
                # axis but still shard over dp.
                out_specs=(
                    P(self.dp_axis, self.axis),
                    P(self.dp_axis),
                    P(self.dp_axis),
                ),
                check_vma=False,
            )
        )

    @functools.lru_cache(maxsize=32)
    def _build_ring(self, n_local: int, caps: tuple):
        from dsort_tpu.parallel.exchange import _ring_exchange_shard

        shard_fn = functools.partial(
            _ring_exchange_shard,
            num_workers=self.num_workers,
            caps=caps,
            axis=self.axis,
            merge_kernel=self.job.merge_kernel,
            kernel=self.job.local_kernel,
        )

        def step(xs_b, counts_b, splitters_b):
            return jax.vmap(shard_fn)(xs_b, counts_b, splitters_b)

        return jax.jit(
            shard_map(
                step,
                mesh=self.mesh,
                in_specs=(
                    P(self.dp_axis, self.axis),
                    P(self.dp_axis, self.axis),
                    P(self.dp_axis),
                ),
                out_specs=(P(self.dp_axis, self.axis),) * 3,
                check_vma=False,
            )
        )

    def _job_ckpt(
        self, job_id: str | None, data: np.ndarray,
        payload: np.ndarray | None = None,
    ):
        """Per-job result checkpoint (shard 0 = sorted keys, 1 = payload).

        Brings ``dsort batch`` into the recovery story (VERDICT r3 #7): a
        killed batch re-run restores completed jobs and re-packs the
        buckets over the missing ones.  The fingerprint covers the payload
        too, so editing a record's payload (keys unchanged) re-sorts
        instead of silently restoring the stale permutation.  Returns None
        unless checkpointing is configured for this job.
        """
        if not (self.job.checkpoint_dir and job_id):
            return None
        from dsort_tpu.checkpoint import ShardCheckpoint
        from dsort_tpu.models.external_sort import _fingerprint

        ckpt = ShardCheckpoint(self.job.checkpoint_dir, job_id)
        fp = _fingerprint(data)
        if payload is not None:
            fp += "|" + _fingerprint(payload)
        shards = 1 if payload is None else 2
        if ckpt.sync_manifest(shards, data.dtype, len(data), fp):
            log.warning(
                "batch job %r: checkpointed result belongs to different "
                "data; cleared", job_id,
            )
        return ckpt

    @staticmethod
    def _check_unique_ids(job_ids) -> None:
        ids = [j for j in job_ids if j]
        dupes = sorted({j for j in ids if ids.count(j) > 1})
        if dupes:
            # Two jobs sharing a checkpoint id would fingerprint-clear each
            # other every run — resume would silently never work.
            raise ValueError(f"duplicate job_ids in batch: {dupes}")

    def sort(
        self, jobs, metrics: Metrics | None = None, job_ids=None,
        keep_on_device: bool = False, exchange: str | None = None,
    ):
        """Sort a list of host key arrays; returns the sorted list.

        Jobs are grouped into **size buckets** (per-shard capacity rounded up
        to a power of two) and each bucket runs as its own uniform batch, so
        one 16M-key job in a batch of 1K-key jobs no longer makes every dp
        slot pay the 16M layout (the padded volume drops ~dp-fold; metrics
        counter ``padded_elems`` records what was actually allocated).
        Power-of-two rounding bounds the number of distinct compiled
        programs at log2(largest/smallest).

        ``job_ids`` (optional, parallel to ``jobs``) + ``JobConfig.
        checkpoint_dir`` make the batch resumable: each completed job's
        sorted result persists under its id, a re-run restores those
        without re-sorting (counter ``batch_jobs_restored``), and the
        buckets re-pack over only the missing jobs.  The fingerprint guard
        clears a job's stale result if its data changed.

        ``keep_on_device=True`` returns a list of `DeviceSortResult` handles
        instead of host arrays: each job's sorted keys stay on device as its
        slice of the bucket program's output (lazy ``.to_host()``, jitted
        ``.validate_on_device()``).  Integer keys only, and checkpointing is
        skipped (a device-resident handle is not a persisted artifact).
        """
        metrics = metrics if metrics is not None else Metrics()
        if self.job.redundancy > 1:
            # The replica plane rides the single-job ring schedule only:
            # the batched (dp, w) driver has no coded shard program yet
            # (ARCHITECTURE §14 scope) — run uncoded rather than silently
            # pretending the batch is loss-tolerant.
            log.warning(
                "redundancy=%d applies to single-job keys-only sorts; "
                "this batch runs uncoded (re-run recovery)",
                self.job.redundancy,
            )
        jobs = [np.asarray(j) for j in jobs]
        if not jobs:
            return []
        if any(j.dtype != jobs[0].dtype for j in jobs):
            # Packing mixed dtypes into one batch buffer would silently
            # value-cast keys; refuse loudly.
            raise TypeError(
                f"all jobs must share one key dtype, got "
                f"{sorted({str(j.dtype) for j in jobs})}"
            )
        if keep_on_device:
            if is_float_key_dtype(jobs[0].dtype):
                raise TypeError(
                    "keep_on_device supports integer keys only; use sort() "
                    "for floats"
                )
            if self.job.checkpoint_dir and job_ids:
                log.warning(
                    "keep_on_device skips checkpointing: device-resident "
                    "handles are not persisted artifacts"
                )
            # With no ids, `_job_ckpt` stays None everywhere below — the
            # device-resident batch rides the SAME bucket loop as the
            # eager path, just with `keep=True` and no persistence.
            job_ids = None
        elif is_float_key_dtype(jobs[0].dtype):
            from dsort_tpu.ops.float_order import sort_float_key_batch_via_uint

            # Float keys pre-map to ordered uints; checkpoint under the
            # MAPPED dtype (ids pass through so resume still works).
            return sort_float_key_batch_via_uint(
                self.sort, jobs, metrics, job_ids=job_ids, exchange=exchange
            )
        if job_ids is None:
            job_ids = [None] * len(jobs)
        self._check_unique_ids(job_ids)
        outs: list = [None] * len(jobs)
        ckpts: list = [None] * len(jobs)
        for i, (j, jid) in enumerate(zip(jobs, job_ids)):
            ckpts[i] = self._job_ckpt(jid, j)
            if ckpts[i] is not None and ckpts[i].has(0):
                outs[i] = ckpts[i].load(0)
                metrics.bump("batch_jobs_restored")
        buckets: dict[int, list[int]] = {}
        for i, j in enumerate(jobs):
            if outs[i] is None:
                buckets.setdefault(self._bucket_cap(len(j)), []).append(i)
        for cap in sorted(buckets):
            idxs = buckets[cap]
            for i, out in zip(idxs, self._run_bucket(
                [jobs[i] for i in idxs], None, cap, metrics,
                keep=keep_on_device, exchange=exchange,
            )):
                outs[i] = out
                if ckpts[i] is not None:
                    ckpts[i].save(0, out)
        return outs

    @functools.lru_cache(maxsize=32)
    def _build_kv(self, n_local: int, cap_pair: int, kv_trailing: tuple):
        p = self.num_workers
        shard_fn = functools.partial(
            _sample_sort_kv_shard,
            num_workers=p,
            oversample=self.job.oversample,
            cap_pair=cap_pair,
            axis=self.axis,
            kernel=self.job.local_kernel,
            merge_kernel=self.job.merge_kernel,
        )

        def step(ks_b, vs_b, cs_b):
            # Per-device block: (jobs_per_dp, n_local) keys, counts, and
            # (jobs_per_dp, n_local, ...) payloads.
            return jax.vmap(shard_fn)(ks_b, vs_b, cs_b)

        return jax.jit(
            shard_map(
                step,
                mesh=self.mesh,
                in_specs=(P(self.dp_axis, self.axis),) * 3,
                out_specs=(P(self.dp_axis, self.axis),) * 5,
                check_vma=False,
            )
        )

    def sort_kv(self, pairs, metrics: Metrics | None = None, job_ids=None):
        """Batched key+payload sorts: ``pairs`` is a list of (keys, payload).

        The kv counterpart of `sort` (VERDICT r3 #7): every job's payload
        follows its keys through one batched shuffle program per (size,
        payload-shape) bucket.  With ``job_ids`` + ``checkpoint_dir`` a
        re-run restores completed jobs (keys as shard 0, payload as shard
        1) without re-sorting.  Returns the list of (sorted_keys,
        permuted_payload).  Float keys (incl. NaN) ride as order-preserving
        uints like every other driver (VERDICT r4 weak #5 closed the
        batch-kv asymmetry): NaN-keyed records sort last with their
        payloads attached, keys come back canonicalized.
        """
        metrics = metrics if metrics is not None else Metrics()
        pairs = [(np.asarray(k), np.asarray(v)) for k, v in pairs]
        if not pairs:
            return []
        if any(k.dtype != pairs[0][0].dtype for k, _ in pairs):
            raise TypeError(
                f"all jobs must share one key dtype, got "
                f"{sorted({str(k.dtype) for k, _ in pairs})}"
            )
        if is_float_key_dtype(pairs[0][0].dtype):
            from dsort_tpu.ops.float_order import sort_float_kv_batch_via_uint

            return sort_float_kv_batch_via_uint(
                self.sort_kv, pairs, metrics, job_ids
            )
        if job_ids is None:
            job_ids = [None] * len(pairs)
        self._check_unique_ids(job_ids)
        outs: list = [None] * len(pairs)
        ckpts: list = [None] * len(pairs)
        for i, ((k, v), jid) in enumerate(zip(pairs, job_ids)):
            if len(k) != len(v):
                raise ValueError(
                    f"job {i}: {len(k)} keys vs {len(v)} payload rows"
                )
            ckpts[i] = self._job_ckpt(jid, k, payload=v)
            if ckpts[i] is not None and ckpts[i].has(0) and ckpts[i].has(1):
                outs[i] = (ckpts[i].load(0), ckpts[i].load(1))
                metrics.bump("batch_jobs_restored")
        buckets: dict[tuple, list[int]] = {}
        for i, (k, v) in enumerate(pairs):
            if outs[i] is None:
                key = (self._bucket_cap(len(k)), v.shape[1:], v.dtype.str)
                buckets.setdefault(key, []).append(i)
        for bkey in sorted(buckets, key=str):
            idxs = buckets[bkey]
            for i, out in zip(idxs, self._run_bucket(
                [pairs[i][0] for i in idxs], [pairs[i][1] for i in idxs],
                bkey[0], metrics,
            )):
                outs[i] = out
                if ckpts[i] is not None:
                    ckpts[i].save(0, out[0])
                    ckpts[i].save(1, out[1])
        return outs

    def _run_bucket(
        self, keys_list, payloads_list, cap: int, metrics: Metrics,
        keep: bool = False, exchange: str | None = None,
    ):
        """Sort ONE uniform-capacity batch (every job fits ``(w, cap)``).

        The single bucket driver for both the key-only and kv paths
        (``payloads_list=None`` selects key-only): one copy of the padding
        layout, the measured-capacity retry loop, and the per-worker
        assemble.  Returns sorted key arrays, or (keys, payload) tuples —
        or, with ``keep=True`` (key-only), per-job `DeviceSortResult`
        handles over the batch output's device-resident job slices.
        """
        kv = payloads_list is not None
        timer = PhaseTimer(metrics)
        p, dp = self.num_workers, self.dp
        # Pad the batch to a multiple of dp jobs (empty filler jobs), and
        # every job to ONE shared (w, cap) layout so the program is static.
        n_jobs = len(keys_list)
        batch = -(-n_jobs // dp) * dp
        trailing = payloads_list[0].shape[1:] if kv else ()
        metrics.bump("padded_elems", batch * p * cap)
        with timer.phase("partition"):
            ks = np.empty((batch, p * cap), dtype=keys_list[0].dtype)
            cs = np.zeros((batch, p), dtype=np.int32)
            if kv:
                vs = np.zeros(
                    (batch, p * cap) + trailing, dtype=payloads_list[0].dtype
                )
            for b in range(batch):
                k = keys_list[b] if b < n_jobs else keys_list[0][:0]
                if kv:
                    v = payloads_list[b] if b < n_jobs else payloads_list[0][:0]
                    sk, sv, counts = pad_kv_to_shards(k, v, p, cap=cap)
                    vs[b] = sv.reshape((-1,) + trailing)
                else:
                    sk, counts = pad_to_shards(k, p, cap=cap)
                ks[b] = sk.reshape(-1)
                cs[b] = counts
            # ONE device_put straight from numpy — no jnp.asarray staging
            # hop (the same data-plane rule as `_sort_ranges_impl`).
            sharding = NamedSharding(self.mesh, P(self.dp_axis, self.axis))
            if kv:
                xj, cj, vj = jax.device_put((ks, cs, vs), sharding)
            else:
                xj, cj = jax.device_put((ks, cs), sharding)
        exch = self._resolve_exchange(exchange)
        if exch == "fused":
            # The fused kernel addresses its remote copies by the worker
            # axis index; under the batched 2-D (dp, w) mesh the logical
            # device id needs the dp coordinate too — the batch keeps the
            # lax ring (same caps, same bytes, P-1 dispatches per bucket).
            log.warning(
                "exchange='fused' is single-job only; the batch uses the "
                "lax ring exchange"
            )
            exch = "ring"
        if exch == "hier":
            # The two-level schedule keys its host grouping off the 1-D
            # worker axis; the batched (dp, w) mesh keeps the flat ring.
            log.warning(
                "exchange='hier' is single-job only; the batch uses the "
                "lax ring exchange"
            )
            exch = "ring"
        if exch == "ring" and kv:
            # The batched kv path keeps the one-shot exchange for now: a
            # per-bucket payload-plane ring adds little over the key-only
            # ring the batch API (`sort`) serves.
            log.warning(
                "exchange='ring' is key-only for batched jobs; the kv "
                "batch uses the all_to_all exchange"
            )
            exch = "alltoall"
        if exch == "ring":
            from dsort_tpu.parallel.exchange import (
                check_ring_overflow,
                note_ring_plan,
                ring_caps,
            )

            planfn = self._build_plan(cap)
            with timer.phase("spmd_sort"):
                xs_sorted, splitters, hist = planfn(xj, cj)
                hist_h = jax.device_get(hist)
            caps = ring_caps(hist_h, cap, p)
            note_ring_plan(
                metrics, caps, hist_h, cap, p, keys_list[0].dtype.itemsize,
                self.job.capacity_factor, jobs=batch,
            )
            ringfn = self._build_ring(cap, caps)
            with timer.phase("spmd_sort"):
                out_k, out_counts, overflow = ringfn(xs_sorted, cj, splitters)
                c, ov = jax.device_get((out_counts, overflow))
            check_ring_overflow(ov)
        else:
            cap_pair = cap_pair_policy(cap, self.job.capacity_factor, p)
            for _ in range(self.job.max_capacity_retries + 1):
                with timer.phase("spmd_sort"):
                    if kv:
                        fn = self._build_kv(cap, cap_pair, trailing)
                        out_k, out_v, out_counts, overflow, max_len = fn(xj, vj, cj)
                    else:
                        fn = self._build(cap, cap_pair)
                        out_k, out_counts, overflow, max_len = fn(xj, cj)
                    # One fetch = completion barrier + every retry scalar (see
                    # sort_ranges).
                    c, ov, ml = jax.device_get((out_counts, overflow, max_len))
                slot = keys_list[0].dtype.itemsize + (
                    int(np.prod(trailing, dtype=np.int64))
                    * payloads_list[0].dtype.itemsize
                    if kv
                    else 0
                )
                note_alltoall_attempt(metrics, cap_pair, slot, p, jobs=batch)
                if not bool(ov.any()):
                    break
                metrics.bump("capacity_retries")
                observed = int(ml.max())
                cap_pair = next_cap_pair(observed, cap_pair, cap, p)
                metrics.event(
                    "capacity_retry", observed=observed, cap_pair=cap_pair
                )
                log.warning("batch overflow (max bucket %d): retrying with "
                            "cap_pair=%d", observed, cap_pair)
            else:
                raise RuntimeError("sample sort bucket overflow after max retries")
        if keep:
            # Device-resident: each job's handle wraps its slice of the
            # batch output (still on device — slicing the batch dim never
            # round-trips the keys).  Rows are the p workers' merged runs.
            from dsort_tpu.parallel.device_result import DeviceSortResult

            cb = c.reshape(batch, p)
            handles = []
            for b in range(n_jobs):
                h = DeviceSortResult(
                    out_k[b],
                    shard_lengths=cb[b],
                    n=int(cb[b].sum()),
                    metrics=metrics,
                    label="batch",
                )
                metrics.bump("device_handles")
                metrics.event(
                    "device_handle", n_keys=h.n, shards=h.num_shards
                )
                handles.append(h)
            return handles
        with timer.phase("assemble"):
            # ONE fetch for everything the assemble needs (keys + payloads
            # ride a single device_get — the file's one-fetch doctrine),
            # then per-job output buffers filled worker-run by worker-run
            # with no per-worker concat.  The (dp, w)-sharded array's
            # shards do not map 1:1 to jobs, so per-shard overlapped
            # fetches do not apply here.
            if kv:
                mk, mv = jax.device_get((out_k, out_v))
                mv = mv.reshape((batch, p, -1) + trailing)
            else:
                mk = np.asarray(out_k)
            mk = mk.reshape(batch, p, -1)
            c = c.reshape(batch, p)

            def job_out(m, b):
                n_b = int(c[b].sum())
                out = np.empty((n_b,) + m.shape[3:], dtype=m.dtype)
                off = 0
                for i in range(p):
                    ci = int(c[b, i])
                    out[off : off + ci] = m[b, i, :ci]
                    off += ci
                return out

            keys_out = [job_out(mk, b) for b in range(n_jobs)]
            if not kv:
                return keys_out
            return [
                (keys_out[b], job_out(mv, b)) for b in range(n_jobs)
            ]
