"""Multi-round out-of-core SPMD sort: the wave pipeline (ROADMAP item 2).

`models.external_sort` removes the fits-in-memory cap on ONE device;
`parallel.exchange` gives the mesh an adaptive ring shuffle; this module
composes them so a dataset larger than the WHOLE MESH's device memory sorts
at device speed — the Exoshuffle-CloudSort shape (arXiv:2301.03734):
application-level shuffle waves streaming over a shared runtime instead of a
job-at-a-time barrier.

The pipeline:

1. **global splitters, once** — a deterministic strided sample of the whole
   input picks ``P-1`` splitters up front, so every wave's buckets land on
   STABLE owner devices and the final output is the concatenation of the
   per-device ranges — no global re-merge.
2. **wave loop** — the input is consumed in device-budget-sized waves
   (``wave_elems`` keys).  Each wave is range-partitioned over the mesh and
   ring-exchanged (`exchange._wave_plan_shard` measures the wave's bucket
   histogram against the fixed splitters; `exchange.ring_caps` sizes each
   ppermute step's buffer on the capacity ladder, exactly the PR 4 plan),
   leaving device ``r`` holding the wave's sorted ``r``-th key range.
3. **overlap** — the perf headline: wave ``k``'s device exchange overlaps
   wave ``k-1``'s host-side spill (and, for record jobs, its per-range run
   merge) on reader/writer threads, extending the proven
   `external_sort._overlapped_run_generation` schedule from one device to
   the mesh.  The pipeline is bounded by max(read, exchange, spill) instead
   of their sum.
4. **run store + merge** — each (wave, range) result spills as one sorted
   run in `checkpoint.ShardCheckpoint`'s ``(wave, run)`` namespace; the
   final phase streams each range's runs through the native heap merge into
   its slice of the output (which may be a memmap), so peak residency stays
   O(wave_elems), independent of N.

**Resume contract (run granularity).**  The manifest extends the external
sort's fingerprint guard with the wave layout AND the sampled splitters, so
a crash resumes against bit-identical bucket ownership:

- a wave with all ``P`` runs present restores for free (``runs_resumed``);
- an interrupted wave (process kill mid-spill, stale store) re-sorts ONLY
  its missing runs on the host (``wave_resume`` event, ``wave_runs_resorted``
  counter) — never the job;
- a device fault inside a wave's ring (`fault_hook` seam, the scheduler's
  mid-ring drill point) is repaired IN FLIGHT: the wave's input is still
  host-resident, so its runs re-sort on the host and the pipeline continues
  with the remaining waves on the mesh.

``DSORT_WAVE_DIE_AFTER_WAVE=<k>`` is the crash-drill hook: the process
exits(17) right after wave ``k``'s runs are durable — exactly the state a
mid-job kill leaves.
"""

from __future__ import annotations

import functools
import os
import tempfile
import time

import numpy as np

from dsort_tpu.checkpoint import ShardCheckpoint
from dsort_tpu.config import JobConfig
from dsort_tpu.models.external_sort import _fingerprint
from dsort_tpu.ops.float_order import (
    float_to_ordered_uint,
    is_float_key_dtype,
    ordered_uint_dtype,
    ordered_uint_to_float,
)
from dsort_tpu.utils.logging import get_logger
from dsort_tpu.utils.metrics import Metrics, PhaseTimer

log = get_logger("wave_sort")

#: Crash-drill hook: ``os._exit(17)`` right after this wave's runs land.
DIE_AFTER_WAVE_ENV = "DSORT_WAVE_DIE_AFTER_WAVE"


def _recoverable(exc: BaseException) -> bool:
    """A wave fault the pipeline repairs in flight: an injected worker loss
    or a classified device/transient runtime error.  Genuine program errors
    propagate — repairing them on the host would mask a bug."""
    from dsort_tpu.scheduler.fault import WorkerFailure, classify_runtime_error

    return isinstance(exc, WorkerFailure) or classify_runtime_error(exc) is not None


def _fault_reason(exc: BaseException) -> str:
    from dsort_tpu.scheduler.fault import classify_runtime_error

    return classify_runtime_error(exc) or "worker_failure"


def sample_global_splitters(data, n: int, p: int, mapper=None, oversample: int = 64):
    """``P-1`` global splitters from ONE deterministic strided sample.

    Sampling is position-based (`np.linspace` picks, like `_fingerprint`),
    so a resumed job recomputes identical splitters from identical data —
    the manifest still records them, and a mismatch is a stale store.
    ``mapper`` maps float keys to ordered uints so splitters live in
    storage space.  O(sample) host memory even on a memmap.
    """
    if p <= 1:
        empty = np.array(data[:0])
        return mapper(empty) if mapper is not None else np.asarray(empty)
    s = min(n, max(4096, p * oversample))
    idx = np.unique(np.linspace(0, n - 1, num=s, dtype=np.int64))
    sample = np.array(data[idx])
    if mapper is not None:
        sample = mapper(sample)
    sample.sort(kind="stable")
    pos = (np.arange(1, p, dtype=np.int64) * len(sample)) // p
    return sample[pos]


def _shard_cap(wave_budget: int, p: int) -> int:
    """Static per-device buffer length, identical for EVERY wave (the final
    partial wave pads up), so the whole job compiles one plan and a bounded
    ladder of ring variants: ceil(budget / P), 8-aligned."""
    return -(-(-(-wave_budget // p)) // 8) * 8


def _die_check(w: int) -> None:
    """Crash-drill hook point: runs after wave ``w``'s runs are durable."""
    if os.environ.get(DIE_AFTER_WAVE_ENV) == str(w):
        log.warning("crash drill: exiting after wave %d persisted", w)
        os._exit(17)


def _sync_wave_manifest(
    ckpt, *, resume, job_id, num_waves, num_ranges, wave_elems, dtype,
    total, fingerprint, storage_dtype, splitters,
) -> None:
    """THE (wave, run) store staleness guard, shared by the key and record
    pipelines: trust persisted runs only if the layout AND the splitters
    match — splitters define bucket ownership, so a mismatch would
    concatenate ranges of a different partition into corrupt output."""
    spl = [int(v) for v in splitters]
    if not resume:
        ckpt.clear()
    else:
        m = ckpt.manifest()
        stale = (m is None and bool(ckpt.completed_wave_runs())) or (
            m is not None
            and (
                m.get("kind") != "wave"
                or m.get("num_waves") != num_waves
                or m.get("num_ranges") != num_ranges
                or m.get("wave_elems") != wave_elems
                or m.get("dtype") != str(np.dtype(dtype))
                or m.get("storage_dtype") != storage_dtype
                or m.get("total") != total
                or m.get("fingerprint") != fingerprint
                or m.get("splitters") != spl
            )
        )
        if stale:
            log.warning(
                "wave job %r: persisted runs belong to a different "
                "job/layout; clearing", job_id,
            )
            ckpt.clear()
    ckpt.write_manifest(
        num_waves * num_ranges, dtype, total,
        kind="wave", num_waves=num_waves, num_ranges=num_ranges,
        wave_elems=wave_elems, fingerprint=fingerprint,
        storage_dtype=storage_dtype, splitters=spl,
    )


def _classify_waves(ckpt, num_waves: int, p: int, metrics: Metrics):
    """Resume triage over the (wave, run) store: returns ``(fresh,
    partial)`` — fresh waves run the mesh pipeline, partial ones repair
    only their missing runs; complete waves restore for free
    (``runs_resumed``)."""
    done = set(ckpt.completed_wave_runs())
    fresh, partial, resumed = [], [], 0
    for w in range(num_waves):
        missing = [r for r in range(p) if (w, r) not in done]
        resumed += p - len(missing)
        if not missing:
            continue
        (partial if len(missing) < p else fresh).append((w, missing))
    if resumed:
        metrics.bump("runs_resumed", resumed)
    return fresh, partial


def _range_mask(keys: np.ndarray, splitters: np.ndarray, r: int, p: int):
    """Host twin of the device bucket rule (`exchange._bucket_bounds`,
    side='left'): range ``r`` owns keys in ``[splitters[r-1], splitters[r])``
    with open ends at 0 and P-1.  Keys equal to a splitter go right."""
    mask = np.ones(len(keys), bool)
    if r > 0:
        mask &= keys >= splitters[r - 1]
    if r < p - 1:
        mask &= keys < splitters[r]
    return mask


def _merge_runs_into(runs, target, metrics: Metrics) -> None:
    """Stream sorted runs into ``target`` (a view of the output buffer or a
    memmap slice) via the native heap merge; numpy reduction fallback."""
    from dsort_tpu.runtime import native

    runs = [r for r in runs if len(r)]
    if not runs:
        return
    if len(runs) == 1:
        target[:] = runs[0]
        return
    if native.available() and native.supports_dtype(runs[0].dtype):
        metrics.bump("native_merges")
        native.kway_merge(runs, out=target)
        return
    from dsort_tpu.ops.merge import merge_sorted_host

    target[:] = merge_sorted_host([np.asarray(r) for r in runs])


def _run_wave_pipeline(
    waves, *, read, dispatch, retire, repair, die_check, overlap: bool
) -> None:
    """The shared overlapped wave driver (keys and records).

    Schedule per wave ``k``: the reader thread loads wave ``k+1``'s slice,
    the mesh runs wave ``k``, and wave ``k-1`` retires (fetch + spill +
    host run-merge) — its checkpoint writes ride a writer thread, surfaced
    in order like `_overlapped_run_generation`.  ``overlap=False`` is the
    strict sequential schedule, with the same output.

    A recoverable device fault in a wave's dispatch or retire re-sorts that
    wave's runs on the host (``repair`` — the input slice is still
    host-resident) and the pipeline continues; ``die_check`` runs after
    each wave's runs are durable (the crash-drill hook point).
    """
    from concurrent.futures import ThreadPoolExecutor

    reader = ThreadPoolExecutor(max_workers=1) if overlap else None
    writer = ThreadPoolExecutor(max_workers=1) if overlap else None

    def inline_save(fn, *a):
        fn(*a)

    def settle(retiring):
        """Surface the writer-thread retire of wave ``w`` — repairing it on
        a recoverable device fault — then run the crash-drill hook."""
        w, chunk, fut = retiring
        try:
            fut.result()
        except Exception as e:  # noqa: BLE001 — routed through _recoverable
            if not _recoverable(e):
                raise
            repair(w, chunk, _fault_reason(e))
        die_check(w)

    try:
        nxt = reader.submit(read, waves[0]) if reader else None
        retiring = None  # (wave, chunk, writer-thread future)
        for pos, w in enumerate(waves):
            chunk = nxt.result() if reader else read(w)
            if reader and pos + 1 < len(waves):
                nxt = reader.submit(read, waves[pos + 1])
            try:
                state = dispatch(w, chunk)
            except Exception as e:  # noqa: BLE001
                if not _recoverable(e):
                    raise
                repair(w, chunk, _fault_reason(e))
                die_check(w)
                state = None
            if state is None:
                continue
            if overlap:
                # Hand the WHOLE retire (completion fetch + spill) to the
                # writer thread: wave w's D2H and checkpoint writes run
                # while the main thread reads, plans and dispatches wave
                # w+1 — the mesh-scale `_overlapped_run_generation`
                # schedule.  One wave retires at a time (bounded memory),
                # surfaced in order.
                if retiring is not None:
                    settle(retiring)
                retiring = (
                    w, chunk,
                    writer.submit(retire, w, chunk, state, inline_save),
                )
            else:
                try:
                    retire(w, chunk, state, inline_save)
                except Exception as e:  # noqa: BLE001
                    if not _recoverable(e):
                        raise
                    repair(w, chunk, _fault_reason(e))
                die_check(w)
        if retiring is not None:
            settle(retiring)
    finally:
        if reader is not None:
            reader.shutdown(wait=True)
        if writer is not None:
            writer.shutdown(wait=True)


class ExternalWaveSort:
    """Out-of-core mesh sort: wave-pipelined ring exchange + run store.

    ``mesh``: the device mesh (default: all local devices).
    ``wave_elems``: keys consumed per wave — the per-wave device budget;
    a dataset ``W`` times larger runs as ``W`` pipelined waves.
    ``spill_dir``/``job_id``/``resume``: the `ShardCheckpoint` (wave, run)
    store and its resume key.  ``overlap=False`` disables the pipeline
    (same output, sequential).  ``exchange`` ("ring" | "fused", default
    `JobConfig.exchange` via the shared resolver): "fused" runs each
    wave's exchange+merge as ONE Pallas kernel (`ops.ring_kernel`) — the
    wave never leaves the device between partition and spill.
    """

    def __init__(
        self,
        mesh=None,
        wave_elems: int = 1 << 22,
        spill_dir: str | None = None,
        job_id: str = "wave",
        job: JobConfig | None = None,
        resume: bool = True,
        overlap: bool = True,
        axis_name: str = "w",
        exchange: str | None = None,
        redundancy: int | None = None,
        redundancy_mode: str | None = None,
    ):
        if wave_elems < 2:
            raise ValueError("wave_elems must be >= 2")
        if mesh is None:
            from dsort_tpu.parallel.mesh import local_device_mesh

            mesh = local_device_mesh()
        self.mesh = mesh
        # The worker axis, like SampleSort: a mesh may carry a leading
        # batch ("dp") axis whose size is not the worker count.
        self.axis = (
            axis_name if axis_name in mesh.axis_names else mesh.axis_names[-1]
        )
        self.num_workers = int(mesh.shape[self.axis])
        self.wave_elems = int(wave_elems)
        self.spill_dir = spill_dir or os.path.join(
            tempfile.gettempdir(), "dsort_external"
        )
        self.job_id = job_id
        self.job = job or JobConfig()
        self.resume = resume
        self.overlap = overlap
        # Per-wave exchange schedule through the one resolver seam
        # (override > JobConfig.exchange): "ring" is the PR 4 lax schedule;
        # "fused" runs each wave's transfers as ONE Pallas kernel
        # (`ops.ring_kernel`) and merges in the same program, so a wave
        # never leaves the device between partition and spill; "alltoall" is meaningless here (the wave
        # plan IS the measured-histogram ring plan) and maps to "ring".
        from dsort_tpu.parallel.exchange import (
            resolve_exchange,
            resolve_hier_hosts,
            resolve_redundancy,
            resolve_redundancy_mode,
        )

        exch = resolve_exchange(exchange, self.job.exchange, self.num_workers)
        # "hier" runs each wave's exchange as the two-level schedule
        # (ARCHITECTURE §17): cross-host waves aggregate per destination
        # HOST before the DCN leg, so each host spills its own ranges from
        # one merged inbound transfer per source host.
        self.hier_hosts = 0
        if exch == "hier":
            self.hier_hosts = resolve_hier_hosts(
                self.job.hier_hosts, self.num_workers
            )
            if self.hier_hosts < 2:
                log.warning(
                    "exchange='hier' needs >= 4 workers grouped into >= 2 "
                    "hosts (have %d); waves use the lax ring schedule",
                    self.num_workers,
                )
                exch = "ring"
        self.exchange = exch if exch in ("fused", "hier") else "ring"
        # Coded redundancy (ARCHITECTURE §14): r > 1 ships every wave's
        # buckets to their r-1 ring successors too, so a device lost
        # mid-wave is repaired by a LOCAL merge of replica slots — no host
        # re-sort (`wave_runs_resorted` stays 0) and the pipeline
        # continues.  The replica plane rides the lax ring only, so a
        # coded wave overrides exchange="fused" back to "ring".
        self.redundancy = resolve_redundancy(
            redundancy, self.job.redundancy, self.num_workers
        )
        # v2 mode axis: "replicate" ships full sorted copies (r-1 x wire
        # premium), "parity" ships XOR/GF(256) parity slots instead
        # (~1/P the premium at the same single-loss survivability).
        self.redundancy_mode = resolve_redundancy_mode(
            redundancy_mode, getattr(self.job, "redundancy_mode", "replicate")
        )
        if self.redundancy > 1 and self.exchange != "ring":
            log.warning(
                "redundancy=%d needs the lax ring schedule; coded waves "
                "override exchange=%r to 'ring'",
                self.redundancy, self.exchange,
            )
            self.exchange = "ring"
        #: Test seam around a wave's exchange dispatch — the same mid-ring
        #: injection point as `SampleSort.fault_hook` (and, like there, a
        #: CODED wave's hook fires after the exchange: replica placement
        #: completes with it — `parallel.coded`'s simulation note).
        self.fault_hook = None
        self._plan_cache: dict = {}
        self._ring_cache: dict = {}
        self._fused_cache: dict = {}
        self._coded_cache: dict = {}
        self._hier_cache: dict = {}
        self._single_cache: dict = {}

    # -- compiled programs ---------------------------------------------------

    def _build_plan(self, n_local: int):
        import jax
        from jax.sharding import PartitionSpec as P

        from dsort_tpu.obs.prof import instrument_jit
        from dsort_tpu.parallel.exchange import _wave_plan_shard
        from dsort_tpu.utils.compat import shard_map

        fn = self._plan_cache.get(n_local)
        if fn is None:
            p = self.num_workers
            body = functools.partial(
                _wave_plan_shard,
                num_workers=p,
                axis=self.axis,
                kernel=self.job.local_kernel,
            )
            fn = instrument_jit(
                jax.jit(
                    shard_map(
                        body,
                        mesh=self.mesh,
                        in_specs=(P(self.axis), P(self.axis), P()),
                        out_specs=(P(self.axis), P()),
                        check_vma=False,
                    )
                ),
                key_fn=lambda *a: (
                    "wave_plan", p, n_local, str(a[0].dtype),
                    self.job.local_kernel,
                ),
            )
            self._plan_cache[n_local] = fn
        return fn

    def _build_ring(self, n_local: int, caps: tuple):
        import jax
        from jax.sharding import PartitionSpec as P

        from dsort_tpu.obs.prof import instrument_jit
        from dsort_tpu.parallel.exchange import _ring_exchange_shard
        from dsort_tpu.utils.compat import shard_map

        key = (n_local, caps)
        fn = self._ring_cache.get(key)
        if fn is None:
            p = self.num_workers
            body = functools.partial(
                _ring_exchange_shard,
                num_workers=p,
                caps=caps,
                axis=self.axis,
                merge_kernel=self.job.merge_kernel,
                kernel=self.job.local_kernel,
            )
            # Same donation rule as SampleSort._build_ring: the sorted wave
            # shard is dead after the exchange (repair re-sorts from the
            # HOST copy, never this buffer), so donate off-CPU.
            donate = (
                (0,)
                if next(iter(self.mesh.devices.flat)).platform != "cpu"
                else ()
            )
            fn = instrument_jit(
                jax.jit(
                    shard_map(
                        body,
                        mesh=self.mesh,
                        in_specs=(P(self.axis), P(self.axis), P()),
                        out_specs=(P(self.axis),) * 3,
                        check_vma=False,
                    ),
                    donate_argnums=donate,
                ),
                key_fn=lambda *a: (
                    "wave_ring", p, n_local, caps, str(a[0].dtype),
                    self.job.local_kernel,
                ),
            )
            self._ring_cache[key] = fn
        return fn

    def _build_fused(self, n_local: int, caps: tuple):
        """Fused per-wave exchange+merge (`ops.ring_kernel`): the wave's
        P-1 transfer steps run as ONE kernel launch and the range merge in
        the same program — between its partition and its spill the wave
        never leaves the device or dispatches a second program."""
        import jax
        from jax.sharding import PartitionSpec as P

        from dsort_tpu.obs.prof import instrument_jit
        from dsort_tpu.ops.ring_kernel import (
            fused_mesh,
            fused_ring_exchange_shard,
        )
        from dsort_tpu.utils.compat import shard_map

        key = (n_local, caps)
        fn = self._fused_cache.get(key)
        if fn is None:
            p = self.num_workers
            body = functools.partial(
                fused_ring_exchange_shard,
                num_workers=p,
                caps=caps,
                axis=self.axis,
                merge_kernel=self.job.merge_kernel,
                kernel=self.job.local_kernel,
            )
            # Donation matches `_build_ring` (repair re-sorts from the
            # host-resident wave slice, never this buffer).
            donate = (
                (0,)
                if next(iter(self.mesh.devices.flat)).platform != "cpu"
                else ()
            )
            fn = instrument_jit(
                jax.jit(
                    shard_map(
                        body,
                        mesh=fused_mesh(self.mesh, self.axis),
                        in_specs=(P(self.axis), P(self.axis), P(), P()),
                        out_specs=(P(self.axis),) * 3,
                        check_vma=False,
                    ),
                    donate_argnums=donate,
                ),
                key_fn=lambda *a: (
                    "wave_fused", p, n_local, caps, str(a[0].dtype),
                    self.job.local_kernel,
                ),
            )
            self._fused_cache[key] = fn
        return fn

    def _build_coded(self, n_local: int, caps: tuple):
        """Coded per-wave exchange: the measured-caps ring schedule plus
        the redundancy plane — replica slots
        (`exchange._coded_ring_exchange_shard`) or XOR/GF(256) parity
        slots (`exchange._parity_ring_exchange_shard`) by
        ``redundancy_mode`` — so a wave surviving a device loss repairs
        off-plane instead of a host re-sort.  No donation — a fault needs
        the wave's merged ranges AND the plane host-fetchable after the
        dispatch."""
        import jax
        from jax.sharding import PartitionSpec as P

        from dsort_tpu.obs.prof import instrument_jit
        from dsort_tpu.parallel.exchange import (
            _coded_ring_exchange_shard,
            _parity_ring_exchange_shard,
        )
        from dsort_tpu.utils.compat import shard_map

        parity = self.redundancy_mode == "parity"
        key = (n_local, caps, self.redundancy_mode)
        fn = self._coded_cache.get(key)
        if fn is None:
            p = self.num_workers
            body = functools.partial(
                _parity_ring_exchange_shard if parity
                else _coded_ring_exchange_shard,
                num_workers=p,
                caps=caps,
                axis=self.axis,
                redundancy=self.redundancy,
                merge_kernel=self.job.merge_kernel,
                kernel=self.job.local_kernel,
            )
            fn = instrument_jit(
                jax.jit(
                    shard_map(
                        body,
                        mesh=self.mesh,
                        in_specs=(P(self.axis), P(self.axis), P()),
                        out_specs=(P(self.axis),) * (6 if parity else 5),
                        check_vma=False,
                    ),
                ),
                key_fn=lambda *a: (
                    "wave_parity" if parity else "wave_coded", p, n_local,
                    caps, self.redundancy, str(a[0].dtype),
                    self.job.local_kernel,
                ),
            )
            self._coded_cache[key] = fn
        return fn

    def _build_hier(self, n_local: int, plan):
        """Two-level per-wave exchange (`exchange._hier_exchange_shard`):
        intra-host aggregation, one DCN transfer per (src-host, dst-host)
        pair, local scatter — a cross-host wave's spill traffic rides the
        planned legs instead of P-1 flat transfers.  ``plan`` is the
        `HierPlan` rung, same cache doctrine as `_build_ring`'s caps."""
        import jax
        from jax.sharding import PartitionSpec as P

        from dsort_tpu.obs.prof import instrument_jit
        from dsort_tpu.parallel.exchange import _hier_exchange_shard
        from dsort_tpu.utils.compat import shard_map

        key = (n_local, plan)
        fn = self._hier_cache.get(key)
        if fn is None:
            p = self.num_workers
            body = functools.partial(
                _hier_exchange_shard,
                num_workers=p,
                hosts=plan.hosts,
                agg_cap=plan.agg_cap,
                leg_caps=plan.leg_caps,
                scatter_cap=plan.scatter_cap,
                axis=self.axis,
                merge_kernel=self.job.merge_kernel,
                kernel=self.job.local_kernel,
            )
            # Donation matches `_build_ring` (repair re-sorts from the
            # host-resident wave slice, never this buffer).
            donate = (
                (0,)
                if next(iter(self.mesh.devices.flat)).platform != "cpu"
                else ()
            )
            fn = instrument_jit(
                jax.jit(
                    shard_map(
                        body,
                        mesh=self.mesh,
                        in_specs=(P(self.axis), P(self.axis), P()),
                        out_specs=(P(self.axis),) * 3,
                        check_vma=False,
                    ),
                    donate_argnums=donate,
                ),
                key_fn=lambda *a: (
                    "wave_hier", p, n_local, plan, str(a[0].dtype),
                    self.job.local_kernel,
                ),
            )
            self._hier_cache[key] = fn
        return fn

    def _build_single(self, n_local: int):
        """P == 1 degenerate wave program: just the padded local sort."""
        import jax

        from dsort_tpu.obs.prof import instrument_jit
        from dsort_tpu.ops.local_sort import sort_padded

        fn = self._single_cache.get(n_local)
        if fn is None:
            kernel = self.job.local_kernel
            fn = instrument_jit(
                jax.jit(lambda x, c: sort_padded(x, c, kernel)[0]),
                key_fn=lambda *a: (
                    "wave_single", 1, n_local, str(a[0].dtype), kernel
                ),
            )
            self._single_cache[n_local] = fn
        return fn

    # -- the sort ------------------------------------------------------------

    def sort(
        self,
        data: np.ndarray,
        out: np.ndarray | None = None,
        metrics: Metrics | None = None,
    ) -> np.ndarray:
        """Sort ``data`` (ndarray or memmap) out-of-core over the mesh.

        ``data`` is read in wave-sized slices and ``out`` may be a memmap,
        so neither end needs to fit in RAM.  Float keys ride as ordered
        uints per wave and unmap at egress, like `ExternalSort`.
        """
        metrics = metrics if metrics is not None else Metrics()
        timer = PhaseTimer(metrics)
        n = len(data)
        if n == 0:
            return np.asarray(data).copy() if out is None else out
        fdt = np.dtype(data.dtype) if is_float_key_dtype(data.dtype) else None
        storage = (
            ordered_uint_dtype(fdt) if fdt is not None else np.dtype(data.dtype)
        )
        if storage.itemsize == 8:
            import jax

            from dsort_tpu.config import ConfigError

            if not jax.config.jax_enable_x64:
                raise ConfigError(
                    "8-byte keys need 64-bit mode: call "
                    "jax.config.update('jax_enable_x64', True) first"
                )
        mapper = float_to_ordered_uint if fdt is not None else None
        metrics.event(
            "job_start", mode="wave_external", n_keys=n, job_id=self.job_id,
            tenant=self.job.tenant,
        )
        if getattr(self.job, "autotune", False):
            # Wave sizing from the journal's hbm_watermark ledger instead
            # of the hand-set wave_elems (obs.plan, ARCHITECTURE §15).
            # Journaled BEFORE the manifest sync, so a resized resume
            # restarts cleanly under the manifest's wave_elems check.
            from dsort_tpu.obs.plan import planned_wave_elems

            records = (
                [e.to_dict() for e in metrics.journal.events()]
                if metrics.journal is not None else []
            )
            self.wave_elems = planned_wave_elems(
                self.job, self.wave_elems, storage.itemsize, records,
                metrics,
            )
        num_waves = -(-n // self.wave_elems)
        with timer.phase("splitter_sample"):
            splitters = sample_global_splitters(
                data, n, self.num_workers, mapper=mapper
            )
        fp = _fingerprint(data)
        ckpt = ShardCheckpoint(self.spill_dir, self.job_id)
        ckpt.journal = metrics.journal
        _sync_wave_manifest(
            ckpt, resume=self.resume, job_id=self.job_id,
            num_waves=num_waves, num_ranges=self.num_workers,
            wave_elems=self.wave_elems, dtype=data.dtype, total=n,
            fingerprint=fp, storage_dtype=str(storage), splitters=splitters,
        )
        with timer.phase("run_generation"):
            self._run_waves(
                data, n, num_waves, splitters, ckpt, metrics, timer, mapper
            )
        with timer.phase("merge"):
            if fdt is not None:
                target = (
                    out.view(storage) if out is not None
                    else np.empty(n, dtype=storage)
                )
            else:
                target = out if out is not None else np.empty(n, dtype=storage)
            self._merge_ranges(num_waves, n, ckpt, metrics, target)
        if fdt is not None:
            if out is None:
                out = np.empty(n, dtype=fdt)
            # Chunked unmap: O(wave_elems) temporaries, alias-safe (see
            # ExternalSort.sort).
            for lo in range(0, n, self.wave_elems):
                sl = slice(lo, min(lo + self.wave_elems, n))
                out[sl] = ordered_uint_to_float(target[sl], fdt)
            result = out
        else:
            result = target if out is None else out
        metrics.event("job_done", n_keys=n, counters=dict(metrics.counters))
        return result

    def sort_binary_file(
        self,
        in_path: str,
        out_path: str,
        dtype=np.int32,
        metrics: Metrics | None = None,
    ) -> None:
        """Sort a raw binary key file out-of-core end to end (memmap in,
        memmap out) — the `dsort external --mesh` entry point."""
        dtype = np.dtype(dtype)
        size = os.path.getsize(in_path)
        if size % dtype.itemsize:
            raise ValueError(
                f"{in_path}: size {size} not a multiple of itemsize "
                f"{dtype.itemsize}"
            )
        n = size // dtype.itemsize
        if n == 0:
            open(out_path, "wb").close()
            return
        data = np.memmap(in_path, dtype=dtype, mode="r")
        out = np.lib.format.open_memmap(
            out_path, mode="w+", dtype=dtype, shape=(n,)
        ) if out_path.endswith(".npy") else np.memmap(
            out_path, dtype=dtype, mode="w+", shape=(n,)
        )
        self.sort(data, out=out, metrics=metrics)
        out.flush()

    # -- wave machinery ------------------------------------------------------

    def _read_mapped(self, data, n, w, mapper):
        lo = w * self.wave_elems
        sl = data[lo : min(lo + self.wave_elems, n)]
        arr = np.array(sl) if isinstance(data, np.memmap) else np.asarray(sl)
        return mapper(arr) if mapper is not None else arr

    def _run_waves(
        self, data, n, num_waves, splitters, ckpt, metrics, timer, mapper
    ) -> None:
        p = self.num_workers
        fresh, partial = _classify_waves(ckpt, num_waves, p, metrics)
        # Interrupted waves first: run-granular host repair needs no mesh
        # (it must work even when the resume runs on different hardware).
        for w, missing in partial:
            with timer.phase("wave_repair"):
                arr = self._read_mapped(data, n, w, mapper)
                self._repair_wave(
                    arr, w, missing, splitters, ckpt, metrics,
                    reason="restart_resume",
                )
            _die_check(w)
        if not fresh:
            return

        def read(w):
            with timer.phase("wave_read"):
                arr = self._read_mapped(data, n, w, mapper)
                from dsort_tpu.data.partition import pad_to_shards

                shards, counts = pad_to_shards(
                    arr, p, cap=_shard_cap(self.wave_elems, p)
                )
            return arr, shards, counts

        def dispatch(w, chunk):
            arr, shards, counts = chunk
            metrics.event("wave_start", wave=w, n_keys=len(arr))
            try:
                return self._dispatch_wave(
                    shards, counts, splitters, metrics, timer
                )
            except Exception as e:  # noqa: BLE001 — coded seam, then repair
                # A loss in a CODED wave carries the replica snapshot: the
                # wave completes from replica slots right here — zero runs
                # re-sorted — and the pipeline moves on (state None skips
                # retire).  Anything else (incl. an over-budget coded
                # loss) falls through to the host re-sort repair path.
                state = getattr(e, "coded_state", None)
                if state is not None and self._coded_recover_wave(
                    w, e, state, ckpt, metrics, timer
                ):
                    return None
                raise

        def retire(w, chunk, state, save):
            self._retire_wave(w, state, ckpt, metrics, timer, save)

        def repair(w, chunk, reason):
            with timer.phase("wave_repair"):
                self._repair_wave(
                    chunk[0], w, list(range(p)), splitters, ckpt, metrics,
                    reason=reason,
                )

        _run_wave_pipeline(
            [w for w, _ in fresh],
            read=read, dispatch=dispatch, retire=retire, repair=repair,
            die_check=_die_check, overlap=self.overlap,
        )

    def _dispatch_wave(self, shards, counts, splitters, metrics, timer):
        import jax
        import numpy as _np
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dsort_tpu.obs.prof import LEDGER
        from dsort_tpu.parallel.exchange import (
            note_fused_plan,
            note_ring_plan,
            ring_caps,
        )

        p = self.num_workers
        n_local = shards.shape[1]
        if p == 1:
            fn = self._build_single(n_local)
            with timer.phase("wave_sort"):
                import jax.numpy as jnp

                merged = fn(jnp.asarray(shards[0]), int(counts[0]))
            LEDGER.drain_to(metrics)
            return merged, np.zeros(1, bool), counts.astype(np.int64)
        fused = self.exchange == "fused"
        hier = self.exchange == "hier"
        coded = self.redundancy > 1
        shard_spec = NamedSharding(self.mesh, P(self.axis))
        repl = NamedSharding(self.mesh, P())
        planfn = self._build_plan(n_local)
        with timer.phase("wave_sort"):
            xs, cj = jax.device_put((shards.reshape(-1), counts), shard_spec)
            spl = jax.device_put(np.asarray(splitters), repl)
            xs_sorted, hist = planfn(xs, cj, spl)
            # The ONE host fetch of the plan: the (P, P) histogram that
            # sizes the per-step ring buffers (PR 4 doctrine).
            hist_h = _np.asarray(jax.device_get(hist)).reshape(p, p)
        LEDGER.drain_to(metrics)
        caps = ring_caps(hist_h, n_local, p)
        hplan = None
        if coded:
            from dsort_tpu.parallel.exchange import note_coded_plan

            note_coded_plan(
                metrics, caps, hist_h, n_local, p, shards.dtype.itemsize,
                self.job.capacity_factor, self.redundancy,
                mode=self.redundancy_mode,
            )
        elif hier:
            from dsort_tpu.parallel.exchange import hier_plan, note_hier_plan

            hplan = hier_plan(hist_h, n_local, p, self.hier_hosts)
            note_hier_plan(
                metrics, hplan, caps, hist_h, n_local, p,
                shards.dtype.itemsize, self.job.capacity_factor,
            )
        else:
            note = note_fused_plan if fused else note_ring_plan
            note(
                metrics, caps, hist_h, n_local, p, shards.dtype.itemsize,
                self.job.capacity_factor,
            )
        if not coded and self.fault_hook is not None:
            self.fault_hook()
        with timer.phase("wave_exchange"):
            if coded:
                codedfn = self._build_coded(n_local, caps)
                outs = codedfn(xs_sorted, cj, spl)
                merged, cnts, overflow = outs[:3]
            elif hier:
                hierfn = self._build_hier(n_local, hplan)
                merged, _, overflow = hierfn(xs_sorted, cj, spl)
            elif fused:
                fusedfn = self._build_fused(n_local, caps)
                merged, _, overflow = fusedfn(xs_sorted, cj, spl, hist)
            else:
                ringfn = self._build_ring(n_local, caps)
                merged, _, overflow = ringfn(xs_sorted, cj, spl)
        if coded and self.fault_hook is not None:
            from dsort_tpu.scheduler.fault import WorkerFailure

            try:
                self.fault_hook()
            except WorkerFailure as e:
                # Plane placement completed with the exchange: snapshot
                # what the survivors hold so the wave repairs from the
                # replica/parity plane (no host re-sort) —
                # `_coded_recover_wave`.
                from dsort_tpu.parallel.coded import (
                    snapshot_parity_state,
                    snapshot_state,
                )

                snap = (
                    snapshot_parity_state
                    if self.redundancy_mode == "parity" else snapshot_state
                )
                e.coded_state = snap(
                    p, self.redundancy, caps, int(hist_h.sum()), *outs
                )
                raise
        # Keys landing on each range this wave — derived from the already
        # fetched histogram, so the retire step needs no extra scalar fetch.
        recv_lens = hist_h.sum(axis=0).astype(np.int64)
        return merged, overflow, recv_lens

    def _retire_wave(self, w, state, ckpt, metrics, timer, save) -> None:
        import jax

        from dsort_tpu.parallel.exchange import check_ring_overflow

        merged, overflow, recv_lens = state
        p = self.num_workers
        with timer.phase("wave_spill"):
            # This fetch is wave w's completion barrier; under overlap it
            # runs while wave w+1's exchange is already in flight.
            check_ring_overflow(np.asarray(jax.device_get(overflow)))
            mh = np.asarray(jax.device_get(merged)).reshape(p, -1)
            total = 0
            for r in range(p):
                run = np.array(mh[r, : int(recv_lens[r])])
                total += len(run)
                save(ckpt.save_wave_run, w, r, run)
        metrics.bump("waves_sorted")
        metrics.bump("runs_sorted", p)
        metrics.event("wave_done", wave=w, runs=p, n_keys=total)

    def _repair_wave(
        self, arr, w, missing, splitters, ckpt, metrics, reason
    ) -> None:
        """Run-granular recompute: range ``r`` of wave ``w`` is the sorted
        subset the fixed splitters assign to ``r`` — the mesh exchange's
        output for that run, reproduced from the host-resident wave slice."""
        p = self.num_workers
        metrics.event(
            "wave_resume", wave=w, missing=len(missing),
            present=p - len(missing), reason=reason,
        )
        total = 0
        for r in missing:
            run = np.sort(arr[_range_mask(arr, splitters, r, p)], kind="stable")
            ckpt.save_wave_run(w, r, run)
            total += len(run)
            metrics.bump("wave_runs_resorted")
            metrics.bump("runs_sorted")
            metrics.bump("wave_resort_keys", len(run))
        metrics.event("wave_done", wave=w, runs=len(missing), n_keys=total)
        log.warning(
            "wave %d repaired: %d/%d runs re-sorted on host (%s)",
            w, len(missing), p, reason,
        )

    def _coded_recover_wave(
        self, w, exc, state, ckpt, metrics, timer
    ) -> bool:
        """Complete wave ``w`` from the coded exchange's replica plane.

        The dead device's range is reconstructed by a LOCAL merge of a
        survivor's replica slots (`parallel.coded`) and every range lands
        in the (wave, run) store directly — ``wave_runs_resorted`` stays 0
        and the pipeline continues with the next wave on the mesh.
        Returns False — journaling ``coded_budget_exceeded`` — when the
        losses exceed the redundancy budget; the caller then re-raises
        into the host re-sort repair path.
        """
        from dsort_tpu.parallel.coded import dead_positions, journal_recovery

        positions = dead_positions(exc)
        rec = journal_recovery(
            metrics, state, positions, assemble=False, wave=w
        )
        if rec is None:
            log.warning(
                "wave %d: coded recovery over budget (positions %s at "
                "redundancy=%d); repairing by host re-sort",
                w, sorted(positions), state.redundancy,
            )
            return False
        ranges, info = rec
        p = self.num_workers
        with timer.phase("wave_spill"):
            total = 0
            for r in range(p):
                run = np.asarray(ranges[r])
                total += len(run)
                ckpt.save_wave_run(w, r, run)
        metrics.bump("waves_sorted")
        metrics.bump("runs_sorted", p)
        metrics.event("wave_done", wave=w, runs=p, n_keys=total)
        log.warning(
            "wave %d repaired CODED: %d key(s) of %d dead range(s) "
            "recovered from the %s plane — zero runs re-sorted",
            w, info["recovered_keys"], len(positions), state.mode,
        )
        _die_check(w)
        return True

    def _merge_ranges(self, num_waves, n, ckpt, metrics, target) -> None:
        p = self.num_workers
        off = 0
        for r in range(p):
            runs = [
                ckpt.load_wave_run_mmap(w, r) for w in range(num_waves)
            ]
            ln = sum(len(x) for x in runs)
            _merge_runs_into(runs, target[off : off + ln], metrics)
            off += ln
        if off != n:  # a lost run would silently shift every later range
            raise RuntimeError(
                f"wave merge assembled {off} of {n} keys; the run store is "
                "inconsistent — clear the spill dir and re-run"
            )


class ExternalWaveTeraSort:
    """Record (TeraSort) twin of `ExternalWaveSort`.

    Run generation is mesh-parallel: each wave's records shard over the
    mesh and every device sorts its shard by the full 10-byte key (the kv2
    kernel) in one collective-free SPMD dispatch.  The exchange is host-
    side: while wave ``k`` sorts on the mesh, wave ``k-1``'s sorted shards
    split at the fixed primary-key splitters and each range's ``P``
    sub-runs stream through the native two-level heap merge into ONE
    (wave, run) record run — the spill-and-merge half of the overlap.  The
    final phase merges each range's runs across waves straight into the
    output memmap; ranges concatenate in splitter order, so there is no
    global re-merge.  Resume contract and crash hooks match the key
    pipeline exactly.
    """

    RECORD_BYTES = 100

    def __init__(
        self,
        mesh=None,
        wave_recs: int = 1 << 20,
        spill_dir: str | None = None,
        job_id: str = "tera_wave",
        resume: bool = True,
        overlap: bool = True,
        axis_name: str = "w",
        job: JobConfig | None = None,
        exchange: str | None = None,
        redundancy: int | None = None,
        redundancy_mode: str | None = None,
    ):
        if wave_recs < 2:
            raise ValueError("wave_recs must be >= 2")
        import jax

        from dsort_tpu.config import ConfigError

        if not jax.config.jax_enable_x64:
            raise ConfigError(
                "ExternalWaveTeraSort needs 64-bit mode for its uint64 "
                "packed keys: call jax.config.update('jax_enable_x64', "
                "True) first"
            )
        if mesh is None:
            from dsort_tpu.parallel.mesh import local_device_mesh

            mesh = local_device_mesh()
        self.mesh = mesh
        self.axis = (
            axis_name if axis_name in mesh.axis_names else mesh.axis_names[-1]
        )
        self.num_workers = int(mesh.shape[self.axis])
        self.wave_recs = int(wave_recs)
        self.spill_dir = spill_dir or os.path.join(
            tempfile.gettempdir(), "dsort_external"
        )
        self.job_id = job_id
        self.job = job or JobConfig()
        self.resume = resume
        self.overlap = overlap
        # Exchange-knob parity with the key pipeline (override > conf
        # EXCHANGE > default), through the one resolver seam.  The record
        # wave's exchange is HOST-side today — each wave's sorted shards
        # split at the fixed splitters and heap-merge on the host
        # (`_retire_wave`) — so a mesh schedule ('ring'/'fused'/'hier')
        # is validated and recorded but warns that no device schedule
        # exists to select here; a silently-dropped knob would misstate
        # the wire posture (same doctrine as `cmd_external`'s warnings).
        from dsort_tpu.parallel.exchange import (
            resolve_exchange,
            resolve_redundancy,
            resolve_redundancy_mode,
        )

        self.exchange = resolve_exchange(
            exchange, self.job.exchange, self.num_workers
        )
        if self.exchange != "alltoall":
            log.warning(
                "the record wave pipeline's exchange is host-side (split "
                "+ native merge); exchange=%r selects no device schedule "
                "here yet — see ARCHITECTURE §17 for the planned kv hier "
                "leg", self.exchange,
            )
        # Record-wave redundancy (v2, ARCHITECTURE §18): the exchange is
        # host-side, so the redundancy "plane" here is the RETAINED host
        # fetch of each wave's sorted shards — the D2H the host-side split
        # needs anyway, pulled BEFORE the fault seam.  Zero wire premium
        # (honestly: there is no device exchange to protect); a device
        # loss after the wave's mesh sort completes retires the wave from
        # the retained copy — ``wave_runs_resorted`` stays 0, exactly the
        # coded contract the key pipeline gives.  ``redundancy_mode`` is
        # accepted for API symmetry and recorded, but selects no extra
        # encoding: retention already costs less wire than either mode.
        self.redundancy = resolve_redundancy(
            redundancy, self.job.redundancy, self.num_workers
        )
        self.redundancy_mode = resolve_redundancy_mode(
            redundancy_mode, getattr(self.job, "redundancy_mode", "replicate")
        )
        self.fault_hook = None
        self._sort_cache: dict = {}

    def _build_sort(self, n_local: int):
        import jax
        from jax.sharding import PartitionSpec as P

        from dsort_tpu.obs.prof import instrument_jit
        from dsort_tpu.utils.compat import shard_map

        fn = self._sort_cache.get(n_local)
        if fn is None:
            from dsort_tpu.ops.local_sort import sort_kv2_padded

            def body(k1, k2, v, c):
                return sort_kv2_padded(k1, k2, v, c[0], stable=False)[2]

            p = self.num_workers
            fn = instrument_jit(
                jax.jit(
                    shard_map(
                        body,
                        mesh=self.mesh,
                        in_specs=(P(self.axis),) * 4,
                        out_specs=P(self.axis),
                        check_vma=False,
                    )
                ),
                key_fn=lambda *a: ("wave_tera_sort", p, n_local),
            )
            self._sort_cache[n_local] = fn
        return fn

    def sort_file(
        self, in_path: str, out_path: str, metrics: Metrics | None = None
    ) -> None:
        """Sort a binary TeraSort file out-of-core through the wave mesh."""
        metrics = metrics if metrics is not None else Metrics()
        timer = PhaseTimer(metrics)
        size = os.path.getsize(in_path)
        if size % self.RECORD_BYTES:
            raise ValueError(
                f"{in_path}: size {size} not a multiple of {self.RECORD_BYTES}"
            )
        n = size // self.RECORD_BYTES
        if n == 0:
            open(out_path, "wb").close()
            return
        data = np.memmap(in_path, dtype=np.uint8, mode="r").reshape(
            n, self.RECORD_BYTES
        )
        metrics.event(
            "job_start", mode="wave_external_kv", n_keys=n, job_id=self.job_id,
        )
        num_waves = -(-n // self.wave_recs)
        with timer.phase("splitter_sample"):
            splitters = self._sample_splitters(data, n)
        fp = _fingerprint(data)
        ckpt = ShardCheckpoint(self.spill_dir, self.job_id)
        ckpt.journal = metrics.journal
        _sync_wave_manifest(
            ckpt, resume=self.resume, job_id=self.job_id,
            num_waves=num_waves, num_ranges=self.num_workers,
            wave_elems=self.wave_recs, dtype=np.uint8, total=n,
            fingerprint=fp, storage_dtype="terasort100", splitters=splitters,
        )
        with timer.phase("run_generation"):
            self._run_waves(data, n, num_waves, splitters, ckpt, metrics, timer)
        with timer.phase("merge"):
            out = np.memmap(
                out_path, dtype=np.uint8, mode="w+",
                shape=(n, self.RECORD_BYTES),
            )
            self._merge_ranges(num_waves, n, ckpt, metrics, out)
            out.flush()
        metrics.event("job_done", n_keys=n, counters=dict(metrics.counters))

    def _sample_splitters(self, data, n: int) -> np.ndarray:
        from dsort_tpu.data.ingest import _pack_be64

        # The shared sampler with the record-key extractor as the mapper:
        # identical stride/tie constants as the key pipeline, so splitter
        # determinism (part of the manifest contract) cannot diverge.
        return sample_global_splitters(
            data, n, self.num_workers,
            mapper=lambda rows: _pack_be64(np.asarray(rows)[:, :8]),
        )

    # -- wave machinery ------------------------------------------------------

    def _read_wave(self, data, n, w) -> np.ndarray:
        lo = w * self.wave_recs
        return np.array(data[lo : min(lo + self.wave_recs, n)])

    def _run_waves(
        self, data, n, num_waves, splitters, ckpt, metrics, timer
    ) -> None:
        p = self.num_workers
        fresh, partial = _classify_waves(ckpt, num_waves, p, metrics)
        for w, missing in partial:
            with timer.phase("wave_repair"):
                self._repair_wave(
                    self._read_wave(data, n, w), w, missing, splitters, ckpt,
                    metrics, reason="restart_resume",
                )
            _die_check(w)
        if not fresh:
            return

        def read(w):
            with timer.phase("wave_read"):
                recs = self._read_wave(data, n, w)
                shards = self._pad_shards(recs)
            return recs, shards

        def dispatch(w, chunk):
            recs, shards = chunk
            metrics.event("wave_start", wave=w, n_keys=len(recs))
            try:
                return self._dispatch_wave(shards, metrics, timer)
            except Exception as e:  # noqa: BLE001 — coded seam, then repair
                # A loss in a CODED record wave carries the retained host
                # shards: the wave retires from them right here — zero
                # runs re-sorted — and the pipeline moves on (state None
                # skips retire).  An uncoded loss falls through to the
                # host re-sort repair path.
                state = getattr(e, "wave_record_state", None)
                if state is not None:
                    self._coded_recover_wave(
                        w, e, state, splitters, ckpt, metrics, timer
                    )
                    return None
                raise

        def retire(w, chunk, state, save):
            self._retire_wave(w, state, splitters, ckpt, metrics, timer, save)

        def repair(w, chunk, reason):
            with timer.phase("wave_repair"):
                self._repair_wave(
                    chunk[0], w, list(range(p)), splitters, ckpt, metrics,
                    reason=reason,
                )

        _run_wave_pipeline(
            [w for w, _ in fresh],
            read=read, dispatch=dispatch, retire=retire, repair=repair,
            die_check=_die_check, overlap=self.overlap,
        )

    def _pad_shards(self, recs: np.ndarray):
        """Host layout: (P, cap) primary/secondary keys + (P, cap, 100)
        records, zero-padded (the kv2 kernel masks pads by count)."""
        from dsort_tpu.data.ingest import _pack_be64, terasort_secondary
        from dsort_tpu.data.partition import equal_partition

        p = self.num_workers
        cap = _shard_cap(self.wave_recs, p)
        sizes = equal_partition(len(recs), p)
        k1 = np.zeros((p, cap), np.uint64)
        k2 = np.zeros((p, cap), np.uint16)
        rv = np.zeros((p, cap, self.RECORD_BYTES), np.uint8)
        off = 0
        for i, s in enumerate(sizes):
            rows = recs[off : off + s]
            k1[i, :s] = _pack_be64(rows[:, :8])
            k2[i, :s] = terasort_secondary(rows[:, 8:10]).astype(np.uint16)
            rv[i, :s] = rows
            off += s
        return k1, k2, rv, np.asarray(sizes, np.int32)

    def _dispatch_wave(self, shards, metrics, timer):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dsort_tpu.obs.prof import LEDGER

        k1, k2, rv, counts = shards
        cap = k1.shape[1]
        spec = NamedSharding(self.mesh, P(self.axis))
        fn = self._build_sort(cap)
        with timer.phase("wave_sort"):
            xk1, xk2, xrv, cj = jax.device_put(
                (
                    k1.reshape(-1),
                    k2.reshape(-1),
                    rv.reshape(-1, self.RECORD_BYTES),
                    counts,
                ),
                spec,
            )
            sorted_recs = fn(xk1, xk2, xrv, cj)
        LEDGER.drain_to(metrics)
        retained = None
        if self.redundancy > 1:
            # The redundancy plane of the host-side record exchange: pull
            # the sorted shards D2H — the fetch `_retire_wave` needs
            # anyway — BEFORE the fault seam, so a device loss past this
            # point cannot take the wave's work with it.
            with timer.phase("wave_spill"):
                retained = np.asarray(jax.device_get(sorted_recs)).reshape(
                    self.num_workers, -1, self.RECORD_BYTES
                )
        if self.fault_hook is not None:
            from dsort_tpu.scheduler.fault import WorkerFailure

            try:
                self.fault_hook()
            except WorkerFailure as e:
                if retained is not None:
                    e.wave_record_state = (retained, counts)
                raise
        return (retained if retained is not None else sorted_recs), counts

    def _retire_wave(
        self, w, state, splitters, ckpt, metrics, timer, save
    ) -> None:
        """Host-side exchange + run merge for one wave: split each device's
        sorted shard at the fixed splitters, then heap-merge each range's
        ``P`` sub-runs into its single (wave, run) record run."""
        import jax

        from dsort_tpu.data.ingest import _pack_be64

        sorted_recs, counts = state
        p = self.num_workers
        with timer.phase("wave_spill"):
            rows = np.asarray(jax.device_get(sorted_recs)).reshape(
                p, -1, self.RECORD_BYTES
            )
            per_range: list[list[np.ndarray]] = [[] for _ in range(p)]
            for d in range(p):
                shard = rows[d, : int(counts[d])]
                k1 = _pack_be64(shard[:, :8])
                bounds = np.searchsorted(k1, splitters, side="left")
                lo = 0
                for r in range(p):
                    hi = int(bounds[r]) if r < p - 1 else len(shard)
                    if hi > lo:
                        per_range[r].append(shard[lo:hi])
                    lo = hi
            total = 0
            for r in range(p):
                run = self._merge_record_runs(per_range[r], metrics)
                total += len(run)
                save(ckpt.save_wave_run, w, r, run)
        metrics.bump("waves_sorted")
        metrics.bump("runs_sorted", p)
        metrics.event("wave_done", wave=w, runs=p, n_keys=total)

    def _merge_record_runs(self, subs, metrics) -> np.ndarray:
        from dsort_tpu.data.ingest import _pack_be64, terasort_secondary
        from dsort_tpu.runtime import native

        subs = [s for s in subs if len(s)]
        if not subs:
            return np.zeros((0, self.RECORD_BYTES), np.uint8)
        if len(subs) == 1:
            return np.array(subs[0])
        k1s = [_pack_be64(s[:, :8]) for s in subs]
        k2s = [
            terasort_secondary(s[:, 8:10]).astype(np.uint16) for s in subs
        ]
        if native.available():
            metrics.bump("native_merges")
            out = np.empty(
                (sum(len(s) for s in subs), self.RECORD_BYTES), np.uint8
            )
            native.kway_merge_kv2(k1s, k2s, subs, out_v=out)
            return out
        order = np.lexsort((np.concatenate(k2s), np.concatenate(k1s)))
        return np.concatenate(subs)[order]

    def _coded_recover_wave(
        self, w, exc, state, splitters, ckpt, metrics, timer
    ) -> None:
        """Complete record wave ``w`` from the retained host shards.

        The wave's sorted shards were fetched D2H before the loss
        surfaced (`_dispatch_wave`), so the normal host-side retire —
        split at the fixed splitters + heap merge — runs unchanged on the
        retained copy: ``wave_runs_resorted`` stays 0 and the journal
        carries the same ``coded_recover`` accounting as the key
        pipeline's replica-plane repair (``replica_bytes=0`` — retention
        ships nothing extra)."""
        from dsort_tpu.parallel.coded import dead_positions

        t0 = time.monotonic()
        positions = sorted(set(dead_positions(exc)))
        per_range: dict[int, int] = {}

        def save(f, w_, r, run):
            per_range[r] = len(run)
            f(w_, r, run)

        self._retire_wave(w, state, splitters, ckpt, metrics, timer, save)
        recovered = sum(per_range.get(d, 0) for d in positions)
        metrics.bump("coded_recoveries")
        metrics.bump("coded_recovered_keys", recovered)
        metrics.event(
            "coded_recover",
            dead=positions,
            holders={},
            recovered_keys=recovered,
            replica_bytes=0,
            redundancy=self.redundancy,
            mode="retain",
            wall_s=round(time.monotonic() - t0, 6),
            wave=w,
        )
        log.warning(
            "record wave %d repaired CODED: %d record(s) of %d dead "
            "range(s) retired from retained host shards — zero runs "
            "re-sorted", w, recovered, len(positions),
        )
        _die_check(w)

    def _repair_wave(
        self, recs, w, missing, splitters, ckpt, metrics, reason
    ) -> None:
        from dsort_tpu.data.ingest import _pack_be64, terasort_secondary

        p = self.num_workers
        metrics.event(
            "wave_resume", wave=w, missing=len(missing),
            present=p - len(missing), reason=reason,
        )
        k1 = _pack_be64(recs[:, :8])
        k2 = terasort_secondary(recs[:, 8:10]).astype(np.uint16)
        total = 0
        for r in missing:
            mask = _range_mask(k1, splitters, r, p)
            rows = recs[mask]
            order = np.lexsort((k2[mask], k1[mask]))
            run = rows[order]
            ckpt.save_wave_run(w, r, run)
            total += len(run)
            metrics.bump("wave_runs_resorted")
            metrics.bump("runs_sorted")
            metrics.bump("wave_resort_keys", len(run))
        metrics.event("wave_done", wave=w, runs=len(missing), n_keys=total)
        log.warning(
            "record wave %d repaired: %d/%d runs re-sorted on host (%s)",
            w, len(missing), p, reason,
        )

    def _merge_ranges(self, num_waves, n, ckpt, metrics, out) -> None:
        from dsort_tpu.data.ingest import _pack_be64, terasort_secondary
        from dsort_tpu.runtime import native

        p = self.num_workers
        off = 0
        for r in range(p):
            runs = [
                ckpt.load_wave_run_mmap(w, r) for w in range(num_waves)
            ]
            runs = [x for x in runs if len(x)]
            ln = sum(len(x) for x in runs)
            target = out[off : off + ln]
            if not runs:
                pass
            elif len(runs) == 1:
                target[:] = runs[0]
            elif native.available():
                metrics.bump("native_merges")
                k1s = [_pack_be64(np.asarray(x[:, :8])) for x in runs]
                k2s = [
                    terasort_secondary(np.asarray(x[:, 8:10])).astype(
                        np.uint16
                    )
                    for x in runs
                ]
                native.kway_merge_kv2(k1s, k2s, runs, out_v=target)
            else:
                allrec = np.concatenate([np.asarray(x) for x in runs])
                order = np.lexsort(
                    (
                        terasort_secondary(allrec[:, 8:10]).astype(np.uint16),
                        _pack_be64(allrec[:, :8]),
                    )
                )
                target[:] = allrec[order]
            off += ln
        if off != n:
            raise RuntimeError(
                f"wave merge assembled {off} of {n} records; the run store "
                "is inconsistent — clear the spill dir and re-run"
            )
