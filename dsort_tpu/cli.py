"""Command-line interface (L6 of SURVEY.md's layer map).

The reference's entire user surface is a stdin REPL on the master ("type a
filename, get output.txt", ``server.c:160-167``) plus conf-file argv
(``server.c:100-103``).  The CLI keeps that workflow (`dsort serve` is the
REPL; conf files in the reference's own format are accepted) and adds the
one-shot, data-generation, cluster, and worker entry points a real tool
needs.

  dsort run INPUT [-o OUT]      one sort job (file -> file)
  dsort run --device-resident   same, sorted array stays on the mesh and
                                validates on device (no relay)
  dsort serve                   REPL: filenames on stdin until 'exit'
  dsort gen N -o FILE           synthetic inputs (uniform / zipf)
  dsort coordinator             native TCP coordinator for a worker cluster
  dsort worker                  worker shim joining a coordinator
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

from dsort_tpu.config import SortConfig
from dsort_tpu.utils.logging import get_logger
from dsort_tpu.utils.metrics import Metrics

# Wedged-on-first-contact latch for the fused small-job path (ADVICE r4).
# The discriminator is the fused LANE, not lapse counts: all fused
# attempts serialize on one lane thread, so "one entry executing for
# longer than any cold compile ever observed" is direct evidence the chip
# is wedged, while any number of cold lapses QUEUED behind a
# still-compiling entry is not.  The ceiling sits 1.5x above the slowest
# cold compile ever recorded (~10 min for one K2a shape, r3).
FUSED_COLD_WEDGE_CEILING_S = 900.0
# A cold latch is EVIDENCE, not proof — a pathological compile can outlast
# even the ceiling (the remote service swings ~8x between sessions).  So
# unlike the warm-wedge latch (the executable had run before; the stuck
# lane is proof), the cold latch expires: after this long the path retries
# — if the stall was a compile it has drained and the retry succeeds fast;
# a truly wedged chip lapses again with the lane stuck even longer and
# re-latches on that single lapse.  Worst case on a wedged chip: one cold
# wait budget per interval.
FUSED_COLD_RETRY_S = 1800.0
# Backstop for FAIL-SLOW devices the lane discriminator cannot see (each
# fused call errors after the wait budget but before the ceiling, so the
# lane keeps draining): this many consecutive cold lapses without a single
# fused success latch the path off too.  A false trip during one slow
# compile with many queued jobs is benign — the latch expires and the
# post-drain retry succeeds and resets.
FUSED_COLD_LAPSE_BACKSTOP = 8

log = get_logger("cli")


def _load_config(args) -> SortConfig:
    """Conf file + CLI overrides, applied field-wise.

    Overrides use ``dataclasses.replace`` on the loaded config — NOT a
    rebuild through a key mapping — so a single CLI flag can never silently
    drop conf-file settings it doesn't know about (code-review r3).

    Autotune precedence (obs.plan, ARCHITECTURE §15): ``--no-autotune``
    wins, then an explicit conf ``AUTOTUNE=``, else ON — the CLI defaults
    the closed loop on (the library's `JobConfig` default stays off).  A
    knob flag actually given (``--exchange``, ``--redundancy``,
    ``--prewarm all``) joins ``JobConfig.explicit`` so the planner never
    overrides it — it journals a ``plan_override`` instead.
    """
    import dataclasses

    from dsort_tpu.config import load_conf_file

    conf_map = load_conf_file(args.conf) if args.conf else {}
    cfg = SortConfig.from_mapping(conf_map) if args.conf else SortConfig()
    job_over: dict = {}
    mesh_over: dict = {}
    if getattr(args, "workers", None):
        mesh_over["num_workers"] = args.workers
    if getattr(args, "dtype", None):
        job_over["key_dtype"] = np.dtype(args.dtype)
    if getattr(args, "kernel", None):
        job_over["local_kernel"] = args.kernel
    if getattr(args, "merge_kernel", None):
        job_over["merge_kernel"] = args.merge_kernel
    if getattr(args, "exchange", None):
        job_over["exchange"] = args.exchange
    if getattr(args, "hier_hosts", None):
        job_over["hier_hosts"] = args.hier_hosts
    if getattr(args, "redundancy", None):
        job_over["redundancy"] = args.redundancy
    if getattr(args, "redundancy_mode", None):
        job_over["redundancy_mode"] = args.redundancy_mode
    if getattr(args, "checkpoint_dir", None):
        job_over["checkpoint_dir"] = args.checkpoint_dir
    if getattr(args, "tenant", None):
        job_over["tenant"] = args.tenant
    if getattr(args, "flight_dir", None):
        job_over["flight_recorder_dir"] = args.flight_dir
    if getattr(args, "no_autotune", False):
        job_over["autotune"] = False
    elif "AUTOTUNE" not in conf_map:
        job_over["autotune"] = True
    explicit = set(cfg.job.explicit)
    if getattr(args, "exchange", None):
        explicit.add("exchange")
    if getattr(args, "redundancy", None):
        explicit.add("redundancy")
    if getattr(args, "redundancy_mode", None):
        explicit.add("redundancy_mode")
    if getattr(args, "slice_devices", None):
        explicit.add("slice_devices")
    if getattr(args, "prewarm", None) == "all":
        explicit.add("prewarm")
    if explicit != set(cfg.job.explicit):
        job_over["explicit"] = tuple(sorted(explicit))
    if job_over:
        cfg = dataclasses.replace(cfg, job=dataclasses.replace(cfg.job, **job_over))
    if mesh_over:
        cfg = dataclasses.replace(
            cfg, mesh=dataclasses.replace(cfg.mesh, **mesh_over)
        )
    return cfg


def _job_id_for(path: str, explicit: str | None) -> str:
    """Stable checkpoint job id for a CLI input file.

    Defaults to the sanitized basename, so re-running ``dsort run FILE``
    after a failure resumes FILE's own checkpoints; the fingerprint guard in
    the schedulers clears stale state if FILE's contents changed.  An
    explicit id is validated, not silently rewritten: ids like ``..`` would
    escape the checkpoint root (and its stale-state clear() would rmtree
    the parent), so they are refused loudly.
    """
    import re

    if explicit:
        if re.fullmatch(r"[A-Za-z0-9._-]+", explicit) and explicit.strip("."):
            return explicit
        raise SystemExit(
            f"invalid --job-id {explicit!r}: use letters, digits, '.', '_', "
            "'-' (and not only dots)"
        )
    name = os.path.basename(str(path))
    jid = re.sub(r"[^A-Za-z0-9._-]", "_", name)
    return jid if jid.strip(".") else "job"


def _make_sorter(cfg: SortConfig, mode: str):
    """Build the sort callable for one of the execution modes."""
    if mode == "spmd":
        from dsort_tpu.models.pipelines import FUSED_SMALL_JOB_MAX, fused_sort_small
        from dsort_tpu.scheduler import SpmdScheduler

        import jax

        devs = jax.devices()
        n = cfg.mesh.num_workers or len(devs)
        sched = SpmdScheduler(devices=devs[:n], job=cfg.job)
        # Once a fused attempt wedges, its lane thread is stuck for the
        # process lifetime and the lane key never changes — skip the fused
        # path from then on instead of paying a full wait budget per job.
        fused_wedged = threading.Event()
        # A chip genuinely wedged on FIRST contact never warms the fused
        # (lane,size) bucket, so every lapse stays "cold" and the
        # compile-grace exemption below would retry forever (ADVICE r4).
        # Bound it with the lane-stuck discriminator (see the module
        # constants): once the fused lane has been inside ONE entry for
        # longer than any compile ever observed, latch the path off until
        # the retry interval expires.
        fused_cold_latch_ts = [0.0]  # 0 = cold latch inactive
        fused_cold_streak = [0]  # consecutive cold lapses since a success

        def fused_path_open() -> bool:
            if fused_wedged.is_set():
                return False  # warm wedge: permanent (stuck proven lane)
            ts = fused_cold_latch_ts[0]
            return not ts or time.monotonic() - ts > FUSED_COLD_RETRY_S

        def sorter(data, metrics, job_id=None):
            # Small jobs skip the SPMD driver: one fused device program is
            # ~2 dispatches instead of ~7, which dominates at this size
            # (VERDICT r2 item 3).  Fault tolerance is preserved: a device/
            # runtime failure on the fused path falls back to the SPMD
            # scheduler, which probes, re-forms and retries.  When the user
            # asked for checkpointing (checkpoint_dir + job_id), the
            # scheduler path runs even for small jobs — resumability wins
            # over dispatch count there.
            checkpointing = cfg.job.checkpoint_dir and job_id
            # A coded job (redundancy > 1) must reach the exchange plane:
            # the fused single-device shortcut has no replica plane, and
            # silently dropping an explicit availability posture would be
            # worse than the extra dispatches — same rule as checkpointing.
            if (
                len(data) < FUSED_SMALL_JOB_MAX
                and not checkpointing
                and cfg.job.redundancy <= 1
                and fused_path_open()
            ):
                try:
                    # run_bounded: the fused program's completion barrier
                    # (the result fetch inside fused_sort_small) is covered
                    # by the same in-flight hang detection as the SPMD
                    # collective (VERDICT r3 #1) — a wedged chip makes this
                    # time out and fall back, never block forever.
                    metrics.event(
                        "job_start", mode="fused", n_keys=len(data),
                        job_id=job_id, tenant=cfg.job.tenant,
                    )
                    out = sched.run_bounded(
                        lambda: fused_sort_small(
                            data, cfg.job.local_kernel, metrics
                        ),
                        n_keys=len(data), tag="fused",
                    )
                    metrics.bump("fused_small_jobs")
                    metrics.event(
                        "job_done", n_keys=len(data),
                        counters=dict(metrics.counters),
                    )
                    fused_cold_latch_ts[0] = 0.0
                    fused_cold_streak[0] = 0
                    return out
                except Exception as e:
                    from dsort_tpu.scheduler.fault import (
                        ProgramWaitTimeout,
                        classify_runtime_error,
                    )

                    if not isinstance(e, ProgramWaitTimeout) and (
                        classify_runtime_error(e) is None
                    ):
                        raise  # genuine program error, not a device loss/hang
                    if isinstance(e, ProgramWaitTimeout) and not getattr(
                        e, "cold", False
                    ):
                        # Only a WARM lapse (the fused executable had
                        # completed here before) latches the path off — a
                        # cold lapse is likely the one-time compile running
                        # long (observed r4: ~5 min vs a 30-150 s grace);
                        # the compile continues on its lane, warms the jit
                        # cache, and the next small job tries fused again.
                        fused_wedged.set()
                    elif isinstance(e, ProgramWaitTimeout):
                        stuck = sched.lane_stuck_for("fused")
                        # The streak resets ONLY on a fused success: a
                        # sustained fail-slow device re-latches on the
                        # single post-expiry retry lapse (streak still at
                        # the backstop), matching the wedged-chip path's
                        # one-budget-per-interval worst case.  Wedged-chip
                        # diagnosis (lane stuck) is checked first so the
                        # log names the right failure mode.
                        fused_cold_streak[0] += 1
                        if stuck > FUSED_COLD_WEDGE_CEILING_S:
                            log.warning(
                                "fused path latched off for %.0f s: the "
                                "fused lane has been inside one entry for "
                                "%.0f s (past the %.0f s compile ceiling "
                                "— chip wedged on first contact, not "
                                "compiling)", FUSED_COLD_RETRY_S, stuck,
                                FUSED_COLD_WEDGE_CEILING_S,
                            )
                            fused_cold_latch_ts[0] = time.monotonic()
                        elif (
                            fused_cold_streak[0]
                            >= FUSED_COLD_LAPSE_BACKSTOP
                        ):
                            log.warning(
                                "fused path latched off for %.0f s: %d "
                                "consecutive cold wait lapses without a "
                                "fused success (fail-slow device backstop)",
                                FUSED_COLD_RETRY_S, fused_cold_streak[0],
                            )
                            fused_cold_latch_ts[0] = time.monotonic()
                    metrics.bump("fused_fallbacks")
                    metrics.event(
                        "fused_fallback",
                        reason=str(e).splitlines()[0][:120],
                    )
                    log.warning(
                        "fused small-job path failed (%s); retrying on the "
                        "SPMD scheduler", str(e).splitlines()[0][:120],
                    )
            return sched.sort(data, metrics=metrics, job_id=job_id)

        return sorter
    if mode == "taskpool":
        from dsort_tpu.scheduler import DeviceExecutor, Scheduler

        import jax

        devs = jax.devices()
        n = cfg.mesh.num_workers or len(devs)
        sched = Scheduler(DeviceExecutor(devices=devs[:n]), cfg.job)
        return lambda data, metrics, job_id=None: sched.run_job(
            data, metrics=metrics, job_id=job_id
        )
    if mode == "local":
        from dsort_tpu.models.pipelines import fused_sort_small

        if cfg.job.checkpoint_dir:
            log.warning(
                "--mode local runs one fused device program and does not "
                "checkpoint; --checkpoint-dir/--job-id are ignored (use "
                "spmd or taskpool mode for resumable jobs)"
            )

        def local_sorter(data, metrics, job_id=None):
            # Journal the job boundaries here too: local mode has no
            # scheduler to emit them, and without job_start/job_done the
            # SLO tracker (obs.slo) cannot see local-mode jobs at all.
            metrics.event(
                "job_start", mode="local", n_keys=len(data), job_id=job_id,
                tenant=cfg.job.tenant,
            )
            out = fused_sort_small(data, cfg.job.local_kernel, metrics)
            metrics.event(
                "job_done", n_keys=len(data), counters=dict(metrics.counters)
            )
            return out

        return local_sorter
    raise SystemExit(f"unknown mode {mode!r}")


def _run_one(
    sorter, in_path: str, out_path: str, dtype, job_id=None, journal=None,
    telemetry=None, args=None,
) -> None:
    from dsort_tpu.data.ingest import read_ints_file, write_ints_file

    t0 = time.perf_counter()
    data = read_ints_file(in_path, dtype=dtype)
    metrics = Metrics(journal=journal)
    if telemetry is not None:
        telemetry.attach(metrics)
    if args is not None:
        _maybe_memwatch(args, metrics)
    try:
        out = sorter(data, metrics, job_id=job_id)
    except BaseException as e:
        # The schedulers emit job_failed only on their CLEAN failure paths
        # (all workers dead); any other escape after job_start would leave
        # the job open forever on the telemetry side — jobs_in_flight
        # inflated for the rest of a serve session.  A duplicate
        # job_failed (scheduler already emitted one) is a no-op for the
        # taps, so closing unconditionally here is safe.
        metrics.event(
            "job_failed", reason=(str(e).splitlines() or [repr(e)])[0][:120],
            counters=dict(metrics.counters),
        )
        raise
    # The 'fetched' SLO stage boundary: on the relay path the sorted keys
    # are host-resident exactly here (obs.slo — sorted_to_fetched).
    metrics.event("result_fetch", n_keys=len(out))
    write_ints_file(out_path, out)
    dt = time.perf_counter() - t0
    log.info(
        "sorted %d keys in %.1f ms (%s) -> %s | phases: %s | %s",
        len(data), dt * 1e3, in_path, out_path, metrics.summary()["phases_ms"],
        dict(metrics.counters),
    )


def _open_journal(args):
    """An `EventLog` when ``--journal PATH`` was given, else None.

    ``--journal-rotate-mb N`` bounds any one JSONL file: at the threshold
    the flushed file rotates to ``path.N`` (`EventLog` docs) and ``dsort
    report`` stitches the set back together.
    """
    if not getattr(args, "journal", None):
        return None
    from dsort_tpu.utils.events import EventLog

    mb = getattr(args, "journal_rotate_mb", None)
    return EventLog(rotate_bytes=int(mb * (1 << 20)) if mb else None)


def _maybe_memwatch(args, metrics) -> None:
    """Attach the HBM-watermark tap (``--memwatch``) to a job's metrics."""
    if getattr(args, "memwatch", False):
        from dsort_tpu.obs.prof import MemWatch

        MemWatch().attach(metrics)


def _write_journal(journal, args) -> None:
    if journal is not None:
        # Append-only flush: serve/coordinator call this after EVERY job of
        # a session, and rewriting the whole file each time would be
        # O(session^2) IO.
        journal.flush_jsonl(args.journal)
        log.info("event journal written to %s (%d events)",
                 args.journal, len(journal))


def _make_device_scheduler(cfg: SortConfig):
    """The `SpmdScheduler` behind every ``--device-resident`` entry point."""
    from dsort_tpu.scheduler import SpmdScheduler

    import jax

    devs = jax.devices()
    n = cfg.mesh.num_workers or len(devs)
    return SpmdScheduler(devices=devs[:n], job=cfg.job)


def _run_one_device(
    cfg, in_path: str, out_path: str, dtype, journal, args=None
) -> int:
    """One device-resident job: sort, validate on device, then write.

    The sorted array never relays to the host for validation — the order
    check and the FNV multiset checksum run as jitted reductions on the
    mesh, and the permutation proof compares the device checksum against
    the (already host-resident) input's checksum.  The single D2H is the
    explicit ``to_host()`` that feeds the output file the `run` contract
    requires.
    """
    from dsort_tpu.data.ingest import read_ints_file, write_ints_file
    from dsort_tpu.models.validate import _multiset

    if cfg.job.checkpoint_dir:
        # Surface the semantics change up front (the scheduler's own warning
        # only fires when a job_id reaches it): device-resident jobs do not
        # persist ranges — a crash re-runs the whole job.
        log.warning(
            "--device-resident does not checkpoint: --checkpoint-dir/"
            "--job-id are ignored; a failed job re-runs from the input"
        )
    sched = _make_device_scheduler(cfg)
    t0 = time.perf_counter()
    data = read_ints_file(in_path, dtype=dtype)
    metrics = Metrics(journal=journal)
    if args is not None:
        _maybe_memwatch(args, metrics)
    handle = sched.sort(data, metrics=metrics, keep_on_device=True)
    rep = handle.validate_on_device()
    in_sum = _multiset(data, len(data), data.dtype.itemsize)
    perm_ok = rep.records == len(data) and rep.checksum == in_sum
    write_ints_file(out_path, handle.to_host())
    dt = time.perf_counter() - t0
    log.info(
        "sorted %d keys in %.1f ms (%s, device-resident) -> %s | on-device "
        "validate: sorted=%s permutation=%s checksum=%016x | phases: %s | %s",
        len(data), dt * 1e3, in_path, out_path, rep.sorted_ok, perm_ok,
        rep.checksum, metrics.summary()["phases_ms"], dict(metrics.counters),
    )
    if not (rep.sorted_ok and perm_ok):
        log.error("on-device validation FAILED for %s", in_path)
        return 1
    return 0


def cmd_run(args) -> int:
    from dsort_tpu.utils.tracing import profile_trace

    cfg = _load_config(args)
    if getattr(args, "device_resident", False):
        if args.mode != "spmd":
            raise SystemExit("--device-resident requires --mode spmd")
        journal = _open_journal(args)
        try:
            with profile_trace(getattr(args, "profile_dir", None)):
                return _run_one_device(
                    cfg, args.input, args.output or cfg.output_path,
                    np.dtype(cfg.job.key_dtype), journal, args=args,
                )
        finally:
            _write_journal(journal, args)
    sorter = _make_sorter(cfg, args.mode)
    job_id = (
        _job_id_for(args.input, args.job_id) if cfg.job.checkpoint_dir else None
    )
    journal = _open_journal(args)
    try:
        with profile_trace(getattr(args, "profile_dir", None)):
            _run_one(
                sorter, args.input, args.output or cfg.output_path,
                np.dtype(cfg.job.key_dtype), job_id=job_id, journal=journal,
                args=args,
            )
    finally:
        # The journal exists to answer "what happened" — a failed job's
        # fault timeline must land on disk too.
        _write_journal(journal, args)
    if getattr(args, "profile_dir", None):
        log.info("profiler trace written to %s", args.profile_dir)
    return 0


def _sigterm_to_interrupt(signum, frame):
    """SIGTERM handler for ``dsort serve``: route the signal into the SAME
    graceful path as Ctrl-C (drain in-flight jobs, reject new admissions,
    flush the journal, exit 0) instead of dying mid-job with an open
    journal."""
    raise KeyboardInterrupt


def _make_serve_service(args, cfg, journal, telemetry):
    """The `serve.SortService` behind ``dsort serve`` (every mode).

    spmd mode gets the full serving core — mesh-slice packing for small
    jobs, the SPMD scheduler for big ones, the compiled-variant cache;
    local/taskpool modes wrap their one-job sorter as the service runner,
    keeping admission, fairness, and graceful shutdown semantics uniform.
    """
    import dataclasses

    from dsort_tpu.serve import SortService
    from dsort_tpu.serve.fair import parse_weights

    serve_over: dict = {}
    if getattr(args, "slice_devices", None):
        serve_over["slice_devices"] = args.slice_devices
    if getattr(args, "queue_limit", None):
        serve_over["max_queue_depth"] = args.queue_limit
    if getattr(args, "tenant_limit", None):
        serve_over["max_tenant_inflight"] = args.tenant_limit
    if getattr(args, "weights", None):
        serve_over["tenant_weights"] = parse_weights(args.weights)
    if getattr(args, "slo_shed_ms", None):
        serve_over["slo_shed_ms"] = args.slo_shed_ms
    # ``--prewarm`` (no value / "auto") predicts the set from the planner's
    # admission history; ``--prewarm all`` keeps the old exhaustive ladder
    # (obs.plan's prewarm policy, ARCHITECTURE §15).
    if getattr(args, "prewarm", None) == "all":
        serve_over["prewarm_policy"] = "all"
    serve_cfg = dataclasses.replace(cfg.serve, **serve_over)
    kwargs = dict(
        job=cfg.job, serve=serve_cfg, telemetry=telemetry, journal=journal,
        journal_path=getattr(args, "journal", None),
    )
    if args.mode == "spmd":
        import jax

        devs = jax.devices()
        n = cfg.mesh.num_workers or len(devs)
        service = SortService(devices=devs[:n], **kwargs)
    else:
        service = SortService(runner=_make_sorter(cfg, args.mode), **kwargs)
    if getattr(args, "prewarm", None) or serve_cfg.prewarm:
        n = service.prewarm()
        log.info("compiled-variant cache prewarmed: %d rung(s)", n)
    return service


def cmd_serve(args) -> int:
    """The reference's interactive job loop (server.c:160-167 workflow),
    served by the multi-tenant async core (`dsort_tpu.serve`).

    Each input line submits a job through admission control; with
    ``--max-in-flight 1`` (the default) the REPL awaits each result —
    byte-compatible with the old blocking loop — while ``--max-in-flight
    N`` lets N jobs run concurrently (small jobs packed onto mesh
    sub-slices, big jobs on the full mesh).  A line may name its tenant:
    ``tenant=acme data.txt``.  SIGINT/SIGTERM drain in-flight jobs, reject
    new admissions with a typed verdict, flush the journal, and exit 0.

    ``--metrics-port`` additionally exposes the live telemetry endpoint
    (`obs.MetricsServer`): Prometheus text at ``/metrics`` (counters,
    queue depth, per-tenant admission verdicts and SLO quantiles,
    compiled-variant cache stats), JSON at ``/json``; render a scrape
    with ``dsort top``.
    """
    import signal

    cfg = _load_config(args)
    dtype = np.dtype(cfg.job.key_dtype)
    journal = _open_journal(args)
    if args.job_id and cfg.job.checkpoint_dir:
        # One explicit id across many REPL inputs would make every new file
        # clear the previous file's checkpoints (fingerprint mismatch) —
        # the per-file derived id is the only sane namespace here.
        log.warning(
            "serve mode ignores --job-id: each input file checkpoints under "
            "its own name"
        )
    telemetry = server = None
    if getattr(args, "metrics_port", None) is not None:
        from dsort_tpu.obs import MetricsServer, Telemetry

        telemetry = Telemetry()
        server = MetricsServer(telemetry, port=args.metrics_port)
        log.info("metrics endpoint: %s (render with `dsort top %s`)",
                 server.url, server.url)
    service = _make_serve_service(args, cfg, journal, telemetry)
    old_term = None
    try:
        old_term = signal.signal(signal.SIGTERM, _sigterm_to_interrupt)
    except ValueError:
        pass  # not the main thread (tests): Ctrl-C path still covered
    try:
        return _serve_loop(args, cfg, service, dtype, journal)
    finally:
        if old_term is not None:
            signal.signal(signal.SIGTERM, old_term)
        if server is not None:
            server.close()


def _parse_serve_line(line: str, default_tenant: str) -> tuple[str, str]:
    """``[tenant=NAME] path`` -> (tenant, path)."""
    name = line.strip()
    tenant = default_tenant
    if name.startswith("tenant="):
        head, _, rest = name.partition(" ")
        tenant = head[len("tenant="):]
        name = rest.strip()
    return tenant, name


def _serve_loop(args, cfg, service, dtype, journal) -> int:
    from dsort_tpu.data.ingest import read_ints_file, write_ints_file

    out_path = args.output or cfg.output_path
    max_in_flight = max(getattr(args, "max_in_flight", 1) or 1, 1)
    pending: list[tuple[str, float, object]] = []  # (name, t0, ticket)

    def reap(limit: int) -> None:
        """Write out finished tickets (FIFO); block on the OLDEST only
        while ``limit`` or more jobs are in flight — the window refills
        one slot at a time instead of draining in batch waves."""
        while pending:
            name, t0, ticket = pending[0]
            if len(pending) < limit and not ticket.done():
                break
            pending.pop(0)
            try:
                out = ticket.result()
            except Exception as e:  # a bad job must not kill the server
                log.error("job failed (%s): %s", name, e)
                continue
            try:
                write_ints_file(out_path, out)
            except OSError as e:  # nor an unwritable output path
                log.error("result write failed (%s): %s", name, e)
                continue
            log.info(
                "sorted %d keys in %.1f ms (%s, tenant %s) -> %s | %s",
                len(out), (time.perf_counter() - t0) * 1e3, name,
                ticket.tenant, out_path, dict(ticket.metrics.counters),
            )

    interrupted = False
    while True:
        try:
            line = input("Enter the filename to sort (or 'exit' to quit): ")
        except EOFError:
            break
        except KeyboardInterrupt:
            # The graceful-shutdown path (SIGINT, and SIGTERM via
            # `_sigterm_to_interrupt`): no traceback spray — drain below.
            print()
            interrupted = True
            break
        tenant, name = _parse_serve_line(line, cfg.job.tenant)
        if not name:
            continue
        if name == "exit":
            break
        try:
            data = read_ints_file(name, dtype=dtype)
        except Exception as e:  # unreadable input must not kill the server
            log.error("job failed (%s): %s", name, e)
            continue
        jid = _job_id_for(name, None) if cfg.job.checkpoint_dir else None
        verdict, ticket = service.submit(
            data, tenant=tenant, job_id=name, ckpt_job_id=jid
        )
        if not verdict.admitted:
            log.error(
                "job NOT admitted (%s): %s (queue depth %d, tenant depth %d)",
                name, verdict.reason, verdict.queue_depth,
                verdict.tenant_depth,
            )
            continue
        pending.append((name, time.perf_counter(), ticket))
        # Sync mode (default) awaits every job — the reference's blocking
        # REPL semantics; async mode keeps up to max_in_flight jobs
        # running and frees one slot before prompting again.  Journal
        # flushing is the SERVICE's job (one writer): it appends after
        # every completion.
        reap(limit=max_in_flight)
    if interrupted:
        st = service.stats()
        log.warning(
            "shutting down: draining %d queued + %d in-flight job(s); new "
            "admissions are rejected with verdict 'shutting_down'",
            st["queued"], st["in_flight"],
        )
    service.shutdown(drain=True)
    reap(limit=1)  # drain: every remaining ticket is done or failed
    # The journal's close: the service recorded serve_stop and flushed the
    # file during shutdown; a journal-less session has nothing to write.
    return 0


def cmd_fleet_agent(args) -> int:
    """Run one fleet execution agent: a process owning a mesh (or mesh
    slice), serving jobs routed to it by a `dsort fleet` controller over
    the framed-JSON fleet protocol (ARCHITECTURE §12).

    Wraps the full serving core (`serve.SortService` — slice packing,
    variant cache, eviction/readmission) behind a TCP endpoint; the
    agent advertises its compiled-variant/ledger keys in heartbeats so
    the controller can route by cache locality.  ``--metrics-port``
    exposes the live per-mesh telemetry (`dsort top URL1 URL2 ...`
    renders the fleet view).  SIGINT/SIGTERM DRAIN: in-flight and queued
    jobs complete (results held for the controller), new fleet submits
    are refused with the typed ``shutting_down`` verdict, and the agent
    exits 0.
    """
    import signal

    from dsort_tpu.fleet.agent import FleetAgent

    cfg = _load_config(args)
    journal = _open_journal(args)
    telemetry = server = None
    if getattr(args, "metrics_port", None) is not None:
        from dsort_tpu.obs import MetricsServer, Telemetry

        telemetry = Telemetry()
        server = MetricsServer(telemetry, port=args.metrics_port)
        log.info("agent metrics endpoint: %s", server.url)
    service = _make_serve_service(args, cfg, journal, telemetry)
    agent = FleetAgent(
        service=service, host=args.host, port=args.port,
        agent_id=args.agent_id, journal=journal,
        journal_path=getattr(args, "journal", None),
    )
    print(f"fleet agent {agent.agent_id} listening on {agent.addr}",
          flush=True)
    stop = threading.Event()

    def _on_term(signum, frame):
        stop.set()

    old = {}
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            old[sig] = signal.signal(sig, _on_term)
        except ValueError:
            pass  # not the main thread (tests)
    try:
        stop.wait()
        log.warning("agent %s draining before exit", agent.agent_id)
        agent.close(drain=True)
    finally:
        for sig, handler in old.items():
            signal.signal(sig, handler)
        if journal is not None:
            _write_journal(journal, args)
        if server is not None:
            server.close()
    return 0


def cmd_fleet(args) -> int:
    """The fleet controller REPL: `dsort serve`'s workflow, routed over
    many mesh-owning agent processes (ARCHITECTURE §12).

    A pure control plane — admission, weighted-DRR fairness, SLO
    shedding, variant-cache-locality routing.  The CONTROLLER LIBRARY
    (`fleet.controller`) never imports a backend (test-pinned); this CLI
    wrapper does touch jax config for the shared `dsort` config surface —
    embed `FleetController` directly for a truly backend-free process.
    Each input line submits a job (``tenant=acme data.txt``)
    which is spooled, queued, and dispatched to an agent from
    ``--agents host:port,...`` (conf ``FLEET_AGENTS``).  With
    ``--state-dir`` every transition persists, so a controller restart
    loses no job: in-flight work keeps running on its agents and
    re-attaches via the journaled job ids; queued jobs drain in the same
    DRR order.  ``--routing random`` is the locality A/B baseline.
    SIGINT/SIGTERM drain like ``dsort serve``.
    """
    import dataclasses
    import signal

    from dsort_tpu.fleet.controller import FleetController
    from dsort_tpu.serve.fair import parse_weights

    cfg = _load_config(args)
    dtype = np.dtype(cfg.job.key_dtype)
    journal = _open_journal(args)
    fleet_cfg = cfg.fleet
    if getattr(args, "state_dir", None):
        fleet_cfg = dataclasses.replace(fleet_cfg, state_dir=args.state_dir)
    if getattr(args, "routing", None):
        fleet_cfg = dataclasses.replace(fleet_cfg, routing=args.routing)
    if getattr(args, "dispatch_timeout", None):
        fleet_cfg = dataclasses.replace(
            fleet_cfg, dispatch_timeout_s=args.dispatch_timeout
        )
    if getattr(args, "no_health_telemetry", False):
        fleet_cfg = dataclasses.replace(fleet_cfg, telemetry=False)
    agents = getattr(args, "agents", None) or ",".join(fleet_cfg.agents)
    if not agents:
        raise SystemExit(
            "dsort fleet needs --agents host:port,... (or conf FLEET_AGENTS)"
        )
    telemetry = server = None
    if getattr(args, "metrics_port", None) is not None:
        from dsort_tpu.obs import MetricsServer, Telemetry

        telemetry = Telemetry()
        server = MetricsServer(telemetry, port=args.metrics_port)
        log.info("controller metrics endpoint: %s", server.url)
    controller = FleetController(
        agents,
        state_dir=fleet_cfg.state_dir,
        max_queue_depth=args.queue_limit or cfg.serve.max_queue_depth,
        max_tenant_inflight=args.tenant_limit or cfg.serve.max_tenant_inflight,
        drr_quantum_keys=cfg.serve.drr_quantum_keys,
        tenant_weights=(
            parse_weights(args.weights) if getattr(args, "weights", None)
            else dict(cfg.serve.tenant_weights)
        ),
        slo_shed_ms=args.slo_shed_ms or cfg.serve.slo_shed_ms,
        routing=fleet_cfg.routing,
        heartbeat_s=fleet_cfg.heartbeat_s,
        dispatch_timeout_s=fleet_cfg.dispatch_timeout_s,
        default_tenant=cfg.job.tenant,
        journal=journal,
        journal_path=getattr(args, "journal", None),
        telemetry=telemetry,
        health_telemetry=fleet_cfg.telemetry,
        flight_dir=cfg.job.flight_recorder_dir,
        # Closed-loop redundancy (obs.plan policy 3): with autotune on and
        # no explicit --redundancy/conf REDUNDANCY, each dispatch stamps a
        # planned r from the rolling health verdicts; an explicit value is
        # forwarded as-is and journals a plan_override per dispatch.
        autotune=cfg.job.autotune,
        redundancy=(
            cfg.job.redundancy if cfg.job.is_explicit("redundancy") else None
        ),
        redundancy_mode=(
            cfg.job.redundancy_mode
            if cfg.job.is_explicit("redundancy_mode") else None
        ),
    )
    if controller.stats()["agents"] == 0:
        log.warning(
            "no agents reachable: submissions are REJECTED with the typed "
            "verdict 'no_capacity' until one connects (heartbeat retries "
            "every %.1fs)", fleet_cfg.heartbeat_s,
        )
    old_term = None
    try:
        old_term = signal.signal(signal.SIGTERM, _sigterm_to_interrupt)
    except ValueError:
        pass
    try:
        # The controller implements the SortService REPL surface (submit/
        # stats/shutdown + future-style tickets), so the serve loop drives
        # it unchanged — one copy of the REPL contract.
        return _serve_loop(args, cfg, controller, dtype, journal)
    finally:
        if old_term is not None:
            signal.signal(signal.SIGTERM, old_term)
        if server is not None:
            server.close()


def cmd_batch(args) -> int:
    """Sort MANY files as one batched SPMD program (the `MeshConfig.dp` axis).

    The reference serves its REPL one job at a time (``server.c:160-167``);
    `BatchSampleSort` runs a whole batch concurrently over a ``(dp, w)``
    mesh — jobs batch over ``dp``, each job's keys shard over ``w``.  Each
    input FILE writes ``<outdir>/<basename>`` sorted.
    """
    import dataclasses

    from dsort_tpu.config import ConfigError
    from dsort_tpu.data.ingest import read_ints_file, write_ints_file
    from dsort_tpu.parallel.mesh import make_mesh
    from dsort_tpu.parallel.sample_sort import BatchSampleSort

    cfg = _load_config(args)
    dtype = np.dtype(cfg.job.key_dtype)
    # Outputs land at outdir/<basename>; two inputs sharing a basename would
    # silently overwrite each other — refuse up front (code-review r3).
    names = [os.path.basename(p) for p in args.inputs]
    dupes = sorted({n for n in names if names.count(n) > 1})
    if dupes:
        raise SystemExit(
            f"duplicate input basenames would overwrite each other in "
            f"--outdir: {dupes}"
        )
    # Mesh sizing/validation is make_mesh's job (it computes w from the
    # visible devices and rejects overcommit), not re-derived here.
    mesh_cfg = dataclasses.replace(cfg.mesh, dp=args.dp or cfg.mesh.dp)
    try:
        mesh = make_mesh(mesh_cfg)
    except ConfigError as e:
        raise SystemExit(str(e))
    dp = int(mesh.shape[mesh_cfg.dp_axis_name])
    w = int(mesh.shape[mesh_cfg.axis_name])
    os.makedirs(args.outdir, exist_ok=True)
    t0 = time.perf_counter()
    jobs = [read_ints_file(p, dtype=dtype) for p in args.inputs]
    journal = _open_journal(args)
    metrics = Metrics(journal=journal)
    # With --checkpoint-dir each file's sorted result persists under its
    # basename: a killed batch re-run restores completed files and re-packs
    # the buckets over the missing ones (VERDICT r3 #7).  Ids must be
    # deduplicated AFTER sanitization — distinct basenames like 'a b.txt'
    # and 'a_b.txt' map to one id, and two jobs sharing a checkpoint id
    # would fingerprint-clear each other every run.
    job_ids = None
    if cfg.job.checkpoint_dir:
        job_ids = [_job_id_for(p, None) for p in args.inputs]
        id_dupes = sorted({j for j in job_ids if job_ids.count(j) > 1})
        if id_dupes:
            raise SystemExit(
                "these inputs sanitize to the same checkpoint id(s) "
                f"{id_dupes}; rename the files or drop --checkpoint-dir"
            )
    try:
        outs = BatchSampleSort(mesh, cfg.job).sort(
            jobs, metrics=metrics, job_ids=job_ids
        )
    finally:
        _write_journal(journal, args)
    for src, out in zip(args.inputs, outs):
        write_ints_file(os.path.join(args.outdir, os.path.basename(src)), out)
    dt = time.perf_counter() - t0
    log.info(
        "batch-sorted %d jobs (%d keys total) in %.1f ms on a (dp=%d, w=%d) "
        "mesh -> %s | phases: %s",
        len(jobs), sum(len(j) for j in jobs), dt * 1e3, dp, w, args.outdir,
        metrics.summary()["phases_ms"],
    )
    return 0


def cmd_gen(args) -> int:
    from dsort_tpu.data.ingest import (
        gen_terasort_file,
        gen_uniform,
        gen_uniform_bin_file,
        gen_zipf,
        write_ints_file,
    )

    if args.dist == "terasort":
        if args.format == "bin":
            # TeraSort output is ALWAYS binary 100-byte records; a --format
            # bin here would silently be ignored while the user expects raw
            # keys — refuse loudly instead (code-review r3).
            raise SystemExit(
                "--format bin is for raw key files; --dist terasort always "
                "writes binary 100-byte records (drop --format)"
            )
        gen_terasort_file(args.output, args.n, seed=args.seed)
        log.info("wrote %d terasort records to %s", args.n, args.output)
        return 0
    if args.format == "bin":
        # Raw binary keys (ExternalSort's input format), streamed in bounded
        # memory — the only practical format at 10^9-key scale.
        if args.dist != "uniform":
            raise SystemExit("--format bin supports --dist uniform only")
        gen_uniform_bin_file(
            args.output, args.n, dtype=np.dtype(args.dtype), seed=args.seed
        )
        log.info("wrote %d %s binary keys to %s", args.n, args.dtype, args.output)
        return 0
    if args.dist == "uniform":
        data = gen_uniform(args.n, dtype=np.dtype(args.dtype), seed=args.seed)
    else:
        data = gen_zipf(
            args.n, a=args.zipf_a, dtype=np.dtype(args.dtype), seed=args.seed
        )
    write_ints_file(args.output, data)
    log.info("wrote %d %s keys (%s) to %s", args.n, args.dtype, args.dist, args.output)
    return 0


def cmd_terasort(args) -> int:
    """Sort a binary TeraSort record file (BASELINE config #4)."""
    import jax

    from dsort_tpu.data.ingest import (
        read_terasort_file,
        terasort_secondary,
        write_terasort_file,
    )
    from dsort_tpu.parallel.mesh import local_device_mesh
    from dsort_tpu.parallel.sample_sort import SampleSort
    from dsort_tpu.config import JobConfig

    # The exchange knob, conf-key parity with `dsort run`/`dsort external`:
    # an explicit --exchange flag wins, then a conf EXCHANGE key, then the
    # JobConfig default (same precedence ladder as _load_config).
    conf_job = SortConfig.from_conf_file(args.conf).job if args.conf else None
    exchange = getattr(args, "exchange", None) or (
        conf_job.exchange if conf_job else None
    )
    hier_hosts = getattr(args, "hier_hosts", None) or (
        conf_job.hier_hosts if conf_job else 0
    )
    if args.external:
        from dsort_tpu.models.external_sort import ExternalTeraSort

        mesh_n = getattr(args, "mesh", None)
        if mesh_n is None and args.conf:
            mesh_n = SortConfig.from_conf_file(args.conf).external.mesh
        journal = _open_journal(args)
        metrics = Metrics(journal=journal)
        _maybe_memwatch(args, metrics)
        t0 = time.perf_counter()
        try:
            if mesh_n:
                # Wave pipeline: mesh-parallel run generation, host spill/
                # merge overlapping the next wave's device work.
                from dsort_tpu.models.wave_sort import ExternalWaveTeraSort
                from dsort_tpu.parallel.mesh import local_device_mesh

                s = ExternalWaveTeraSort(
                    mesh=local_device_mesh(mesh_n),
                    wave_recs=args.run_recs,
                    spill_dir=args.spill_dir,
                    job_id=args.job_id,
                    resume=not args.no_resume,
                    job=conf_job,
                    exchange=getattr(args, "exchange", None),
                )
            else:
                if args.workers is not None:
                    log.warning(
                        "--workers needs the wave pipeline: pass --mesh N "
                        "to make run generation mesh-parallel (without it, "
                        "external run generation is single-device and only "
                        "the merge parallelizes over host cores)"
                    )
                if exchange:
                    log.warning(
                        "--exchange has no effect without --mesh: the "
                        "single-device external record sort has no "
                        "exchange; add --mesh N to run record waves"
                    )
                s = ExternalTeraSort(
                    run_recs=args.run_recs,
                    spill_dir=args.spill_dir,
                    job_id=args.job_id,
                    resume=not args.no_resume,
                )
            s.sort_file(
                args.input, args.output or "terasort_out.bin", metrics=metrics
            )
        finally:
            _write_journal(journal, args)
        dt = time.perf_counter() - t0
        n = os.path.getsize(args.input) // ExternalTeraSort.RECORD_BYTES
        log.info(
            "terasort (external%s): %d records in %.1f ms (%.2f Mrec/s) | %s"
            " | phases: %s",
            f", {mesh_n}-device waves" if mesh_n else "",
            n, dt * 1e3, n / dt / 1e6, dict(metrics.counters),
            metrics.summary()["phases_ms"],
        )
        return 0

    keys, payload = read_terasort_file(args.input)
    mesh = local_device_mesh(args.workers)
    job = JobConfig(
        key_dtype=np.uint64, payload_bytes=payload.shape[1],
        exchange=exchange or JobConfig.exchange,
        hier_hosts=hier_hosts or JobConfig.hier_hosts,
    )
    metrics = Metrics()
    t0 = time.perf_counter()
    sk, sv = SampleSort(mesh, job).sort_kv(
        keys, payload, metrics=metrics, secondary=terasort_secondary(payload),
        exchange=getattr(args, "exchange", None),
    )
    dt = time.perf_counter() - t0
    write_terasort_file(args.output or "terasort_out.bin", sk, sv)
    log.info(
        "terasort: %d records in %.1f ms (%.2f Mrec/s) | phases: %s",
        len(keys), dt * 1e3, len(keys) / dt / 1e6, metrics.summary()["phases_ms"],
    )
    return 0


def cmd_external(args) -> int:
    """Out-of-core sort of a raw binary key file.

    Default: the single-device run/merge pipeline
    (`models.external_sort.ExternalSort`).  With ``--mesh N`` (or conf
    ``EXTERNAL_MESH``) the dataset runs through the WAVE pipeline
    (`models.wave_sort.ExternalWaveSort`): device-budget-sized waves are
    range-partitioned and ring-exchanged over the mesh while the previous
    wave's runs spill on the host — datasets far larger than the mesh's
    memory sort at device speed, resumable at (wave, run) granularity.
    Flags override conf keys (``EXTERNAL_RUN_ELEMS`` /
    ``EXTERNAL_WAVE_ELEMS`` / ``EXTERNAL_MESH``), same precedence as the
    serving layer's ``SERVE_*``.
    """
    ext = (
        SortConfig.from_conf_file(args.conf).external if args.conf
        else SortConfig().external
    )
    run_elems = args.run_elems if args.run_elems is not None else ext.run_elems
    wave_elems = (
        args.wave_elems if args.wave_elems is not None else ext.wave_elems
    )
    mesh_n = args.mesh if args.mesh is not None else ext.mesh
    journal = _open_journal(args)
    metrics = Metrics(journal=journal)
    _maybe_memwatch(args, metrics)
    t0 = time.perf_counter()
    try:
        if mesh_n:
            from dsort_tpu.models.wave_sort import ExternalWaveSort
            from dsort_tpu.parallel.mesh import local_device_mesh

            from dsort_tpu.config import JobConfig

            job_kw = {}
            if args.kernel:
                job_kw["local_kernel"] = args.kernel
            if getattr(args, "hier_hosts", None):
                job_kw["hier_hosts"] = args.hier_hosts
            s = ExternalWaveSort(
                mesh=local_device_mesh(mesh_n),
                wave_elems=wave_elems,
                spill_dir=args.spill_dir,
                job_id=args.job_id,
                job=JobConfig(**job_kw) if job_kw else None,
                resume=not args.no_resume,
                overlap=not getattr(args, "no_overlap", False),
                exchange=getattr(args, "exchange", None),
                redundancy=getattr(args, "redundancy", None),
                redundancy_mode=getattr(args, "redundancy_mode", None),
            )
        else:
            from dsort_tpu.models.external_sort import ExternalSort

            if getattr(args, "exchange", None):
                log.warning(
                    "--exchange has no effect without --mesh: the "
                    "single-device external sort has no exchange; add "
                    "--mesh N to run the wave pipeline"
                )
            if getattr(args, "redundancy", None) and args.redundancy > 1:
                # Louder than the --exchange case: a silently-dropped
                # availability posture would leave the operator believing
                # device-loss tolerance is active when it is not.
                log.warning(
                    "--redundancy has no effect without --mesh: the "
                    "single-device external sort has no replica plane; "
                    "add --mesh N to run coded waves"
                )
            s = ExternalSort(
                run_elems=run_elems,
                spill_dir=args.spill_dir,
                job_id=args.job_id,
                local_kernel=args.kernel or "auto",
                resume=not args.no_resume,
            )
        s.sort_binary_file(
            args.input, args.output, dtype=np.dtype(args.dtype or "int32"),
            metrics=metrics,
        )
    finally:
        # Journal parity with `dsort run`: the fault/resume timeline (wave
        # events included) must land on disk even when the job fails.
        _write_journal(journal, args)
    dt = time.perf_counter() - t0
    log.info(
        "external-sorted %s -> %s in %.1f ms%s | %s | phases: %s",
        args.input, args.output, dt * 1e3,
        f" ({mesh_n}-device waves)" if mesh_n else "",
        dict(metrics.counters), metrics.summary()["phases_ms"],
    )
    return 0


def cmd_validate(args) -> int:
    """Validate a sort output (valsort role): order + permutation-of-input."""
    from dsort_tpu.models.validate import (
        checksum_bin_file,
        checksum_ints_file,
        checksum_terasort_file,
        validate_bin_file,
        validate_ints_file,
        validate_terasort_file,
    )

    if args.terasort:
        rep = validate_terasort_file(args.input)
    elif args.binary:
        rep = validate_bin_file(args.input, dtype=np.dtype(args.dtype))
    else:
        rep = validate_ints_file(args.input, dtype=np.dtype(args.dtype))
    result = {
        "records": rep.records,
        "sorted": rep.sorted_ok,
        "checksum": f"{rep.checksum:016x}",
    }
    if rep.first_violation is not None:
        result["first_violation"] = rep.first_violation
    ok = rep.sorted_ok
    if args.against:
        if args.terasort:
            n_in, sum_in = checksum_terasort_file(args.against)
        elif args.binary:
            n_in, sum_in = checksum_bin_file(args.against, dtype=np.dtype(args.dtype))
        else:
            n_in, sum_in = checksum_ints_file(args.against, dtype=np.dtype(args.dtype))
        result["permutation_of_input"] = (
            n_in == rep.records and sum_in == rep.checksum
        )
        ok = ok and result["permutation_of_input"]
    print(json.dumps(result))
    return 0 if ok else 1


def cmd_report(args) -> int:
    """Render event journal(s): human timeline + phase/counter tables.

    With several journals (``dsort report --merge a.jsonl b.jsonl`` — the
    ``--merge`` flag is implied by passing more than one) the per-process
    traces merge into ONE aligned fleet timeline (`obs.merge`: each
    journal's monotonic base is rebased via its wall<->mono offset, every
    record tagged with its source).  Each positional path expands to its
    rotated set (``--journal-rotate-mb`` pieces stitch back into one
    journal, never mistaken for a second process).  Torn or malformed
    lines are skipped and counted, never fatal.  ``--chrome-trace``
    additionally exports a Perfetto ``trace_event`` file (one pid per
    source journal, one tid per job) that loads next to a
    ``jax.profiler`` capture.

    ``--analyze`` replays the records through `obs.analyze` instead of
    printing the timeline: phase waterfall with the cross-process
    critical path, straggler attribution, queue-wait/compile/execute
    split, wire bytes (priced against ``--link-mbps`` when given), skew
    and HBM watermarks — the why-slow verdict.  ``--analyze-json PATH``
    additionally writes the machine-readable verdict.
    """
    import json as _json

    from dsort_tpu.obs.merge import (
        expand_path_args,
        group_rotated,
        merge_records,
        read_journal_set,
    )
    from dsort_tpu.utils.events import format_report, to_chrome_trace

    try:
        # Fleet runs produce N journals per run: a positional arg may be a
        # directory or glob of per-agent journals, expanded here before the
        # rotation-set grouping (a rotated piece inside a directory still
        # stitches into its base journal, never a phantom process).
        paths = expand_path_args(args.journal)
    except ValueError as e:
        raise SystemExit(f"dsort report: {e}")
    sources = group_rotated(paths)
    journals, skipped = [], 0
    for s in sources:
        recs, sk = read_journal_set(s)
        journals.append(recs)
        skipped += sk
    if len(journals) > 1 or args.merge:
        records = merge_records(journals)
    else:
        records = journals[0]
    if skipped:
        log.warning("skipped %d malformed journal line(s)", skipped)
    rc = 0
    if args.conform:
        # Trace-contract conformance (ARCHITECTURE §16): the journal
        # replayed against the declared TRACE_CONTRACTS grammars.  A
        # violation exits 1 — this is a gate, not a report.
        from dsort_tpu.analysis.spec.contracts import (
            conformance_report,
            format_conformance,
        )

        conf = conformance_report(records)
        print(format_conformance(conf), end="")
        if not conf["ok"]:
            rc = 1
    if args.analyze or args.analyze_json:
        from dsort_tpu.obs.analyze import analyze_records, format_analysis

        link = (args.link_mbps * 1e6 / 8) if args.link_mbps else None
        verdict = analyze_records(records, link_bytes_per_s=link)
        print(format_analysis(verdict), end="")
        if args.analyze_json:
            with open(args.analyze_json, "w", encoding="utf-8") as f:
                _json.dump(verdict, f, indent=1)
            log.info("analysis verdict written to %s", args.analyze_json)
    elif not args.conform:
        print(format_report(records), end="")
    if args.chrome_trace:
        with open(args.chrome_trace, "w", encoding="utf-8") as f:
            _json.dump(to_chrome_trace(records), f)
        log.info("chrome trace written to %s (load in Perfetto / "
                 "chrome://tracing)", args.chrome_trace)
    return rc


def cmd_top(args) -> int:
    """One-shot (or ``--interval`` refreshing) console view of metrics
    endpoint scrape(s) — the operator's `top` for a running ``dsort serve
    --metrics-port`` session, or, with SEVERAL URLs (the fleet
    controller's endpoint plus one per agent), the per-mesh fleet view
    with combined admissions/cache tables (ARCHITECTURE §12)."""
    from dsort_tpu.obs.top import fetch_metrics, render_fleet, render_top

    urls = args.url or ["http://127.0.0.1:9100/metrics"]
    shown = 0
    while True:
        scrapes, unreachable = [], []
        for url in urls:
            try:
                scrapes.append((url, fetch_metrics(url)))
            except (OSError, ValueError) as e:
                log.error("scrape of %s failed: %s", url, e)
                unreachable.append(url)
        if not scrapes:
            return 1
        if shown:
            print()  # separate refreshes; no terminal tricks needed
        if len(urls) == 1:
            print(f"dsort top — {urls[0]}")
            print(render_top(scrapes[0][1]), end="")
        else:
            # A fleet view must render the REACHABLE meshes while one
            # agent restarts — that is exactly when the operator looks.
            print(f"dsort top — {len(scrapes)}/{len(urls)} sources")
            print(render_fleet(scrapes), end="")
            for url in unreachable:
                print(f"  (unreachable: {url})")
        shown += 1
        if args.interval is None or (args.count and shown >= args.count):
            return 0
        try:
            time.sleep(args.interval)
        except KeyboardInterrupt:
            print()
            return 0


def _project_root(start: str) -> str:
    """Nearest ancestor holding pyproject.toml (fallback: ``start``)."""
    d = os.path.abspath(start)
    while True:
        if os.path.exists(os.path.join(d, "pyproject.toml")):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            return os.path.abspath(start)
        d = parent


def _git_changed_files(root: str) -> list[str]:
    """Lintable files changed vs HEAD (worktree + index) plus untracked
    ones, absolute paths.  Loud on any git failure — a broken `--changed`
    must never pass vacuously as "0 files changed"."""
    import subprocess

    def run(*argv):
        r = subprocess.run(
            ["git", "-C", root, *argv], capture_output=True, text=True,
        )
        if r.returncode != 0:
            raise SystemExit(
                f"dsort lint --changed: git {' '.join(argv)} failed: "
                f"{r.stderr.strip() or r.returncode}"
            )
        return r.stdout.splitlines()

    # --relative anchors diff paths at `root` (not the git toplevel).
    names = set(run("diff", "--name-only", "--relative", "HEAD"))
    names.update(run("ls-files", "--others", "--exclude-standard"))
    from dsort_tpu.analysis.engine import _LINTABLE

    out = []
    for name in sorted(names):
        path = os.path.join(root, name)
        if name.endswith(_LINTABLE) and os.path.exists(path):
            out.append(path)
    return out


def cmd_lint(args) -> int:
    """Run the project-native static analysis suite (`dsort_tpu.analysis`).

    Checks the invariants the fault-tolerance story rests on — registry
    coverage (Python AND the C++ coordinator's event vocabulary),
    lock discipline, tracing hygiene, recovery-path exception hygiene,
    compat-shim routing, import-layer purity, durability discipline,
    protocol coverage, kernel/thread lifecycle — without running a
    cluster or touching a backend.  Exit 0 = clean (modulo baseline),
    1 = findings.
    """
    from dsort_tpu.analysis import (
        LintStats,
        format_json,
        format_sarif,
        format_text,
        lint_paths,
        load_config,
        write_baseline,
    )

    root = args.root or _project_root(os.getcwd())
    cfg = load_config(root)
    if args.baseline:
        cfg.baseline = args.baseline
    # Content-hash result cache: `make lint` stays interactive on the
    # grown tree (invalidated by any checker/config/registry change).
    cache_path = (
        None if args.no_cache else os.path.join(root, ".lint-cache.json")
    )
    if args.changed:
        if args.paths:
            raise SystemExit(
                "dsort lint: --changed and explicit paths are exclusive"
            )
        if args.write_baseline:
            # The baseline is a whole-tree artifact: regenerating it from
            # a changed-files subset would silently drop every tolerated
            # entry for unchanged files.
            raise SystemExit(
                "dsort lint: --changed and --write-baseline are exclusive "
                "(the baseline must be regenerated from the full tree)"
            )
        # Scope to the DEFAULT lint target (the package tree) when it
        # exists: a touched test fixture is bad by design and must not
        # fail the pre-commit pass.  A root without the package (another
        # project borrowing the linter) keeps the root-wide scope.
        paths = _git_changed_files(root)
        target = os.path.join(root, "dsort_tpu")
        if os.path.isdir(target):
            target += os.sep
            paths = [p for p in paths if p.startswith(target)]
        if not paths:
            sys.stdout.write("dsort lint: no changed lintable files\n")
            return 0
    else:
        # User-given paths resolve against CWD (normal CLI semantics); only
        # the default target is root-relative.  A missing path is a loud
        # error — a typo'd CI invocation must never pass vacuously as
        # "0 findings".
        paths = [os.path.abspath(p) for p in args.paths] or [
            os.path.join(root, "dsort_tpu")
        ]
        missing = [p for p in paths if not os.path.exists(p)]
        if missing:
            raise SystemExit(f"dsort lint: no such path(s): {missing}")
    if args.write_baseline:
        # Capture EVERYTHING the tree currently shows: linting through the
        # existing baseline would drop already-tolerated findings and the
        # rewrite would erase them — regenerating must be idempotent.
        path = cfg.abspath(cfg.baseline) or os.path.join(
            root, ".lint-baseline.json"
        )
        cfg.baseline = None
        diags = lint_paths(paths, cfg, cache_path=cache_path)
        write_baseline(path, diags)
        log.info("baseline written to %s (%d entries)", path, len(diags))
        return 0
    stats = LintStats() if args.stats else None
    diags = lint_paths(paths, cfg, cache_path=cache_path, stats=stats)
    formatter = {"json": format_json, "sarif": format_sarif}.get(
        args.format, format_text
    )
    sys.stdout.write(formatter(diags))
    if stats is not None:
        # Stats go to stderr so `--format sarif > out.sarif` stays a valid
        # SARIF document with the table still visible.
        sys.stderr.write(stats.format())
    return 1 if any(d.severity == "error" for d in diags) else 0


def cmd_spec(args) -> int:
    """Protocol spec plane (`dsort_tpu.analysis.spec`, ARCHITECTURE §16).

    ``dsort spec check`` explores bounded interleavings of the fleet
    protocol — frame delivery on FIFO links, retransmission, dispatch
    timeouts, link death/re-attach, controller crash+restore — with the
    REAL `ControlPolicy` embedded via its ``state_dict`` round-trip, and
    checks every reached state against the safety invariant catalog
    (`SPEC_INVARIANTS`).  A violation is minimized to a deterministic
    schedule and (with ``--dump-fixture``) written as a replayable JSON
    fixture.  Exit 0 = no violation in the explored space; 1 = violation.

    ``dsort spec replay --fixture F`` re-executes a dumped schedule and
    exits 0 iff it still reproduces its recorded invariant violation —
    the regression contract for ``tests/data/spec/`` fixtures.

    Backend-free by design: like ``lint``, this command never initializes
    JAX (the model is pure control-plane state).
    """
    from dsort_tpu.analysis.spec.model import (
        ModelConfig,
        check_model,
        dump_fixture,
        format_result,
        load_fixture,
        replay_schedule,
    )

    seams = tuple(args.seam or ())
    if args.action == "replay":
        if not args.fixture:
            raise SystemExit("dsort spec replay: --fixture is required")
        schedule, cfg, fseams = load_fixture(args.fixture)
        violation = replay_schedule(schedule, cfg, fseams)
        if violation is None:
            print(f"{args.fixture}: schedule no longer violates anything")
            return 1
        print(
            f"{args.fixture}: reproduces {violation.invariant} after "
            f"{len(violation.schedule)} action(s): {violation.detail}"
        )
        return 0
    cfg = ModelConfig(
        n_agents=args.agents, n_jobs=args.jobs,
        max_duplications=args.duplications, max_deaths=args.deaths,
        max_crashes=args.crashes,
    )
    result = check_model(
        cfg, seams=seams, max_states=args.max_states,
        max_depth=args.max_depth,
    )
    print(format_result(result, seams), end="")
    if result.violation is not None and args.dump_fixture:
        dump_fixture(args.dump_fixture, result.violation, cfg, seams)
        log.info("violation fixture written to %s", args.dump_fixture)
    return 0 if result.ok else 1


def cmd_coordinator(args) -> int:
    """Run the native coordinator and serve REPL jobs over the cluster."""
    from dsort_tpu.runtime import NativeCoordinator
    from dsort_tpu.data.ingest import read_ints_file, write_ints_file

    cfg = _load_config(args)
    dtype = np.dtype(cfg.job.key_dtype)
    nworkers = args.workers or 4
    with NativeCoordinator(
        port=args.port if args.port is not None else cfg.server_port,
        heartbeat_timeout_s=cfg.job.heartbeat_timeout_s,
    ) as coord:
        log.info("coordinator listening on port %d", coord.port)
        coord.wait_workers(nworkers, timeout_s=args.join_timeout)
        log.info("%d workers joined", nworkers)
        journal = _open_journal(args)
        while True:
            try:
                line = input("Enter the filename to sort (or 'exit' to quit): ")
            except EOFError:
                return 0
            except KeyboardInterrupt:
                # server.c:51-59 parity: clean socket close on Ctrl-C — the
                # coordinator's context manager shuts the cluster down.
                print()
                return 0
            name = line.strip()
            if name == "exit" or not name:
                if name == "exit":
                    return 0
                continue
            try:
                data = read_ints_file(name, dtype=dtype)
                metrics = Metrics(journal=journal)
                out = coord.run_job(data, num_shards=nworkers, metrics=metrics)
                write_ints_file(args.output or cfg.output_path, out)
                log.info(
                    "sorted %d keys | live workers %d | reassignments %d",
                    len(data), coord.num_live, coord.reassignments,
                )
            except Exception as e:
                log.error("job failed: %s", e)
            finally:
                # Cumulative across REPL jobs, rewritten after each (same
                # discipline as `dsort serve`): the native cluster's fault
                # timeline lands on disk even when a job fails.
                _write_journal(journal, args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="dsort", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    def common(p, mode_default="spmd"):
        p.add_argument("--conf", help="KEY=value conf file (reference format accepted)")
        p.add_argument("--mode", default=mode_default,
                       choices=["spmd", "taskpool", "local"])
        p.add_argument("--workers", type=int)
        p.add_argument("--dtype")
        p.add_argument("--kernel", choices=["auto", "lax", "block", "bitonic", "pallas", "radix"])
        p.add_argument("--merge-kernel",
                       choices=["auto", "sort", "bitonic", "block_merge"],
                       help="post-shuffle combine (default auto: block_merge "
                            "wherever the block kernel applies)")
        p.add_argument("--exchange",
                       choices=["alltoall", "ring", "fused", "hier"],
                       help="bucket exchange schedule (default alltoall; "
                            "ring = chunked ppermute with adaptive per-step "
                            "headroom and merge-as-you-receive; fused = the "
                            "same measured ring schedule as ONE Pallas "
                            "kernel — in-kernel async remote DMAs, P-1 "
                            "dispatches collapsed to one launch; hier = the "
                            "two-level pod schedule: intra-host aggregation "
                            "then ONE merged DCN transfer per host pair, "
                            "ARCHITECTURE §17)")
        p.add_argument("--hier-hosts", type=int,
                       help="host count the hier schedule groups the worker "
                            "mesh into (default 0 = auto: the process count "
                            "when genuinely multi-host, else 2 simulated; "
                            "conf key HIER_HOSTS)")
        p.add_argument("--redundancy", type=int,
                       help="coded redundancy r (default 1 = off): the ring "
                            "exchange additionally ships every bucket to "
                            "its destination's r-1 ring successors, so up "
                            "to r-1 device losses recover by a LOCAL merge "
                            "of replica slots — zero keys re-sorted, zero "
                            "re-dispatch (ARCHITECTURE \u00a714; forces the "
                            "lax ring schedule; conf key REDUNDANCY)")
        p.add_argument("--redundancy-mode",
                       choices=["replicate", "parity"],
                       help="how r > 1 ships its premium (ARCHITECTURE "
                            "\u00a718): 'replicate' = full bucket copies, "
                            "(r-1)x extra wire bytes; 'parity' = XOR (r=2) "
                            "or RAID-6 P+Q GF(256) (r>=3) parity slots \u2014 "
                            "same local-merge recovery at ~1/P x the "
                            "premium (conf key REDUNDANCY_MODE)")
        p.add_argument("--checkpoint-dir",
                       help="persist per-shard/range progress here; a re-run "
                            "of the same input resumes instead of re-sorting")
        p.add_argument("--job-id",
                       help="checkpoint namespace (default: input basename)")
        p.add_argument("--journal",
                       help="write the job's structured event journal "
                            "(JSONL) here; render with `dsort report`")
        p.add_argument("--journal-rotate-mb", type=float,
                       help="rotate the journal to PATH.N at this size so "
                            "a long session never grows one unbounded "
                            "file; `dsort report` stitches the set back")
        p.add_argument("--tenant",
                       help="tenant label on this job's events and SLO "
                            "histograms (default 'default')")
        p.add_argument("--flight-dir",
                       help="fault flight recorder directory: any recovery "
                            "path dumps a postmortem bundle here "
                            "(ring + config + mesh state + counters)")
        p.add_argument("--no-autotune", action="store_true",
                       help="disable the closed-loop planner (obs.plan): no "
                            "measured-signal knob filling, no plan_decision "
                            "events — every knob rides its flag/conf/default "
                            "value exactly (conf AUTOTUNE=0; the planner is "
                            "otherwise ON for CLI runs, and explicit flags "
                            "always win over it either way)")
        p.add_argument("-o", "--output")

    p = sub.add_parser("run", help="sort one file")
    p.add_argument("input")
    p.add_argument("--profile-dir",
                   help="capture a jax.profiler trace of the job here")
    p.add_argument("--device-resident", action="store_true",
                   help="keep the sorted array on the mesh and validate it "
                        "on device (order + multiset checksum as jitted "
                        "reductions); the output file write is the only D2H")
    p.add_argument("--memwatch", action="store_true",
                   help="snapshot device memory at every phase boundary "
                        "into hbm_watermark journal events (obs.prof)")
    common(p)
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser("serve", help="interactive job loop (reference REPL, "
                                     "served by the async multi-tenant core)")
    common(p)
    p.add_argument("--metrics-port", type=int,
                   help="expose the live telemetry endpoint on this port "
                        "(0 = ephemeral; Prometheus text at /metrics, "
                        "JSON at /json; view with `dsort top`)")
    p.add_argument("--max-in-flight", type=int, default=1,
                   help="REPL jobs in flight at once (default 1 = await "
                        "each job, the reference's blocking semantics; >1 "
                        "= async submit with concurrent mesh-slice packing)")
    p.add_argument("--prewarm", nargs="?", const="auto",
                   choices=("auto", "all"),
                   help="compile fused rungs at startup: 'auto' (the "
                        "default value) compiles the planner's predicted "
                        "rung x dtype set from recent admissions — full "
                        "ladder on a cold start; 'all' keeps the old "
                        "exhaustive ladder (conf SERVE_PREWARM=1|all)")
    p.add_argument("--slice-devices", type=int,
                   help="devices per small-job mesh sub-slice (default 1; "
                        "concurrent small jobs pack onto disjoint slices)")
    p.add_argument("--queue-limit", type=int,
                   help="admission control: max jobs queued service-wide")
    p.add_argument("--tenant-limit", type=int,
                   help="admission control: max queued+running jobs per "
                        "tenant")
    p.add_argument("--weights",
                   help="fair-scheduler tenant weights, e.g. acme=2,blue=1 "
                        "(unlisted tenants weigh 1)")
    p.add_argument("--slo-shed-ms", type=float,
                   help="admission shedding target: reject (verdict "
                        "'slo_shed') while a tenant's live p95 queue wait "
                        "exceeds this many ms with work still queued; "
                        "recovers automatically once the queue drains")
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser(
        "fleet-agent",
        help="fleet execution agent: serve this process's mesh to a "
             "`dsort fleet` controller (ARCHITECTURE §12)",
    )
    common(p)
    p.add_argument("--host", default="127.0.0.1",
                   help="bind address for the fleet protocol endpoint")
    p.add_argument("--port", type=int, default=0,
                   help="fleet protocol port (0 = ephemeral, printed at "
                        "startup)")
    p.add_argument("--agent-id",
                   help="stable agent identity (default: generated); a "
                        "restarted agent keeps its routing identity by "
                        "reusing the id")
    p.add_argument("--metrics-port", type=int,
                   help="expose this mesh's live telemetry endpoint "
                        "(render the whole fleet with `dsort top URL...`)")
    p.add_argument("--prewarm", nargs="?", const="auto",
                   choices=("auto", "all"),
                   help="compile fused rungs at startup, advertised to the "
                        "controller for locality routing: 'auto' = the "
                        "planner's predicted set, 'all' = the exhaustive "
                        "ladder")
    p.add_argument("--slice-devices", type=int,
                   help="devices per small-job mesh sub-slice")
    p.add_argument("--queue-limit", type=int,
                   help="this agent's local queue bound")
    p.add_argument("--tenant-limit", type=int,
                   help="this agent's local per-tenant bound")
    p.add_argument("--weights", help=argparse.SUPPRESS)
    p.add_argument("--slo-shed-ms", type=float, help=argparse.SUPPRESS)
    p.add_argument("--max-in-flight", type=int, help=argparse.SUPPRESS)
    p.set_defaults(fn=cmd_fleet_agent)

    p = sub.add_parser(
        "fleet",
        help="fleet controller REPL: route jobs over many mesh-owning "
             "agents; restart-safe (ARCHITECTURE §12)",
    )
    common(p)
    p.add_argument("--agents",
                   help="agent endpoints host:port,host:port (conf "
                        "FLEET_AGENTS)")
    p.add_argument("--state-dir",
                   help="persist the control-plane state here so a "
                        "controller restart loses no job (conf "
                        "FLEET_STATE_DIR)")
    p.add_argument("--routing", choices=["locality", "random", "health"],
                   help="variant-cache-locality routing (default), the "
                        "random A/B baseline, or health — locality for "
                        "small jobs plus live straggler-penalized big-job "
                        "placement from the streamed telemetry verdicts "
                        "(conf FLEET_ROUTING)")
    p.add_argument("--no-health-telemetry", action="store_true",
                   help="heartbeats only: do not opt agents into the "
                        "health plane's bounded delta stream (conf "
                        "FLEET_TELEMETRY=0)")
    p.add_argument("--dispatch-timeout", type=float,
                   help="per-agent send deadline in seconds: a stuck-but-"
                        "connected agent fails over after this long "
                        "(conf FLEET_DISPATCH_TIMEOUT_S; default: the "
                        "request timeout)")
    p.add_argument("--metrics-port", type=int,
                   help="expose the controller's telemetry endpoint")
    p.add_argument("--max-in-flight", type=int, default=1,
                   help="REPL jobs in flight at once (like `dsort serve`)")
    p.add_argument("--queue-limit", type=int,
                   help="admission control: max jobs queued fleet-wide")
    p.add_argument("--tenant-limit", type=int,
                   help="admission control: max queued+running jobs per "
                        "tenant")
    p.add_argument("--weights",
                   help="fair-scheduler tenant weights, e.g. acme=2,blue=1")
    p.add_argument("--slo-shed-ms", type=float,
                   help="admission shedding target (ms, per-tenant live "
                        "p95 queue wait)")
    p.set_defaults(fn=cmd_fleet)

    p = sub.add_parser(
        "batch", help="sort many files as ONE batched SPMD program (dp axis)"
    )
    p.add_argument("inputs", nargs="+")
    p.add_argument("--outdir", required=True)
    p.add_argument("--dp", type=int,
                   help="independent-jobs mesh axis size (default from conf)")
    common(p)
    p.set_defaults(fn=cmd_batch)

    p = sub.add_parser("gen", help="generate synthetic input files")
    p.add_argument("n", type=int)
    p.add_argument("-o", "--output", required=True)
    p.add_argument("--dist", default="uniform", choices=["uniform", "zipf", "terasort"])
    p.add_argument("--dtype", default="int32")
    p.add_argument("--zipf-a", type=float, default=1.3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--format", default="text", choices=["text", "bin"],
                   help="'bin' streams raw binary keys (external-sort input)")
    p.set_defaults(fn=cmd_gen)

    p = sub.add_parser("terasort", help="sort a binary 100-byte-record file")
    p.add_argument("input")
    p.add_argument("-o", "--output")
    p.add_argument("--workers", type=int, default=None)
    p.add_argument("--external", action="store_true",
                   help="out-of-core: spill sorted record runs, native merge")
    p.add_argument("--mesh", type=int,
                   help="external mode: run record waves over this many "
                        "devices (the wave pipeline; conf EXTERNAL_MESH)")
    p.add_argument("--run-recs", type=int, default=1 << 20,
                   help="records per spilled run / per wave (external mode)")
    p.add_argument("--exchange",
                   choices=["alltoall", "ring", "fused", "hier"],
                   help="bucket exchange schedule (conf key EXCHANGE; flag "
                        "wins).  In-core record sorts route it through the "
                        "kv exchange plane; external record waves run the "
                        "host-side exchange, where mesh schedules warn and "
                        "the knob is validated for conf parity")
    p.add_argument("--hier-hosts", type=int,
                   help="host grouping for --exchange hier (default 0 = "
                        "auto; conf HIER_HOSTS)")
    p.add_argument("--spill-dir")
    p.add_argument("--job-id", default="tera_external")
    p.add_argument("--no-resume", action="store_true",
                   help="discard checkpointed runs and start fresh")
    p.add_argument("--journal",
                   help="write the job's structured event journal (JSONL) "
                        "here; render with `dsort report`")
    p.add_argument("--journal-rotate-mb", type=float,
                   help="rotate the journal to PATH.N at this size")
    p.add_argument("--memwatch", action="store_true",
                   help="snapshot device memory at phase boundaries into "
                        "hbm_watermark journal events")
    p.add_argument("--conf", help="KEY=value conf file (EXTERNAL_* keys)")
    p.set_defaults(fn=cmd_terasort)

    p = sub.add_parser("external", help="out-of-core sort of a raw binary key file")
    p.add_argument("input")
    p.add_argument("-o", "--output", required=True)
    p.add_argument("--dtype", default="int32")
    p.add_argument("--kernel", choices=["auto", "lax", "block", "bitonic", "pallas", "radix"])
    p.add_argument("--run-elems", type=int, default=None,
                   help="keys per spilled run, single-device mode (conf "
                        "EXTERNAL_RUN_ELEMS; default %d)" % (1 << 22))
    p.add_argument("--mesh", type=int,
                   help="sort in mesh-parallel WAVES over this many devices "
                        "(the wave pipeline, ARCHITECTURE §10; conf "
                        "EXTERNAL_MESH)")
    p.add_argument("--wave-elems", type=int, default=None,
                   help="keys per wave — the per-wave device budget (conf "
                        "EXTERNAL_WAVE_ELEMS; default %d)" % (1 << 22))
    p.add_argument("--no-overlap", action="store_true",
                   help="disable the wave pipeline's spill/exchange overlap "
                        "(the A/B baseline)")
    p.add_argument("--exchange", choices=["ring", "fused", "hier"],
                   help="per-wave exchange schedule (wave mode; default "
                        "ring; fused = exchange+merge as one Pallas kernel "
                        "per wave; hier = the two-level pod schedule — "
                        "each wave aggregates per destination HOST before "
                        "the DCN leg, ARCHITECTURE §17)")
    p.add_argument("--hier-hosts", type=int,
                   help="host grouping for --exchange hier (default 0 = "
                        "auto; conf HIER_HOSTS)")
    p.add_argument("--redundancy", type=int,
                   help="coded redundancy r for each wave's exchange "
                        "(default 1 = off): a device lost mid-wave repairs "
                        "from replica slots instead of a host re-sort — "
                        "wave_runs_resorted stays 0 (ARCHITECTURE §14)")
    p.add_argument("--redundancy-mode",
                   choices=["replicate", "parity"],
                   help="replica plane mode for coded waves: full copies "
                        "or XOR/P+Q parity slots (ARCHITECTURE §18)")
    p.add_argument("--spill-dir")
    p.add_argument("--job-id", default="external")
    p.add_argument("--no-resume", action="store_true",
                   help="discard checkpointed runs and start fresh")
    p.add_argument("--journal",
                   help="write the job's structured event journal (JSONL) "
                        "here; render with `dsort report` (--analyze shows "
                        "the wave waterfall)")
    p.add_argument("--journal-rotate-mb", type=float,
                   help="rotate the journal to PATH.N at this size")
    p.add_argument("--memwatch", action="store_true",
                   help="snapshot device memory at phase boundaries into "
                        "hbm_watermark journal events")
    p.add_argument("--conf", help="KEY=value conf file (EXTERNAL_* keys)")
    p.set_defaults(fn=cmd_external)

    p = sub.add_parser(
        "validate", help="validate a sort output (order + permutation checksum)"
    )
    p.add_argument("input")
    p.add_argument("--against", help="original input file to prove permutation")
    p.add_argument("--terasort", action="store_true",
                   help="treat files as binary 100-byte-record TeraSort data")
    p.add_argument("--binary", action="store_true",
                   help="treat files as raw binary key arrays (streamed)")
    p.add_argument("--dtype", default="int32")
    p.set_defaults(fn=cmd_validate)

    p = sub.add_parser(
        "report", help="render event journal(s) (timeline + phases/counters)"
    )
    p.add_argument("journal", nargs="+",
                   help="journal JSONL(s) from `--journal`; several merge "
                        "into one clock-aligned fleet timeline; a "
                        "directory or glob expands to the journals inside "
                        "(fleet runs write one per agent)")
    p.add_argument("--merge", action="store_true",
                   help="merge the journals into one aligned trace "
                        "(implied when more than one is given)")
    p.add_argument("--chrome-trace",
                   help="also export a Perfetto trace_event JSON here "
                        "(one pid per source journal, one tid per job)")
    p.add_argument("--analyze", action="store_true",
                   help="replay the journal(s) into a why-slow verdict: "
                        "phase waterfall + cross-process critical path, "
                        "straggler attribution, queue/compile/execute "
                        "split, wire bytes, skew, HBM watermarks")
    p.add_argument("--analyze-json",
                   help="also write the machine-readable verdict JSON here")
    p.add_argument("--link-mbps", type=float,
                   help="measured link bandwidth (Mbit/s): prices the "
                        "journal's wire bytes into expected seconds in "
                        "the --analyze verdict")
    p.add_argument("--conform", action="store_true",
                   help="replay the journal(s) against the declared "
                        "TRACE_CONTRACTS grammars (ARCHITECTURE §16) and "
                        "exit 1 on any violated contract")
    p.set_defaults(fn=cmd_report)

    p = sub.add_parser(
        "top", help="console view of running metrics endpoint(s) "
                    "(several URLs = the per-mesh fleet view)"
    )
    p.add_argument("url", nargs="*",
                   help="metrics endpoint URL(s) (default "
                        "http://127.0.0.1:9100/metrics; several render the "
                        "fleet view with combined admissions/cache tables)")
    p.add_argument("--interval", type=float,
                   help="refresh every N seconds (default: one-shot)")
    p.add_argument("--count", type=int,
                   help="stop after N refreshes (with --interval)")
    p.set_defaults(fn=cmd_top)

    p = sub.add_parser(
        "lint",
        help="project-native static analysis (registry/concurrency/tracing "
             "invariants; see ARCHITECTURE.md)",
    )
    p.add_argument("paths", nargs="*",
                   help="files/directories to check (default: dsort_tpu/)")
    p.add_argument("--format", default="text",
                   choices=["text", "json", "sarif"],
                   help="output format (sarif: SARIF 2.1.0 for "
                        "code-scanning upload)")
    p.add_argument("--stats", action="store_true",
                   help="print a per-checker wall-time/findings table "
                        "(file vs project phase) to stderr")
    p.add_argument("--baseline",
                   help="baseline JSON path (default from [tool.dsort.lint])")
    p.add_argument("--write-baseline", action="store_true",
                   help="record the current findings as tolerated (the "
                        "shipped tree keeps this file empty)")
    p.add_argument("--changed", action="store_true",
                   help="lint only files changed vs git HEAD (plus "
                        "untracked) — the interactive pre-commit scope")
    p.add_argument("--no-cache", action="store_true",
                   help="skip the content-hash result cache "
                        "(.lint-cache.json)")
    p.add_argument("--root",
                   help="project root (default: nearest pyproject.toml)")
    p.set_defaults(fn=cmd_lint)

    p = sub.add_parser(
        "spec",
        help="protocol spec plane: explicit-state model check of the fleet "
             "protocol, or replay of a violation fixture (ARCHITECTURE §16)",
    )
    p.add_argument("action", choices=["check", "replay"],
                   help="check: explore bounded interleavings against the "
                        "invariant catalog; replay: re-execute a dumped "
                        "violation fixture deterministically")
    p.add_argument("--fixture", help="fixture JSON for `replay`")
    p.add_argument("--agents", type=int, default=2,
                   help="modeled fleet size (default 2)")
    p.add_argument("--jobs", type=int, default=3,
                   help="jobs submitted in the model (default 3)")
    p.add_argument("--duplications", type=int, default=1,
                   help="frame retransmission budget (default 1)")
    p.add_argument("--deaths", type=int, default=1,
                   help="link-death budget (default 1)")
    p.add_argument("--crashes", type=int, default=1,
                   help="controller crash+restore budget (default 1)")
    p.add_argument("--max-states", type=int, default=12_000,
                   help="distinct-state exploration bound (default 12000 — "
                        "the make spec-smoke bound)")
    p.add_argument("--max-depth", type=int, default=40,
                   help="schedule depth bound (default 40)")
    p.add_argument("--seam", action="append",
                   choices=["ack_before_persist", "nonatomic_reserve"],
                   help="re-introduce a known-bad mutation (test seam); "
                        "repeatable — the checker must find a violation")
    p.add_argument("--dump-fixture",
                   help="write the minimized violating schedule as a "
                        "replayable JSON fixture here")
    p.set_defaults(fn=cmd_spec)

    p = sub.add_parser("coordinator", help="native TCP coordinator + job REPL")
    common(p)  # provides --workers (cluster size; default 4 below)
    p.add_argument("--port", type=int)
    p.add_argument("--join-timeout", type=float, default=60.0)
    p.set_defaults(fn=cmd_coordinator)

    p = sub.add_parser("worker", help="worker shim (joins a coordinator)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9008)
    p.add_argument("--conf")
    p.add_argument("--dtype", default="int32")
    p.add_argument("--backend", choices=["jax", "numpy"], default="jax")
    p.add_argument("--kernel", default="auto",
                   choices=["auto", "lax", "block", "bitonic", "pallas", "radix"])
    p.set_defaults(fn=None)

    args = ap.parse_args(argv)
    if args.cmd not in ("lint", "spec"):
        # 64-bit keys (int64/uint64 — BASELINE config #3, TeraSort prefixes)
        # need x64 mode before any backend use; the library is tested under
        # x64 (tests/conftest.py), so enable it for every execution command.
        # Routed through the compat shim (the one allowed call site — the
        # analysis suite's DS501 enforces this); `lint` and `spec` skip
        # the toggle so static analysis and model checking never
        # initialize a backend.
        from dsort_tpu.utils.backend import use_compile_cache
        from dsort_tpu.utils.compat import set_x64

        set_x64(True)
        use_compile_cache()
    if args.cmd == "worker":
        from dsort_tpu.runtime.worker import main as worker_main

        wargs = ["--host", args.host, "--port", str(args.port),
                 "--dtype", args.dtype, "--backend", args.backend,
                 "--kernel", args.kernel]
        if args.conf:
            wargs += ["--conf", args.conf]
        return worker_main(wargs)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
