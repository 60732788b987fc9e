"""Per-phase timers and throughput metrics (SURVEY.md §5.5 upgrade).

The reference has no timers at all — not even elapsed time per job.  This
module provides the phase breakdown (ingest / partition / local sort /
shuffle / merge / egress) and the north-star keys/sec/chip metric from
BASELINE.json.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from collections import defaultdict

#: Process-wide job ordinals: one `Metrics` instance == one logical job, so
#: the first event a Metrics emits claims the next ordinal and every event
#: of that job carries it as the ``job`` field.  This is what lets the
#: Chrome-trace exporter give concurrent jobs distinct lanes and the SLO
#: tracker attribute stage boundaries to the right job in an interleaved
#: journal.  ``itertools.count`` is atomic under the GIL.
_JOB_ORDINALS = itertools.count(1)


@dataclasses.dataclass
class Metrics:
    """Accumulated per-phase wall times and counters for one job.

    Lock-protected: taskpool shard handlers and (rarely) an abandoned SPMD
    attempt overlapping its successor can bump the same instance from
    multiple threads, and dict read-modify-write is not atomic.
    """

    phase_s: dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float)
    )
    counters: dict[str, int] = dataclasses.field(
        default_factory=lambda: defaultdict(int)
    )
    #: Optional `utils.events.EventLog`: when attached, every site that
    #: already threads a Metrics can journal typed events via `event` —
    #: the one hook that reaches all execution modes without new plumbing.
    journal: object | None = dataclasses.field(
        default=None, repr=False, compare=False
    )
    #: Live event taps (`dsort_tpu.obs`): objects with
    #: ``observe(etype, fields, mono, metrics)``, called synchronously on
    #: every `event` — the hook the telemetry registry and the fault flight
    #: recorder ride WITHOUT needing a journal attached.  Taps must never
    #: raise into the job (they are diagnostics).
    taps: list = dataclasses.field(
        default_factory=list, repr=False, compare=False
    )
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False
    )
    _job_ord: int | None = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def add(self, phase: str, seconds: float) -> None:
        with self._lock:
            self.phase_s[phase] += seconds

    def bump(self, counter: str, by: int = 1) -> None:
        with self._lock:
            self.counters[counter] += by

    def event(self, etype: str, **fields) -> None:
        """Emit a journal event and fan it out to the live taps.

        A no-op when neither a journal nor a tap is attached.  Every event
        is stamped with this instance's ``job`` ordinal (`_JOB_ORDINALS`);
        taps receive the journal's monotonic stamp so live consumers (the
        SLO histograms) and post-hoc journal analysis derive IDENTICAL
        durations.
        """
        if self.journal is None and not self.taps:
            return
        fields.setdefault("job", self._job_ordinal())
        mono = None
        if self.journal is not None:
            mono = self.journal.emit(etype, **fields).mono
        if self.taps:
            if mono is None:
                mono = time.monotonic()
            for tap in list(self.taps):
                tap.observe(etype, dict(fields), mono, self)

    def _job_ordinal(self) -> int:
        with self._lock:
            if self._job_ord is None:
                self._job_ord = next(_JOB_ORDINALS)
            return self._job_ord

    def total_s(self) -> float:
        """Seconds of the top-level phases; a ``<phase>.<step>`` key is a
        part of its phase and is not counted twice."""
        return sum(v for k, v in self.phase_s.items() if "." not in k)

    def keys_per_sec(self, n_keys: int) -> float:
        t = self.total_s()
        return n_keys / t if t > 0 else float("inf")

    def keys_per_sec_per_chip(self, n_keys: int, n_chips: int) -> float:
        return self.keys_per_sec(n_keys) / max(n_chips, 1)

    def summary(self) -> dict:
        return {
            "phases_ms": {k: round(v * 1e3, 3) for k, v in self.phase_s.items()},
            "counters": dict(self.counters),
            "total_ms": round(self.total_s() * 1e3, 3),
        }


class PhaseTimer:
    """Context-manager timer feeding a `Metrics` object.

    Each phase is also emitted as a ``jax.profiler`` trace annotation
    (`utils.tracing.annotate`), so when a profile capture is active
    (``dsort run --profile-dir`` / `tracing.profile_trace`) the host-side
    phases line up against device ops in the TensorBoard/Perfetto timeline.

    A dotted name ``<phase>.<step>`` (or `step` inside an open phase) times
    one step of a phase: its seconds land under that key and its annotation
    is ``dsort:<phase>.<step>``, but it journals no ``phase_start`` /
    ``phase_end``, so every event-fed consumer (the journal, ``dsort
    report``, the ``/metrics`` phase series, health) sees only the phases.
    A step entered several times in one phase (once per shard) sums.
    """

    def __init__(self, metrics: Metrics):
        self.metrics = metrics
        self._open: list[str] = []  # the top-level phases entered, innermost last

    @contextlib.contextmanager
    def phase(self, name: str):
        from dsort_tpu.utils.tracing import annotate

        top = "." not in name
        if top:
            self.metrics.event("phase_start", phase=name)
            self._open.append(name)
        t0 = time.perf_counter()
        try:
            with annotate(f"dsort:{name}"):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.metrics.add(name, dt)
            if top:
                self._open.pop()
                self.metrics.event("phase_end", phase=name, seconds=round(dt, 6))

    def step(self, step: str):
        """Time ``step`` of the innermost open phase as ``<phase>.<step>``."""
        if not self._open:
            raise RuntimeError(f"step {step!r} outside any phase")
        return self.phase(f"{self._open[-1]}.{step}")
