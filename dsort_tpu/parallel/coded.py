"""Coded redundancy plane: survive device loss without re-running anything.

Every failure path in the tree — mesh re-form, handle invalidation,
mid-ring loss, slice eviction, mid-wave repair — recovers by *re-running
work on the survivors*, a measured 2.4x throughput hit under one injected
failure (``config5_zipf_1M_injected_failure_8dev_cpu_mesh``).  Coded
TeraSort (arXiv:1702.04850) shows the alternative this module implements:
during the ring exchange each device ALSO ships its outbound buckets to
its ``r-1`` ring successors (`exchange._coded_ring_exchange_shard`), so
when a device dies its successors already hold every bucket of its key
range as sorted replica slots.  Recovery is then a **local merge** of one
survivor's replica buffer — zero keys re-sorted, zero re-dispatch of the
plan phase — and the mesh-availability posture (arXiv:2011.03605) becomes
the default rather than a special mode.

The host-side contract lives here:

- `CodedExchangeState`: the post-exchange snapshot a coded dispatch
  attaches to the `WorkerFailure` it re-raises — survivors' merged ranges
  plus the replica buffers/lengths.  `reconstruct(dead)` rebuilds every
  dead position's range from a live holder's replica slots via the k-way
  run merge (`ops.merge.merge_sorted_host` — a merge of sorted runs, never
  a re-sort); `assemble(dead)` concatenates the ranges in splitter order
  into the full sorted output.
- `CodedBudgetExceeded`: raised when a dead range's every holder
  (``d+1 .. d+r-1``) is dead too — the caller journals
  ``coded_budget_exceeded`` and degrades cleanly to today's re-run path.
- `dead_positions`: maps a `WorkerFailure` (single ``worker`` or the
  aggregated ``workers`` list a multi-loss sweep attaches) onto mesh
  positions, through the scheduler's live-worker list when one applies.

Simulation note (same fidelity doctrine as the wave plane's in-flight
repair): replica placement completes WITH the exchange, so the drill's
injection point sits after the exchange dispatch — modelling a loss
discovered at the completion fetch.  On real hardware the per-step DMA
schedule places each replica alongside its primary shipment, so a loss
after step ``k`` leaves every range's slots ``<= k`` already placed; the
cpu-mesh drill exercises the post-placement recovery contract.
"""

from __future__ import annotations

import dataclasses
import threading

import numpy as np

#: SPMD-verifier contract (parsed, not imported — `dsort_tpu.analysis.spmd`).
#: This module is the coded exchange's HOST bookkeeping plane (claim
#: journals, recovery solves); issuing a mesh collective from here would be
#: a layering break, and the DS1202 host-plane rule makes it a lint error.
SPMD_CONTRACT = {"plane": "host"}

__all__ = [
    "CodedBudgetExceeded",
    "CodedExchangeState",
    "StragglerClaim",
    "dead_positions",
    "journal_recovery",
    "snapshot_state",
    "snapshot_parity_state",
    "snapshot_kv_state",
    "snapshot_parity_kv_state",
]


# -- GF(256) arithmetic (polynomial 0x11D, generator g = 2) -----------------
#
# The host half of the parity plane: the device folds out-bucket byte rows
# into XOR (RAID P) and Horner ``sum g^k d_k`` (RAID Q) slots
# (`exchange._parity_fold`); these tables solve the resulting one- or
# two-erasure systems.  255-periodic exponents bound the plane to meshes
# whose two unknown bucket indices never coincide mod 255 — the solver
# degrades to the budget-exceeded path on the (P > 255) collision rather
# than dividing by zero.

_GF_EXP = np.zeros(510, np.uint8)
_GF_LOG = np.zeros(256, np.int32)
_x = 1
for _i in range(255):
    _GF_EXP[_i] = _x
    _GF_LOG[_x] = _i
    _x <<= 1
    if _x & 0x100:
        _x ^= 0x11D
_GF_EXP[255:510] = _GF_EXP[:255]
del _x, _i


def _gf_scale(row: np.ndarray, c: int) -> np.ndarray:
    """Multiply a uint8 byte row by the GF(256) scalar ``c``."""
    if c == 0:
        return np.zeros_like(row)
    if c == 1:
        return row.copy()
    out = np.zeros_like(row)
    nz = row != 0
    out[nz] = _GF_EXP[_GF_LOG[row[nz]] + _GF_LOG[c]]
    return out


def _parity_solve(known_rows: dict, parity: list, unknowns: list) -> dict:
    """Solve one parity group's erasures in byte space.

    ``known_rows`` maps bucket index -> uint8 row, ``parity`` is the
    group's ``[P, Q?]`` planes, ``unknowns`` the (<= 2) missing bucket
    indices.  One unknown needs only the XOR fold; two eliminate through
    Q: with ``P' = P ^ xor(known)`` and ``Q' = Q ^ sum g^k known_k``,
    ``a = (Q' ^ g^j P') / (g^i ^ g^j)`` and ``b = P' ^ a``.
    """
    pprime = parity[0].copy()
    for r in known_rows.values():
        pprime ^= r
    if len(unknowns) == 1:
        return {unknowns[0]: pprime}
    i, j = unknowns
    qprime = parity[1].copy()
    for k, r in known_rows.items():
        qprime ^= _gf_scale(r, int(_GF_EXP[k % 255]))
    gi, gj = int(_GF_EXP[i % 255]), int(_GF_EXP[j % 255])
    denom = gi ^ gj
    inv = int(_GF_EXP[255 - _GF_LOG[denom]])
    a = _gf_scale(qprime ^ _gf_scale(pprime, gj), inv)
    return {i: a, j: pprime ^ a}


def _host_sentinel(dtype):
    """Host twin of `ops.local_sort.sentinel_for` (numpy scalar)."""
    dtype = np.dtype(dtype)
    if np.issubdtype(dtype, np.floating):
        return np.array(np.inf, dtype)
    return np.array(np.iinfo(dtype).max, dtype)


def _byte_row(run: np.ndarray, cap: int, pad) -> np.ndarray:
    """One bucket run extended to ``cap`` slots with ``pad``, viewed as its
    raw byte vector — the host twin of `exchange._byte_plane` (same
    platform, same byte order)."""
    full = np.full((cap,) + run.shape[1:], pad, run.dtype)
    full[: len(run)] = run
    return np.ascontiguousarray(full).view(np.uint8).reshape(-1)


class StragglerClaim:
    """Exactly-once claim for one straggler-served range.

    The owner-fetch and reconstruction legs race; whichever calls
    `claim` first owns the range, the loser's result is discarded.  The
    decision is a single compare-and-set under one lock — the journal
    grammar (``straggler_serve`` in `analysis.spec.contracts`) pins that
    at most one of the two legs journals a serve.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._winner: str | None = None

    def claim(self, who: str) -> bool:
        with self._lock:
            if self._winner is None:
                self._winner = who
                return True
            return False

    @property
    def winner(self) -> str | None:
        with self._lock:
            return self._winner


class CodedBudgetExceeded(RuntimeError):
    """Losses exceed what the replica plane covers: some dead range's every
    holder is dead too.  The caller journals ``coded_budget_exceeded`` and
    falls back to the re-run recovery path — bit-identical output, just at
    the old re-run cost."""

    def __init__(self, dead, redundancy: int):
        self.dead = sorted(int(d) for d in dead)
        self.redundancy = int(redundancy)
        super().__init__(
            f"coded redundancy budget exceeded: positions {self.dead} dead "
            f"at redundancy={self.redundancy} (a lost range's every ring "
            "successor holding its replica is dead too)"
        )


def dead_positions(exc, live=None) -> list[int]:
    """Mesh positions a `WorkerFailure` names.

    ``exc.workers`` (the aggregated list a multi-loss injector sweep
    attaches) wins over the single ``exc.worker``.  With ``live`` — the
    scheduler's live-worker index list the failed attempt ran on — worker
    ids map to their mesh position (``live.index``); without it the ids ARE
    positions (a bare `SampleSort` knows only mesh positions).
    """
    workers = list(getattr(exc, "workers", None) or [exc.worker])
    if live is None:
        return [int(w) for w in workers]
    return [live.index(w) for w in workers if w in live]


def journal_recovery(metrics, state, dead, assemble: bool = True, **extra):
    """Run one reconstruction under the §14 journal contract.

    THE coded-recovery accounting, shared by every consumer (the SPMD
    scheduler, the wave pipeline, serve's eviction completion) so the
    ``coded_recover`` field set and the budget-fallback journaling can
    never drift between them: on success returns ``(result, info)`` —
    ``assemble=True`` yields the full sorted output, ``False`` the
    per-position range list — after bumping
    ``coded_recoveries``/``coded_recovered_keys`` and emitting one
    ``coded_recover`` (replicate mode) or ``parity_recover`` (parity
    mode) event (dead, holders, recovered_keys, replica_bytes,
    redundancy, mode, measured ``wall_s``, plus any ``extra`` fields the
    caller scopes it with).  On `CodedBudgetExceeded` journals
    ``coded_budget_exceeded`` and returns None — the caller degrades to
    its re-run path.
    """
    import time

    t0 = time.monotonic()
    try:
        op = state.assemble if assemble else state.reconstruct
        result, info = op(dead)
    except CodedBudgetExceeded as b:
        metrics.event(
            "coded_budget_exceeded", dead=b.dead, redundancy=b.redundancy,
            **extra,
        )
        return None
    mode = getattr(state, "mode", "replicate")
    metrics.bump("coded_recoveries")
    metrics.bump("coded_recovered_keys", info["recovered_keys"])
    metrics.event(
        "parity_recover" if mode == "parity" else "coded_recover",
        dead=sorted(int(d) % state.num_workers for d in dead),
        holders=info["holders"],
        recovered_keys=info["recovered_keys"],
        replica_bytes=info["replica_bytes"],
        redundancy=state.redundancy,
        mode=mode,
        wall_s=round(time.monotonic() - t0, 6),
        **extra,
    )
    return result, info


def snapshot_state(
    num_workers: int, redundancy: int, caps, n: int,
    merged, out_counts, overflow, reps, rep_lens,
) -> "CodedExchangeState":
    """Host snapshot of one coded exchange's device outputs.

    THE fetch shared by every coded dispatch (`SampleSort`, the wave
    pipeline): survivors' merged ranges (valid-trimmed) plus the replica
    plane.  The overflow invariant is checked FIRST — an overflowed
    exchange ran against a different splitter plan than its caps and must
    raise, not hand a recovery path inconsistent buffers.
    """
    import jax

    from dsort_tpu.parallel.exchange import check_ring_overflow

    p = int(num_workers)
    c, ov, mh, reps_h, lens_h = jax.device_get(
        (out_counts, overflow, merged, reps, rep_lens)
    )
    check_ring_overflow(ov)
    c = np.asarray(c).reshape(-1)
    mh = np.asarray(mh).reshape(p, -1)
    return CodedExchangeState(
        num_workers=p,
        redundancy=int(redundancy),
        caps=tuple(int(x) for x in caps),
        n=int(n),
        ranges=[np.array(mh[i, : int(c[i])]) for i in range(p)],
        replicas=np.asarray(reps_h).reshape(p, int(redundancy) - 1, -1),
        replica_lens=np.asarray(lens_h).reshape(p, int(redundancy) - 1, p),
    )


def snapshot_parity_state(
    num_workers: int, redundancy: int, caps, n: int,
    merged, out_counts, overflow, sent, sent_lens, parity,
) -> "CodedExchangeState":
    """Host snapshot of one PARITY-coded exchange
    (`exchange._parity_ring_exchange_shard` outputs): survivors' merged
    ranges, every device's retained out-bucket plane + valid lengths, and
    the received GF(256) parity plane.  Availability doctrine: a dead
    sender's out-bucket row may be consumed only when its RECEIVER is
    live (the receiver holds the delivered copy — on real hardware the
    retained recv buffer, here the same values from the snapshot);
    `CodedExchangeState._reconstruct_parity` enforces exactly that rule,
    solving the remaining rows through the parity slots."""
    import jax

    from dsort_tpu.parallel.exchange import check_ring_overflow

    p = int(num_workers)
    c, ov, mh, sent_h, lens_h, par_h = jax.device_get(
        (out_counts, overflow, merged, sent, sent_lens, parity)
    )
    check_ring_overflow(ov)
    c = np.asarray(c).reshape(-1)
    mh = np.asarray(mh).reshape(p, -1)
    par = np.asarray(par_h)
    return CodedExchangeState(
        num_workers=p,
        redundancy=int(redundancy),
        caps=tuple(int(x) for x in caps),
        n=int(n),
        ranges=[np.array(mh[i, : int(c[i])]) for i in range(p)],
        mode="parity",
        sent=np.asarray(sent_h).reshape(p, -1),
        sent_lens=np.asarray(lens_h).reshape(p, p),
        parity=par.reshape(p, -1, par.shape[-1]),
    )


def snapshot_kv_state(
    num_workers: int, redundancy: int, caps, n: int,
    merged_k, merged_v, out_counts, overflow, reps_k, reps_v, rep_lens,
) -> "CodedExchangeState":
    """Host snapshot of one coded KV exchange
    (`exchange._coded_ring_exchange_kv_shard` outputs): the keys-mode
    snapshot plus the payload ranges and the payload replica plane."""
    import jax

    from dsort_tpu.parallel.exchange import check_ring_overflow

    p = int(num_workers)
    r1 = int(redundancy) - 1
    c, ov, mh, mv, rk, rv, lens_h = jax.device_get(
        (out_counts, overflow, merged_k, merged_v, reps_k, reps_v, rep_lens)
    )
    check_ring_overflow(ov)
    c = np.asarray(c).reshape(-1)
    mh = np.asarray(mh).reshape(p, -1)
    rv = np.asarray(rv)
    rv = rv.reshape((p, r1) + rv.shape[1:])
    mv = np.asarray(mv).reshape((p, -1) + rv.shape[3:])  # rows of a flat payload
    return CodedExchangeState(
        num_workers=p,
        redundancy=int(redundancy),
        caps=tuple(int(x) for x in caps),
        n=int(n),
        ranges=[np.array(mh[i, : int(c[i])]) for i in range(p)],
        replicas=np.asarray(rk).reshape(p, r1, -1),
        replica_lens=np.asarray(lens_h).reshape(p, r1, p),
        val_ranges=[np.array(mv[i, : int(c[i])]) for i in range(p)],
        val_replicas=rv,
    )


def snapshot_parity_kv_state(
    num_workers: int, redundancy: int, caps, n: int,
    merged_k, merged_v, out_counts, overflow,
    sent_k, sent_v, sent_lens, parity_k, parity_v,
) -> "CodedExchangeState":
    """Host snapshot of one PARITY-coded KV exchange
    (`exchange._parity_ring_exchange_kv_shard` outputs): the keys-parity
    snapshot plus the retained payload plane and its parity twin."""
    import jax

    from dsort_tpu.parallel.exchange import check_ring_overflow

    p = int(num_workers)
    c, ov, mh, mv, sk, sv, lens_h, pk, pv = jax.device_get(
        (out_counts, overflow, merged_k, merged_v, sent_k, sent_v,
         sent_lens, parity_k, parity_v)
    )
    check_ring_overflow(ov)
    c = np.asarray(c).reshape(-1)
    mh = np.asarray(mh).reshape(p, -1)
    sv = np.asarray(sv)
    sv = sv.reshape((p, sv.shape[0] // p) + sv.shape[1:])
    mv = np.asarray(mv).reshape((p, -1) + sv.shape[2:])  # rows of a flat payload
    pk = np.asarray(pk)
    pv = np.asarray(pv)
    return CodedExchangeState(
        num_workers=p,
        redundancy=int(redundancy),
        caps=tuple(int(x) for x in caps),
        n=int(n),
        ranges=[np.array(mh[i, : int(c[i])]) for i in range(p)],
        mode="parity",
        sent=np.asarray(sk).reshape(p, -1),
        sent_lens=np.asarray(lens_h).reshape(p, p),
        parity=pk.reshape(p, -1, pk.shape[-1]),
        val_ranges=[np.array(mv[i, : int(c[i])]) for i in range(p)],
        sent_vals=sv,
        parity_vals=pv.reshape(p, -1, pv.shape[-1]),
    )


@dataclasses.dataclass
class CodedExchangeState:
    """Everything the survivors hold after one coded exchange.

    ``ranges[i]`` is mesh position ``i``'s merged key range (valid-trimmed
    host copy).  Replicate mode: ``replicas[(h, j-1)]`` is holder ``h``'s
    replica buffer of predecessor ``h-j``'s range — ``P`` sorted
    sentinel-padded runs at the static caps-cumsum offsets — with
    ``replica_lens[(h, j-1)][k]`` the slot's valid length.  Parity mode:
    ``sent[s]`` is device ``s``'s retained out-bucket plane (slot ``k`` =
    its bucket toward range ``(s+k) % P``), ``sent_lens`` the ``(P, P)``
    valid lengths (the plan histogram re-ordered — host-measured before
    any loss), and ``parity[m, j]`` the parity slot ``j`` of group
    ``(m-1-j) % P`` device ``m`` received.  KV jobs carry the payload
    twins (``val_ranges`` / ``val_replicas`` / ``sent_vals`` /
    ``parity_vals``).  ``caps`` is the plan-measured per-step capacity
    tuple every plane was sized from.
    """

    num_workers: int
    redundancy: int
    caps: tuple
    n: int
    ranges: list
    replicas: np.ndarray | None = None       # (P, r-1, sum(caps))
    replica_lens: np.ndarray | None = None   # (P, r-1, P)
    mode: str = "replicate"
    sent: np.ndarray | None = None           # (P, sum(caps)) parity mode
    sent_lens: np.ndarray | None = None      # (P, P) parity mode
    parity: np.ndarray | None = None         # (P, npar, Lk) uint8
    val_ranges: list | None = None           # kv: per-position payload rows
    val_replicas: np.ndarray | None = None   # (P, r-1, sum(caps), *trailing)
    sent_vals: np.ndarray | None = None      # (P, sum(caps), *trailing)
    parity_vals: np.ndarray | None = None    # (P, npar, Lv) uint8

    @property
    def kv(self) -> bool:
        """Whether this snapshot covers a key+payload exchange."""
        return self.val_ranges is not None

    def _offsets(self) -> np.ndarray:
        return np.concatenate(
            [[0], np.cumsum(np.asarray(self.caps, np.int64))]
        )

    def holder_of(self, d: int, dead: set) -> tuple[int, int] | None:
        """The first LIVE ring successor holding range ``d``'s replica, as
        ``(holder, j)``; None when the budget is exceeded for ``d``."""
        for j in range(1, self.redundancy):
            h = (int(d) + j) % self.num_workers
            if h not in dead:
                return h, j
        return None

    def reconstruct(self, dead):
        """Rebuild every dead position's range locally.

        Returns ``(result, info)``: ``result`` is the per-position range
        list with dead entries REPLACED by their reconstruction — for a
        kv snapshot a ``(key_ranges, val_ranges)`` pair — and ``info``
        the accounting dict (``recovered_keys``, ``replica_bytes``,
        ``holders``) the caller journals.  Raises `CodedBudgetExceeded`
        when the losses exceed what the plane covers.  Both modes merge
        already-sorted runs — zero keys re-sorted.
        """
        p = self.num_workers
        dead_set = {int(d) % p for d in dead}
        if self.mode == "parity":
            return self._reconstruct_parity(dead_set)
        return self._reconstruct_replicate(dead_set)

    def _reconstruct_replicate(self, dead_set: set):
        from dsort_tpu.ops.merge import merge_sorted_host, merge_sorted_host_kv

        p = self.num_workers
        plan = {}
        for d in sorted(dead_set):
            hj = self.holder_of(d, dead_set)
            if hj is None:
                raise CodedBudgetExceeded(dead_set, self.redundancy)
            plan[d] = hj
        offsets = self._offsets()
        out = list(self.ranges)
        out_v = list(self.val_ranges) if self.kv else None
        recovered = 0
        replica_bytes = 0
        for d, (h, j) in plan.items():
            buf = np.asarray(self.replicas[h, j - 1])
            lens = np.asarray(self.replica_lens[h, j - 1])
            slots = [
                (int(offsets[k]), int(lens[k]))
                for k in range(p) if int(lens[k]) > 0
            ]
            runs = [np.asarray(buf[o: o + ln]) for o, ln in slots]
            replica_bytes += int(lens.sum()) * buf.dtype.itemsize
            if self.kv:
                vbuf = np.asarray(self.val_replicas[h, j - 1])
                vruns = [np.asarray(vbuf[o: o + ln]) for o, ln in slots]
                if runs:
                    rng, vrng = merge_sorted_host_kv(runs, vruns)
                else:
                    rng, vrng = buf[:0].copy(), vbuf[:0].copy()
                out_v[d] = vrng
                row_b = int(
                    np.prod(vbuf.shape[1:], dtype=np.int64)
                ) * vbuf.dtype.itemsize
                replica_bytes += int(lens.sum()) * row_b
            else:
                rng = merge_sorted_host(runs) if runs else buf[:0].copy()
            out[d] = rng
            recovered += len(rng)
        info = {
            "recovered_keys": int(recovered),
            "replica_bytes": int(replica_bytes),
            "holders": {int(d): int(h) for d, (h, _) in plan.items()},
        }
        return ((out, out_v) if self.kv else out), info

    def _parity_of(self, s: int, j: int) -> np.ndarray:
        """Parity slot ``j`` of group ``s`` — held by ring successor
        ``s+1+j`` (the ppermute shift the shard program shipped it at)."""
        return np.asarray(self.parity[(int(s) + 1 + j) % self.num_workers, j])

    def _parity_val_of(self, s: int, j: int) -> np.ndarray:
        return np.asarray(
            self.parity_vals[(int(s) + 1 + j) % self.num_workers, j]
        )

    def _reconstruct_parity(self, dead_set: set):
        """The parity-plane solve (coded exchange v2).

        Group ``s`` (dead sender ``s``'s out-bucket plane) has exactly
        ``|dead|`` unknown rows: row ``k`` is unavailable iff BOTH its
        sender ``s`` and its receiver ``(s+k) % P`` are dead (a live
        receiver retains the delivered copy; a live sender retains the
        out plane).  ``|dead| <= npar`` with every needed parity holder
        alive solves every group; anything beyond raises
        `CodedBudgetExceeded` and the caller degrades to re-run.
        """
        from dsort_tpu.ops.merge import merge_sorted_host, merge_sorted_host_kv

        p = self.num_workers
        npar = int(self.parity.shape[1])
        nd = len(dead_set)
        if nd > npar:
            raise CodedBudgetExceeded(dead_set, self.redundancy)
        offsets = self._offsets()
        cap_max = int(max(self.caps))
        kdt = self.sent.dtype
        pad = _host_sentinel(kdt)
        holders = {}
        unknown = {}
        for s in sorted(dead_set):
            ks = [k for k in range(p) if (s + k) % p in dead_set]
            hs = [(s + 1 + j) % p for j in range(nd)]
            if any(h in dead_set for h in hs):
                raise CodedBudgetExceeded(dead_set, self.redundancy)
            if len(ks) == 2 and (ks[1] - ks[0]) % 255 == 0:
                # g^i == g^j: the two-erasure system is singular (only
                # reachable past P=255) — degrade rather than divide by 0.
                raise CodedBudgetExceeded(dead_set, self.redundancy)
            unknown[s] = ks
            holders[s] = hs
        recovered_k: dict[tuple, np.ndarray] = {}
        recovered_v: dict[tuple, np.ndarray] = {}
        parity_bytes = 0
        for s, ks in unknown.items():
            known = {
                k: _byte_row(
                    self.sent[s, int(offsets[k]):
                              int(offsets[k]) + int(self.sent_lens[s, k])],
                    cap_max, pad,
                )
                for k in range(p) if k not in ks
            }
            planes = [self._parity_of(s, j) for j in range(len(ks))]
            parity_bytes += sum(pl.nbytes for pl in planes)
            for k, row in _parity_solve(known, planes, ks).items():
                ln = int(self.sent_lens[s, k])
                recovered_k[(s, k)] = np.array(row.view(kdt)[:ln])
            if self.kv:
                vdt = self.sent_vals.dtype
                trailing = self.sent_vals.shape[2:]
                vknown = {
                    k: _byte_row(
                        self.sent_vals[
                            s, int(offsets[k]):
                            int(offsets[k]) + int(self.sent_lens[s, k])
                        ],
                        cap_max, 0,
                    )
                    for k in range(p) if k not in ks
                }
                vplanes = [self._parity_val_of(s, j) for j in range(len(ks))]
                parity_bytes += sum(pl.nbytes for pl in vplanes)
                for k, row in _parity_solve(vknown, vplanes, ks).items():
                    ln = int(self.sent_lens[s, k])
                    recovered_v[(s, k)] = np.array(
                        row.view(vdt).reshape((cap_max,) + trailing)[:ln]
                    )
        out = list(self.ranges)
        out_v = list(self.val_ranges) if self.kv else None
        recovered = 0
        for d in sorted(dead_set):
            runs, vruns = [], []
            for s in range(p):
                k = (d - s) % p
                ln = int(self.sent_lens[s, k])
                if ln == 0:
                    continue
                if s in dead_set:
                    runs.append(recovered_k[(s, k)])
                    if self.kv:
                        vruns.append(recovered_v[(s, k)])
                else:
                    o = int(offsets[k])
                    runs.append(np.asarray(self.sent[s, o: o + ln]))
                    if self.kv:
                        vruns.append(np.asarray(self.sent_vals[s, o: o + ln]))
            if self.kv:
                if runs:
                    rng, vrng = merge_sorted_host_kv(runs, vruns)
                else:
                    rng = self.sent[0, :0].copy()
                    vrng = self.sent_vals[0, :0].copy()
                out_v[d] = vrng
            else:
                rng = (
                    merge_sorted_host(runs) if runs
                    else self.sent[0, :0].copy()
                )
            out[d] = rng
            recovered += len(rng)
        info = {
            "recovered_keys": int(recovered),
            "replica_bytes": int(parity_bytes),
            "holders": {int(s): [int(h) for h in hs]
                        for s, hs in holders.items()},
        }
        return ((out, out_v) if self.kv else out), info

    def assemble(self, dead):
        """The full sorted output with dead ranges reconstructed.

        Ranges concatenate in mesh-position order — position ``i`` owns the
        ``i``-th splitter interval, so the concatenation IS the sorted
        array (the `SampleSort._assemble_ranges` layout); a kv snapshot
        returns the ``(keys, payload)`` pair.  A count mismatch is raised
        loudly: reconstruction must be exactly lossless.
        """
        result, info = self.reconstruct(dead)
        ranges, vranges = result if self.kv else (result, None)
        out = (
            np.concatenate([np.asarray(r) for r in ranges])
            if ranges else np.zeros(0)
        )
        if len(out) != self.n:
            raise RuntimeError(
                f"coded reconstruction assembled {len(out)} of {self.n} "
                "keys; the redundancy plane is inconsistent with the plan"
            )
        if self.kv:
            return (out, np.concatenate(
                [np.asarray(v) for v in vranges], axis=0
            )), info
        return out, info
