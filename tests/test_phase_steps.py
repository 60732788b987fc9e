"""Steps inside a phase: `PhaseTimer` records ``<phase>.<step>`` keys and
``dsort:<phase>.<step>`` annotations beside the phases, without touching
what the journal, the phase series or ``total_s`` report; and the shard
bodies name their device stages with `jax.named_scope`."""

import gc
import re

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dsort_tpu.parallel import SampleSort, local_device_mesh
from dsort_tpu.utils.events import EventLog
from dsort_tpu.utils.metrics import Metrics, PhaseTimer

UPLOAD = {"partition.pad", "partition.put", "spmd_sort.h2d", "spmd_sort.program"}
LANDING = {"assemble.d2h", "assemble.land"}


@pytest.fixture
def annotations(monkeypatch):
    import dsort_tpu.utils.tracing as tracing

    seen, real = [], tracing.annotate

    def recording(name):
        seen.append(name)
        return real(name)

    monkeypatch.setattr(tracing, "annotate", recording)
    return seen


@pytest.mark.parametrize("form", ["step", "dotted"])
def test_step_records_its_dotted_key_and_annotation(form, annotations):
    m = Metrics()
    t = PhaseTimer(m)
    with t.phase("partition"):
        with (t.step("pad") if form == "step" else t.phase("partition.pad")):
            pass
        with t.step("pad"):  # a step entered again sums
            pass
    assert set(m.phase_s) == {"partition", "partition.pad"}
    assert 0 <= m.phase_s["partition.pad"] <= m.phase_s["partition"]
    assert annotations == ["dsort:partition", "dsort:partition.pad", "dsort:partition.pad"]


def test_step_journals_nothing_and_total_leaves_it_out():
    log = EventLog()
    m = Metrics(journal=log)
    t = PhaseTimer(m)
    with t.phase("assemble"):
        with t.step("d2h"):
            pass
        with t.step("land"):
            pass
    assert log.types() == ["phase_start", "phase_end"]
    assert {e.fields["phase"] for e in log.events()} == {"assemble"}
    assert m.total_s() == m.phase_s["assemble"]
    assert m.summary()["total_ms"] == round(m.phase_s["assemble"] * 1e3, 3)


def test_step_outside_a_phase_raises():
    with pytest.raises(RuntimeError, match="outside any phase"):
        with PhaseTimer(Metrics()).step("pad"):
            pass


def _keys(n=50_000, seed=0):
    return np.random.default_rng(seed).integers(0, 10**6, n).astype(np.int32)


def _run(kind, ss, x, m):
    if kind in ("sort", "ring"):
        exchange = "ring" if kind == "ring" else None
        np.testing.assert_array_equal(ss.sort(x, metrics=m, exchange=exchange), np.sort(x))
    elif kind == "resident":
        h = ss.sort(x, metrics=m, keep_on_device=True)
        np.testing.assert_array_equal(h.to_host(), np.sort(x))
    else:
        keys = (x // 4).astype(np.uint64)
        sec = (x % 4).astype(np.int32)
        payload = np.broadcast_to(x.astype(np.uint8)[:, None], (len(x), 12)).copy()
        ok, ov = ss.sort_kv(keys, payload, metrics=m, secondary=sec)
        order = np.lexsort((sec, keys))
        np.testing.assert_array_equal(ok, keys[order])
        np.testing.assert_array_equal(ov[:, 0], payload[order, 0])


def _without_gc(fn, *args):
    """A collection between a phase and its step is not the program's time."""
    gc.collect()
    gc.disable()
    try:
        return fn(*args)
    finally:
        gc.enable()


def _children_sum_to_parents(phase_s):
    for parent in {k for k in phase_s if "." not in k}:
        kids = [v for k, v in phase_s.items() if k.startswith(parent + ".")]
        assert kids, parent
        assert abs(phase_s[parent] - sum(kids)) <= 0.05 * phase_s[parent] + 1e-3, (
            parent, phase_s)


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("kind,want", [
    ("sort", UPLOAD | LANDING),
    ("ring", UPLOAD | LANDING),
    ("resident", UPLOAD),
    ("kv_secondary", UPLOAD | LANDING),
])
def test_a_sort_records_exactly_its_steps(devices, kind, want):
    ss = SampleSort(local_device_mesh(devices))
    x = _keys(200_000)
    _run(kind, ss, x, Metrics())  # compile outside the timed job
    m = Metrics()
    _without_gc(_run, kind, ss, x, m)
    steps = {k for k in m.phase_s if "." in k}
    assert steps == want
    assert {k for k in m.phase_s if "." not in k} == {s.split(".")[0] for s in want}
    _children_sum_to_parents(m.phase_s)


def test_a_capacity_retry_adds_its_upload_to_the_same_steps():
    from dsort_tpu.config import JobConfig

    data = np.full(40_000, 7, np.int32)
    m = Metrics()
    ss = SampleSort(local_device_mesh(8), JobConfig(capacity_factor=1.0))
    out = _without_gc(ss.sort, data, m)
    np.testing.assert_array_equal(out, data)
    assert m.counters["capacity_retries"] >= 1
    assert {k for k in m.phase_s if "." in k} == UPLOAD | LANDING
    _children_sum_to_parents(m.phase_s)


def _inputs(kind, n):
    x = _keys(n, seed=n)
    if kind == "retry":
        return (np.full(n, 7, np.int32),)
    if kind != "kv_secondary":
        return (x,)
    payload = np.broadcast_to(x.astype(np.uint8)[:, None], (n, 12)).copy()
    return (x // 4).astype(np.uint64), payload, (x % 4).astype(np.int32)


@pytest.mark.parametrize("kind,n,exact", [
    (kind, n, exact)
    for kind in ("sort", "resident", "kv_secondary")
    for n, exact in ((20_000, True), (20_004, False))
] + [("retry", 40_000, True)])
def test_full_shards_upload_a_view_of_the_input(kind, n, exact):
    """4 shards of 5000 (8 of 5000 for the retry) are the layout as they
    stand; 4 of 5001 pad to 5008 in a copy."""
    from dsort_tpu.config import JobConfig

    inp = _inputs(kind, n)
    before = [a.copy() for a in inp]
    m = Metrics()
    if kind == "retry":
        ss = SampleSort(local_device_mesh(8), JobConfig(capacity_factor=1.0))
    else:
        ss = SampleSort(local_device_mesh(4))
    if kind == "kv_secondary":
        keys, payload, sec = inp
        ok, ov = ss.sort_kv(keys, payload, metrics=m, secondary=sec)
        order = np.lexsort((sec, keys))
        np.testing.assert_array_equal(ok, keys[order])
        np.testing.assert_array_equal(ov, payload[order])
    else:
        (x,) = inp
        out = ss.sort(x, metrics=m, keep_on_device=kind == "resident")
        out = out.to_host() if kind == "resident" else out
        np.testing.assert_array_equal(out, np.sort(x))
    for a, b in zip(inp, before):
        np.testing.assert_array_equal(a, b)
    assert m.counters["partition_views"] == int(exact)
    assert "partition.pad" in m.phase_s
    if kind == "retry":
        assert m.counters["capacity_retries"] >= 1


def test_journal_phase_series_and_total_of_a_sort_are_the_phases():
    from dsort_tpu.obs.telemetry import Telemetry

    log, tel = EventLog(), Telemetry()
    m = Metrics(journal=log)
    tel.attach(m)
    ss = SampleSort(local_device_mesh(4))
    ss.sort(_keys(seed=1), metrics=m)
    phases = ["partition", "spmd_sort", "assemble"]
    assert [(e.type, e.fields["phase"]) for e in log.events()
            if e.type.startswith("phase_")] == [
        (t, p) for p in phases for t in ("phase_start", "phase_end")]
    series = re.findall(r'dsort_phase_seconds_total\{phase="([^"]+)"\}', tel.render_prometheus())
    assert sorted(series) == sorted(phases)
    assert m.total_s() == pytest.approx(sum(m.phase_s[p] for p in phases))


def _compiled_op_names(kind):
    ss = SampleSort(local_device_mesh(4))
    sh = NamedSharding(ss.mesh, P(ss.axis))
    n_local = 256
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)  # noqa: E731
    counts = spec((4,), np.int32)
    if kind == "keys":
        fn, args = ss._build(n_local, 128, None), [spec((4 * n_local,), np.int32), counts]
    else:
        sec = kind == "records_secondary"
        fn = ss._build(n_local, 128, (12,), sec)
        args = ([spec((4 * n_local,), np.uint64)]
                + ([spec((4 * n_local,), np.int32)] if sec else [])
                + [spec((4 * n_local, 12), np.uint8), counts])
    text = fn._fn.lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.mark.parametrize("kind", ["keys", "records", "records_secondary"])
def test_shard_body_stages_are_named_in_the_hlo(kind):
    stages = {"local_sort", "splitters", "pack", "exchange", "merge"}
    if kind != "keys":
        stages.add("payload_gather")
    names = _compiled_op_names(kind)
    found = {s for s in stages if any(re.search(rf"(^|/){s}/", n) for n in names)}
    assert found == stages
    # the send-buffer gather belongs to ``pack``
    assert any(re.search(r"(^|/)pack/.*gather", n) for n in names)
