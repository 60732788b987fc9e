"""Fleet-plane tests (ISSUE 12, ARCHITECTURE §12): the framed-JSON wire
protocol, the pure (backend-free, serializable) control plane, locality/
size routing over live agents, draining and agent-loss re-routing, the
typed ``no_capacity`` verdict, the controller-restart drill (zero jobs
lost or re-dispatched, journal-asserted), the fleet observability
satellites (`dsort top` multi-URL, `dsort report` directory/glob), and
a mixed three-tenant workload over two mesh-owning agents."""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from dsort_tpu.analysis.spec import assert_conformant
from dsort_tpu.fleet import proto
from dsort_tpu.fleet.agent import FleetAgent
from dsort_tpu.fleet.controller import FleetController
from dsort_tpu.obs.merge import expand_path_args, group_rotated, merge_records
from dsort_tpu.serve.admission import ADMISSION_REASONS, AdmissionController
from dsort_tpu.serve.policy import ControlPolicy
from dsort_tpu.utils.events import COUNTERS, EVENT_TYPES, EventLog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.usefixtures("fresh_compile_ledger")


def _sort_runner(data, metrics, job_id=None):
    metrics.event("job_done", n_keys=len(data), counters=dict(metrics.counters))
    return np.sort(data)


def _agents(*ids, runner=None, journals=None):
    out = []
    for i, aid in enumerate(ids):
        out.append(FleetAgent(
            runner=runner or _sort_runner, agent_id=aid,
            journal=journals[i] if journals else None,
        ))
    return out


def _close_all(ctl, agents):
    try:
        ctl.shutdown(drain=True, timeout=30)
    finally:
        for a in agents:
            a.close()


# -- wire protocol -----------------------------------------------------------


def test_proto_frame_round_trip():
    a, b = socket.socketpair()
    try:
        payload = np.arange(100, dtype=np.int32).tobytes()
        proto.send_frame(a, {"type": "submit", "job_id": "j1"}, payload)
        header, got = proto.recv_frame(b)
        assert header["type"] == "submit" and header["job_id"] == "j1"
        assert header["payload_len"] == len(payload) and got == payload
        proto.send_frame(b, {"type": "accepted", "job_id": "j1"})
        header, got = proto.recv_frame(a)
        assert header["type"] == "accepted" and got == b""
        a.close()
        assert proto.recv_frame(b) is None  # clean EOF at a boundary
    finally:
        for s in (a, b):
            try:
                s.close()
            except OSError:
                pass


def test_proto_rejects_bad_frames():
    with pytest.raises(proto.ProtocolError, match="unregistered"):
        proto.send_frame(None, {"type": "made_up"})
    a, b = socket.socketpair()
    try:
        # A torn frame (payload promised but the stream dies) must raise,
        # never return a short parse.
        head = json.dumps(
            {"type": "submit", "payload_len": 64}
        ).encode()
        import struct

        a.sendall(struct.pack(">I", len(head)) + head + b"short")
        a.close()
        with pytest.raises(proto.ProtocolError, match="mid-"):
            proto.recv_frame(b)
    finally:
        b.close()
    a, b = socket.socketpair()
    try:
        a.sendall(b"\x00\x00\x00\x04oops")
        with pytest.raises(proto.ProtocolError):
            proto.recv_frame(b)
    finally:
        a.close()
        b.close()


def test_encode_decode_array_round_trip():
    x = np.arange(33, dtype=np.int64)
    meta, payload = proto.encode_array(x)
    y = proto.decode_array(meta, payload)
    np.testing.assert_array_equal(x, y)
    with pytest.raises(proto.ProtocolError, match="bytes"):
        proto.decode_array(meta, payload[:-8])


def test_pure_ladder_twins_pinned():
    """The controller computes locality keys WITHOUT the backend: its pure
    twins must stay bit-equal to the jitted pipeline's originals."""
    from dsort_tpu.models.pipelines import FUSED_SMALL_JOB_MAX, pad_rung
    from dsort_tpu.obs.prof import variant_label
    from dsort_tpu.serve.variants import fused_variant_key

    assert proto.FLEET_SMALL_JOB_MAX == FUSED_SMALL_JOB_MAX
    rng = np.random.default_rng(0)
    ns = [1, 7, 8, 9, 100, 1 << 10, (1 << 16) + 3] + list(
        rng.integers(1, 1 << 22, 200)
    )
    for n in ns:
        n = int(n)
        assert proto.fused_rung(n) == pad_rung(n), n
        key = fused_variant_key(n, "int32", "auto")
        assert proto.variant_label_of_key(key) == variant_label(key), key
        assert variant_label(key).startswith(
            proto.fused_rung_prefix(n, "int32")
        )


def test_parse_agent_addrs():
    assert proto.parse_agent_addrs("a:1, b:2") == [("a", 1), ("b", 2)]
    assert proto.parse_agent_addrs([("h", 9)]) == [("h", 9)]
    with pytest.raises(ValueError, match="HOST:PORT"):
        proto.parse_agent_addrs("nocolon")
    with pytest.raises(ValueError, match="no agent"):
        proto.parse_agent_addrs("")


# -- the pure control plane --------------------------------------------------


def test_controller_imports_without_jax():
    """The §12 contract: the control plane (controller + policy + proto)
    imports and constructs in a process where importing jax RAISES."""
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from dsort_tpu.fleet.controller import FleetController\n"
        "from dsort_tpu.serve.policy import ControlPolicy\n"
        "p = ControlPolicy(); v = p.consider('t')\n"
        "assert v.admitted\n"
        "c = FleetController(['127.0.0.1:1'], heartbeat_s=60, start=False)\n"
        "assert c.stats()['agents'] == 0\n"
        "c.kill()\n"
        "print('pure-ok')\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr
    assert "pure-ok" in r.stdout


def test_admission_no_capacity_ordering():
    assert "no_capacity" in ADMISSION_REASONS
    ctl = AdmissionController(max_queue_depth=1, max_tenant_inflight=1)
    v = ctl.consider("t", shutting_down=False, no_capacity=True)
    assert not v.admitted and v.reason == "no_capacity"
    # shutting_down outranks no_capacity; no_capacity outranks queue_full
    v = ctl.consider("t", shutting_down=True, no_capacity=True)
    assert v.reason == "shutting_down"
    ctl.consider("t", shutting_down=False)  # fill the queue
    v = ctl.consider("u", shutting_down=False, no_capacity=True)
    assert v.reason == "no_capacity"
    v = ctl.consider("u", shutting_down=False)
    assert v.reason == "queue_full"


def test_policy_state_round_trip_preserves_drr_order():
    """The restart contract's fairness half: a policy serialized mid-queue
    and restored pops the EXACT order the original would have."""
    def build():
        p = ControlPolicy(
            max_queue_depth=64, max_tenant_inflight=32,
            drr_quantum_keys=1000, tenant_weights={"heavy": 1.0, "vip": 2.0},
        )
        for i in range(6):
            t = ["heavy", "vip", "light"][i % 3]
            p.consider(t)
            p.push(t, 900 + i, f"j{i}")
        p.note_wait("heavy", 0.25)
        return p

    original = build()
    twin = build()
    state = json.loads(json.dumps(original.state_dict()))  # wire round trip
    restored = ControlPolicy(
        max_queue_depth=64, max_tenant_inflight=32,
        drr_quantum_keys=1000, tenant_weights={"heavy": 1.0, "vip": 2.0},
    )
    restored.load_state(state)
    assert restored.queue_depth == original.queue_depth
    assert restored.admission.tenant_inflight("vip") == 2
    seq_twin = [twin.pop() for _ in range(7)]
    seq_restored = [restored.pop() for _ in range(7)]
    assert seq_restored == seq_twin
    assert seq_restored[-1] is None


def test_policy_shed_window_survives_round_trip():
    p = ControlPolicy(slo_shed_ms=1.0)
    p.consider("t")
    p.push("t", 10, "j0")
    for _ in range(8):
        p.note_wait("t", 0.5)  # 500 ms >> 1 ms target
    assert p.should_shed("t")
    q = ControlPolicy(slo_shed_ms=1.0)
    q.load_state(json.loads(json.dumps(p.state_dict())))
    assert q.should_shed("t")


# -- routing over live agents ------------------------------------------------


def test_fleet_end_to_end_two_agents():
    journal = EventLog()
    agents = _agents("A", "B")
    ctl = FleetController(
        [a.addr for a in agents], heartbeat_s=0.2, journal=journal,
    )
    try:
        rng = np.random.default_rng(0)
        # Sequential submit->await keeps the affinity deterministic (no
        # busy-agent spill): every job of a size must land on ONE agent.
        for i in range(8):
            d = rng.integers(0, 10**6, 1000 if i % 2 else 2000, dtype=np.int32)
            v, t = ctl.submit(d, tenant=f"t{i % 2}")
            assert v.admitted
            np.testing.assert_array_equal(t.result(timeout=60), np.sort(d))
        types = [e.type for e in journal.events()]
        assert types.count("agent_register") == 2
        assert types.count("job_routed") == 8
        assert types.count("job_done") == 8
        # Locality stickiness: all jobs of one size land on ONE agent.
        by_size = {}
        for e in journal.events():
            if e.type == "job_routed":
                by_size.setdefault(e.fields["n_keys"], set()).add(
                    e.fields["agent"]
                )
        for size, used in by_size.items():
            assert len(used) == 1, f"size {size} scattered over {used}"
    finally:
        _close_all(ctl, agents)


def test_draining_agent_routes_around():
    """An agent advertising draining takes no new fleet work; jobs flow to
    the healthy agent (spill-over routing, not blocking)."""
    journal = EventLog()
    agents = _agents("A", "B")
    agents[0].drain()  # drains BEFORE the controller connects: the
    # welcome advertises it, so routing is deterministic with no sleeps
    ctl = FleetController(
        [a.addr for a in agents], heartbeat_s=0.2, journal=journal,
    )
    try:
        d = np.arange(500, dtype=np.int32)[::-1].copy()
        tickets = [ctl.submit(d, tenant="t")[1] for _ in range(3)]
        for t in tickets:
            np.testing.assert_array_equal(t.result(timeout=60), np.sort(d))
        routed = [
            e.fields["agent"] for e in journal.events()
            if e.type == "job_routed"
        ]
        assert routed and set(routed) == {"B"}
    finally:
        _close_all(ctl, agents)


def test_no_capacity_when_every_agent_drains():
    """ISSUE 12 satellite: the fleet's all-agents-draining rejection is the
    TYPED `no_capacity` verdict — journaled and counted per tenant in
    dsort_admissions_total — never a reused `queue_full`."""
    from dsort_tpu.obs import Telemetry

    journal = EventLog()
    tel = Telemetry()
    agents = _agents("A", "B")
    for a in agents:
        a.drain()
    ctl = FleetController(
        [a.addr for a in agents], heartbeat_s=0.2, journal=journal,
        telemetry=tel,
    )
    try:
        v, t = ctl.submit(np.arange(100, dtype=np.int32), tenant="acme")
        assert t is None and not v.admitted
        assert v.reason == "no_capacity"
        rej = [e for e in journal.events() if e.type == "job_rejected"]
        assert rej and rej[0].fields["reason"] == "no_capacity"
        assert tel.snapshot()["admissions"]["acme/no_capacity"] == 1
    finally:
        _close_all(ctl, agents)


def test_agent_loss_reroutes_inflight_job():
    """A dead agent's in-flight job re-enters the queue (`job_rerouted`,
    reason agent_lost) and completes on a survivor."""
    gate = threading.Event()

    def blocking_runner(data, metrics, job_id=None):
        gate.wait(60)
        return np.sort(data)

    journal = EventLog()
    a = FleetAgent(runner=blocking_runner, agent_id="A")
    b = FleetAgent(runner=_sort_runner, agent_id="B")
    ctl = FleetController(
        [a.addr, b.addr], heartbeat_s=0.2, journal=journal,
    )
    try:
        d = np.arange(777, dtype=np.int32)[::-1].copy()
        v, ticket = ctl.submit(d, tenant="t")
        assert v.admitted
        # Both agents idle -> least-loaded tie breaks on label: A wins.
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            routed = [e for e in journal.events() if e.type == "job_routed"]
            if routed:
                break
            time.sleep(0.02)
        assert routed and routed[0].fields["agent"] == "A"
        threading.Thread(target=a.kill, daemon=True).start()
        np.testing.assert_array_equal(ticket.result(timeout=60), np.sort(d))
        types = [e.type for e in journal.events()]
        assert "job_rerouted" in types
        rr = next(e for e in journal.events() if e.type == "job_rerouted")
        assert rr.fields["reason"] == "agent_lost" and rr.fields["frm"] == "A"
        routed = [
            e.fields["agent"] for e in journal.events()
            if e.type == "job_routed"
        ]
        assert routed[-1] == "B"
    finally:
        gate.set()
        _close_all(ctl, [b])
        a.close(drain=False)


class _StalledAgent:
    """A stuck-but-connected agent: completes the hello/welcome handshake,
    answers pings, then swallows the first submit and never replies again.
    Accepts exactly ONE connection (reconnects fail) so the post-stall
    routing is deterministic."""

    def __init__(self, variants=()):
        self.variants = list(variants)
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind(("127.0.0.1", 0))
        self._listener.listen(1)
        self.addr = "127.0.0.1:%d" % self._listener.getsockname()[1]
        self.got_submit = threading.Event()
        self._conns = []
        threading.Thread(target=self._serve, daemon=True).start()

    def _serve(self):
        try:
            conn, _ = self._listener.accept()
        except OSError:
            return
        self._conns.append(conn)
        self._listener.close()
        try:
            while True:
                frame = proto.recv_frame(conn)
                if frame is None:
                    return
                header, _ = frame
                if header["type"] == "hello":
                    proto.send_frame(conn, {
                        "type": "welcome", "agent_id": "stall",
                        "capacity": 1, "big_jobs": False, "draining": False,
                        "variants": self.variants, "jobs": {},
                    })
                elif header["type"] == "ping" and not self.got_submit.is_set():
                    proto.send_frame(conn, {
                        "type": "heartbeat", "queued": 0, "in_flight": 0,
                        "draining": False, "variants": self.variants,
                        "capacity": 1,
                    })
                elif header["type"] == "submit":
                    self.got_submit.set()  # swallow; never reply again
        except (proto.ProtocolError, OSError):
            pass

    def close(self):
        for c in self._conns:
            try:
                c.close()
            except OSError:
                pass
        try:
            self._listener.close()
        except OSError:
            pass


def test_stalled_agent_does_not_stall_fleet_dispatch():
    """ISSUE 13 satellite (the ROADMAP-named stall): one stuck-but-
    connected agent must not stall fleet-wide dispatch.  Dispatch runs on
    per-agent lanes with a bounded per-agent send deadline
    (``dispatch_timeout_s``): the healthy agent's jobs flow immediately
    while the stalled lane waits out its deadline, and the swallowed job
    then fails over to the healthy agent."""
    journal = EventLog()
    d_stall = np.arange(1000, dtype=np.int32)[::-1].copy()
    d_ok = np.arange(2000, dtype=np.int32)[::-1].copy()
    # The stalled agent alone advertises the first job's rung: locality
    # routes that job onto it deterministically.
    stalled = _StalledAgent(
        variants=[proto.fused_rung_prefix(len(d_stall), "int32") + "lax"]
    )
    healthy = FleetAgent(runner=_sort_runner, agent_id="H")
    ctl = FleetController(
        [stalled.addr, healthy.addr],
        # A LIVE heartbeat: the health plane must not serialize behind the
        # stuck lane's request slot either (LaneBusy skip) — pings to the
        # healthy agent keep flowing throughout the stall.
        heartbeat_s=0.3,
        request_timeout_s=30,    # the OLD fleet-wide stall bound — never paid
        dispatch_timeout_s=4.0,  # the bounded per-agent send deadline
        journal=journal,
    )
    try:
        v, stuck = ctl.submit(d_stall, tenant="t")
        assert v.admitted
        assert stalled.got_submit.wait(10), "job never routed to the stall"
        # The healthy agent's jobs dispatch and complete WHILE the stalled
        # lane is still inside its send deadline — the old synchronous
        # dispatcher would have serialized them behind the stuck submit
        # for up to request_timeout_s.
        t0 = time.monotonic()
        tickets = [ctl.submit(d_ok, tenant="t")[1] for _ in range(3)]
        for t in tickets:
            np.testing.assert_array_equal(
                t.result(timeout=10), np.sort(d_ok)
            )
        healthy_took = time.monotonic() - t0
        assert healthy_took < 4.0, (
            f"healthy jobs took {healthy_took:.1f}s — dispatch stalled "
            "behind the stuck agent"
        )
        # At the deadline the stalled agent is failed over and the
        # swallowed job completes on the healthy agent.
        np.testing.assert_array_equal(
            stuck.result(timeout=30), np.sort(d_stall)
        )
        rr = [e for e in journal.events() if e.type == "job_rerouted"]
        assert rr and rr[0].fields["reason"] in (
            "dispatch_failed", "agent_lost"
        )
        # The trace is honest: the swallowed job was routed to the stall
        # first, re-routed at the deadline, and every completed dispatch
        # names the healthy agent.
        routed = [
            e.fields["agent"] for e in journal.events()
            if e.type == "job_routed"
        ]
        assert routed[0] == "stall" and routed.count("H") == 4
    finally:
        stalled.close()
        ctl.kill()
        healthy.close(drain=False)


# -- the controller-restart drill (ISSUE 12 acceptance) ----------------------


def test_controller_restart_drill(tmp_path):
    """Kill the controller with jobs queued AND in-flight on 2 agents;
    restart; assert via the MERGED journal that in-flight jobs complete
    without re-dispatch (exactly one agent-side job_start each) and the
    queued jobs drain in the persisted DRR order."""
    gate = threading.Event()

    def slow_runner(data, metrics, job_id=None):
        gate.wait(60)
        metrics.event(
            "job_done", n_keys=len(data), counters=dict(metrics.counters)
        )
        return np.sort(data)

    ja, jb = EventLog(), EventLog()
    agents = _agents("A", "B", runner=slow_runner, journals=[ja, jb])
    state_dir = str(tmp_path / "state")
    j1 = EventLog()
    ctl = FleetController(
        [a.addr for a in agents], state_dir=state_dir, heartbeat_s=0.3,
        journal=j1,
    )
    rng = np.random.default_rng(1)
    datas = []
    try:
        for i in range(6):
            d = rng.integers(0, 10**6, 400, dtype=np.int32)
            v, _ = ctl.submit(d, tenant=["acme", "blue", "coral"][i % 3])
            assert v.admitted
            datas.append(d)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            st = ctl.stats()
            if st["in_flight"] == 2 and st["queued"] == 4:
                break
            time.sleep(0.02)
        st = ctl.stats()
        assert st["in_flight"] == 2 and st["queued"] == 4, st
    finally:
        ctl.kill()  # ungraceful: no drain, no clean close

    # The persisted control plane names every job; replaying its policy
    # snapshot through a fresh ControlPolicy IS the expected DRR order.
    state = json.load(
        open(os.path.join(state_dir, "controller_state.json"))
    )
    assert {j["status"] for j in state["jobs"].values()} == {
        "inflight", "queued"
    }
    replay = ControlPolicy()
    replay.load_state(state["policy"])
    expected_order = []
    while True:
        nxt = replay.pop()
        if nxt is None:
            break
        expected_order.append(nxt[1])
    assert len(expected_order) == 4

    j2 = EventLog()
    ctl2 = FleetController(
        [a.addr for a in agents], state_dir=state_dir, heartbeat_s=0.3,
        journal=j2,
    )
    try:
        gate.set()  # release the in-flight (and then queued) jobs
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            st = ctl2.stats()
            if st["done"] + st["failed"] >= 6:
                break
            time.sleep(0.05)
        st = ctl2.stats()
        assert st["done"] == 6 and st["failed"] == 0, st
    finally:
        ctl2.shutdown(drain=True, timeout=30)
        for a in agents:
            a.close()

    merged = merge_records([
        [e.to_dict() for e in log.events()]
        for log in (j1, j2, ja, jb)
    ])
    # ZERO re-dispatch: each fleet job started exactly once on the agents.
    starts = {}
    for r in merged:
        if r["type"] == "job_start" and r["src"] in (2, 3):
            starts[r.get("job_id")] = starts.get(r.get("job_id"), 0) + 1
    assert len(starts) == 6
    assert all(v == 1 for v in starts.values()), starts
    # The restore-before-dispatch ordering is the declared
    # `controller_restore` contract (ISSUE 17): the restarted controller
    # announces itself BEFORE it dequeues or routes anything.
    report = assert_conformant(merged)
    assert report["contracts"]["controller_restore"]["checked"] == 1
    # The restart announced itself with the persisted counts.
    restore = next(r for r in merged if r["type"] == "controller_restore")
    assert restore["queued"] == 4 and restore["inflight"] == 2
    assert restore["src"] == 1
    # Nothing was re-routed (both agents survived and kept their jobs).
    assert not [r for r in merged if r["type"] == "job_rerouted"]
    # Queued jobs drained in the persisted DRR order.
    routed2 = [
        r["job_id"] for r in merged
        if r["type"] == "job_routed" and r["src"] == 1
    ]
    assert routed2 == expected_order


def test_restart_requeues_job_lost_with_its_agent(tmp_path):
    """An in-flight job whose agent never comes back is re-queued
    (`job_rerouted` reason=agent_lost) instead of waiting forever."""
    gate = threading.Event()

    def slow_runner(data, metrics, job_id=None):
        gate.wait(60)
        return np.sort(data)

    a = FleetAgent(runner=slow_runner, agent_id="A")
    b = FleetAgent(runner=_sort_runner, agent_id="B")
    state_dir = str(tmp_path / "state")
    j1 = EventLog()
    ctl = FleetController(
        [a.addr, b.addr], state_dir=state_dir, heartbeat_s=0.3, journal=j1,
    )
    d = np.arange(300, dtype=np.int32)[::-1].copy()
    try:
        v, _ = ctl.submit(d, tenant="t")
        assert v.admitted
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if ctl.stats()["in_flight"] == 1:
                break
            time.sleep(0.02)
        assert ctl.stats()["in_flight"] == 1
    finally:
        ctl.kill()
    a.kill()  # the agent dies WITH the controller
    gate.set()
    j2 = EventLog()
    ctl2 = FleetController(
        [a.addr, b.addr], state_dir=state_dir, heartbeat_s=0.3, journal=j2,
    )
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            st = ctl2.stats()
            if st["done"] + st["failed"] >= 1:
                break
            time.sleep(0.05)
        assert ctl2.stats()["done"] == 1
        types = [e.type for e in j2.events()]
        assert "controller_restore" in types and "job_rerouted" in types
        rr = next(e for e in j2.events() if e.type == "job_rerouted")
        assert rr.fields["reason"] == "agent_lost"
        assert_conformant(j2)  # restore announced before any dispatch
    finally:
        ctl2.shutdown(drain=True, timeout=30)
        b.close()


# -- observability satellites ------------------------------------------------


def _write_journal(path, types):
    log = EventLog()
    for t, fields in types:
        log.emit(t, **fields)
    log.write_jsonl(str(path))


def test_expand_path_args_directory_and_glob(tmp_path):
    d = tmp_path / "fleet"
    d.mkdir()
    _write_journal(d / "ctl.jsonl", [("job_start", {"mode": "fleet", "n_keys": 1})])
    _write_journal(d / "agent1.jsonl", [("probe", {"worker": 0, "ok": True})])
    (d / "ctl.jsonl.1").write_text(
        (d / "ctl.jsonl").read_text()
    )  # a rotation piece rides along
    got = expand_path_args([str(d)])
    assert [os.path.basename(p) for p in got] == [
        "agent1.jsonl", "ctl.jsonl", "ctl.jsonl.1"
    ]
    # Rotation pieces still collapse into their base journal downstream.
    groups = group_rotated(got)
    assert len(groups) == 2
    got = expand_path_args([str(d / "*.jsonl")])
    assert [os.path.basename(p) for p in got] == ["agent1.jsonl", "ctl.jsonl"]
    # Overlapping args never duplicate a journal into a phantom process.
    got = expand_path_args([str(d), str(d / "ctl.jsonl")])
    assert len(got) == len(set(got))
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(ValueError, match="no"):
        expand_path_args([str(empty)])
    with pytest.raises(ValueError, match="matched no"):
        expand_path_args([str(tmp_path / "nope*.jsonl")])


def test_cli_report_merges_directory(tmp_path, capsys):
    from dsort_tpu import cli

    d = tmp_path / "run"
    d.mkdir()
    _write_journal(d / "ctl.jsonl", [
        ("clock_sync", {"source": "ctl"}),
        ("job_routed", {"job_id": "f1", "agent": "A", "reason": "locality",
                        "n_keys": 10, "tenant": "t"}),
    ])
    _write_journal(d / "agent.jsonl", [
        ("clock_sync", {"source": "A"}),
        ("job_start", {"mode": "fleet", "n_keys": 10, "job_id": "f1"}),
        ("job_done", {"n_keys": 10}),
    ])
    rc = cli.main(["report", str(d)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "job_routed" in out and "job_done" in out


def test_render_fleet_combines_sources():
    from dsort_tpu.obs import Telemetry
    from dsort_tpu.obs.telemetry import parse_prometheus_text
    from dsort_tpu.obs.top import render_fleet

    t1, t2 = Telemetry(), Telemetry()
    t1.admission_verdict("acme", "admitted")
    t1.admission_verdict("acme", "no_capacity")
    t2.admission_verdict("acme", "admitted")
    t1.set_gauge("variant_cache_hits", 8)
    t1.set_gauge("variant_cache_misses", 2)
    t2.set_gauge("variant_cache_hits", 2)
    t2.set_gauge("variant_cache_misses", 8)
    t1.set_gauge("queue_depth", 3)
    scrapes = [
        ("http://a/metrics", parse_prometheus_text(t1.render_prometheus())),
        ("http://b/metrics", parse_prometheus_text(t2.render_prometheus())),
    ]
    text = render_fleet(scrapes)
    assert "fleet:" in text and "http://a/metrics" in text
    # combined admissions: acme admitted 2, no_capacity 1
    assert "acme" in text and "no_capacity" in text
    # combined cache: 10 hits / 20 lookups = 50.0%
    assert "hit rate 50.0%" in text


def test_render_fleet_controller_admissions_not_double_counted():
    """With a controller among the sources (dsort_fleet_agents gauge),
    the fleet-wide admissions table sums controllers ONLY — an agent's
    local admission of a routed job mirrors the controller's and would
    double-count every fleet job."""
    from dsort_tpu.obs import Telemetry
    from dsort_tpu.obs.telemetry import parse_prometheus_text
    from dsort_tpu.obs.top import render_fleet

    ctl, agent = Telemetry(), Telemetry()
    ctl.set_gauge("fleet_agents", 1)
    ctl.admission_verdict("acme", "admitted")
    agent.admission_verdict("acme", "admitted")  # the routed job, again
    text = render_fleet([
        ("http://ctl/metrics", parse_prometheus_text(ctl.render_prometheus())),
        ("http://a1/metrics", parse_prometheus_text(agent.render_prometheus())),
    ])
    row = next(
        ln for ln in text.splitlines()
        if ln.strip().startswith("acme") and "admitted" in ln
    )
    assert row.split()[-1] == "1", row


def test_cli_top_multi_url_renders_fleet_view(capsys):
    from dsort_tpu import cli
    from dsort_tpu.obs import MetricsServer, Telemetry

    t1, t2 = Telemetry(), Telemetry()
    t1.admission_verdict("acme", "admitted")
    with MetricsServer(t1) as s1, MetricsServer(t2) as s2:
        rc = cli.main(["top", s1.url, s2.url])
    out = capsys.readouterr().out
    assert rc == 0
    assert "2/2 sources" in out and "fleet:" in out
    assert "admissions (fleet-wide):" in out
    # One dead agent must not abort the fleet view (that is exactly when
    # the operator looks): the reachable sources still render.
    with MetricsServer(t1) as s1:
        dead = f"http://127.0.0.1:1/metrics"
        rc = cli.main(["top", s1.url, dead])
    out = capsys.readouterr().out
    assert rc == 0
    assert "1/2 sources" in out and f"(unreachable: {dead})" in out


# -- registries + docs -------------------------------------------------------


def test_fleet_events_and_counters_registered():
    for etype in ("agent_register", "agent_heartbeat", "job_routed",
                  "job_rerouted", "controller_restore"):
        assert etype in EVENT_TYPES
    for counter in ("fleet_jobs_routed", "fleet_jobs_rerouted",
                    "fleet_heartbeats", "controller_restores"):
        assert counter in COUNTERS


def test_architecture_documents_fleet_plane():
    """§12's contract is test-enforced like §7-§11: frame vocabulary,
    event types, the no_capacity verdict, and the restart/re-attach
    contract all appear verbatim."""
    arch = open(os.path.join(REPO, "ARCHITECTURE.md"), encoding="utf-8").read()
    assert "## 12. Fleet plane" in arch
    for frame in proto.FRAME_TYPES:
        assert f"`{frame}`" in arch, f"frame type {frame} undocumented"
    for etype in ("agent_register", "agent_heartbeat", "job_routed",
                  "job_rerouted", "controller_restore"):
        assert f"`{etype}`" in arch, f"fleet event {etype} undocumented"
    assert "`no_capacity`" in arch
    for term in ("length-prefixed", "locality", "re-attach", "draining",
                 "ControlPolicy", "known_jobs", "state_dir"):
        assert term in arch, f"§12 must explain {term}"


def test_fleet_config_keys():
    from dsort_tpu.config import ConfigError, FleetConfig, SortConfig

    cfg = SortConfig.from_mapping({
        "FLEET_AGENTS": "h1:9200, h2:9200",
        "FLEET_STATE_DIR": "/tmp/fleet",
        "FLEET_ROUTING": "random",
        "FLEET_HEARTBEAT_S": "0.5",
        "FLEET_DISPATCH_TIMEOUT_S": "4.5",
        "FLEET_TELEMETRY": "0",
    })
    assert cfg.fleet.agents == ("h1:9200", "h2:9200")
    assert cfg.fleet.state_dir == "/tmp/fleet"
    assert cfg.fleet.routing == "random"
    assert cfg.fleet.heartbeat_s == 0.5
    assert cfg.fleet.dispatch_timeout_s == 4.5
    assert cfg.fleet.telemetry is False
    assert SortConfig.from_mapping({}).fleet.dispatch_timeout_s is None
    assert SortConfig.from_mapping({}).fleet.telemetry is True
    assert SortConfig.from_mapping({"FLEET_ROUTING": "health"}) \
        .fleet.routing == "health"
    with pytest.raises(ConfigError, match="routing"):
        FleetConfig(routing="mystery")
    with pytest.raises(ConfigError, match="heartbeat"):
        FleetConfig(heartbeat_s=0)
    with pytest.raises(ConfigError, match="dispatch_timeout"):
        FleetConfig(dispatch_timeout_s=0)
    with pytest.raises(ConfigError, match="HOST:PORT"):
        FleetConfig(agents=("nocolon",))


# -- CLI surface -------------------------------------------------------------


def test_cli_fleet_repl_two_agents(tmp_path, monkeypatch):
    """`dsort fleet --agents ...` drives the serve REPL over live agents:
    per-line tenants, sorted output files, a journaled fleet lifecycle."""
    from dsort_tpu import cli

    agents = _agents("A", "B")
    rng = np.random.default_rng(3)
    files, datas = [], []
    for i in range(3):
        d = rng.integers(0, 10**6, 700 + i * 100, dtype=np.int64)
        p = tmp_path / f"in{i}.txt"
        np.savetxt(p, d, fmt="%d")
        files.append(p)
        datas.append(d)
    journal = tmp_path / "fleet.jsonl"
    lines = iter(
        [f"tenant=acme {files[0]}", f"tenant=blue {files[1]}",
         f"tenant=acme {files[2]}", "exit"]
    )
    monkeypatch.setattr("builtins.input", lambda *_: next(lines))
    try:
        rc = cli.main([
            "fleet", "--agents", ",".join(a.addr for a in agents),
            "--state-dir", str(tmp_path / "state"),
            "-o", str(tmp_path / "out.txt"),
            "--journal", str(journal),
        ])
    finally:
        for a in agents:
            a.close()
    assert rc == 0
    records = EventLog.read_jsonl(str(journal))
    types = [r["type"] for r in records]
    assert types.count("job_routed") == 3
    assert types.count("job_done") == 3
    assert "agent_register" in types and types[-1] == "serve_stop"
    admitted = [r for r in records if r["type"] == "job_admitted"]
    assert {r["tenant"] for r in admitted} == {"acme", "blue"}
    out = np.loadtxt(tmp_path / "out.txt", dtype=np.int64)
    np.testing.assert_array_equal(out, np.sort(datas[-1]))


def test_cli_fleet_agent_flag_parse():
    """`dsort fleet` without agents fails loudly; bad routing is refused
    at the parser."""
    from dsort_tpu import cli

    with pytest.raises(SystemExit, match="--agents"):
        cli.main(["fleet"])
    with pytest.raises(SystemExit):
        cli.main(["fleet", "--agents", "h:1", "--routing", "mystery"])


def test_cli_fleet_agent_process_drains_on_sigterm(tmp_path):
    """The real `dsort fleet-agent` process: serves a routed job over TCP
    and SIGTERM-drains to exit 0 with a flushed journal."""
    import signal

    journal = tmp_path / "agent.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.Popen(
        [sys.executable, "-m", "dsort_tpu.cli", "fleet-agent",
         "--mode", "local", "--port", "0", "--agent-id", "cliA",
         "--journal", str(journal)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env, cwd=REPO,
    )
    try:
        line = p.stdout.readline()
        assert "listening on" in line, line
        addr = line.strip().rsplit(" ", 1)[-1]
        ctl = FleetController([addr], heartbeat_s=0.3)
        try:
            d = np.arange(1200, dtype=np.int32)[::-1].copy()
            v, ticket = ctl.submit(d, tenant="acme", job_id="cli-job")
            assert v.admitted
            np.testing.assert_array_equal(
                ticket.result(timeout=120), np.sort(d)
            )
        finally:
            ctl.shutdown(drain=True, timeout=30)
        p.send_signal(signal.SIGTERM)
        assert p.wait(timeout=60) == 0
    finally:
        if p.poll() is None:
            p.kill()
    records = EventLog.read_jsonl(str(journal))
    types = [r["type"] for r in records]
    assert "clock_sync" in types and "job_done" in types
    assert {r.get("tenant") for r in records if r["type"] == "job_admitted"} \
        == {"acme"}


# -- mixed tenants over two mesh-owning agents ------------------------------


def _fleet_mixed(routing):
    """Three tenants' small jobs at two repeat sizes, twice over, plus one
    full-mesh job, through two agents that each own half the mesh.

    The first job of each size goes out as a pair, so each size gets its
    home agent; once both are compiled and advertised, the rest go out
    as one burst, all submitted before any result is awaited.  Returns
    whether every output equals ``np.sort``, the fleet-wide variant-cache
    hit rate, the agent each job was routed to, and the rerouted and
    health-verdict counts."""
    import jax

    from dsort_tpu.config import ServeConfig
    from dsort_tpu.serve import SortService

    devs = jax.devices()[:8]
    serve = ServeConfig(max_queue_depth=64, max_tenant_inflight=32)
    agents = [
        FleetAgent(service=SortService(devices=half, serve=serve),
                   agent_id=f"{routing}-{i}")
        for i, half in enumerate((devs[:4], devs[4:]))
    ]
    journal = EventLog()
    ctl = FleetController(
        [a.addr for a in agents], routing=routing, heartbeat_s=0.2,
        journal=journal, max_queue_depth=64, max_tenant_inflight=32,
    )
    sizes = (20_000, 10_000)
    rng = np.random.default_rng(0)
    jobs = [
        (t, rng.integers(0, 1 << 30, sizes[j % 2], dtype=np.int32))
        for _ in range(2) for j in range(4) for t in ("acme", "blue", "coral")
    ]
    jobs.append(("acme", rng.integers(0, 1 << 30, 1 << 20, dtype=np.int32)))
    first = [jobs[0], jobs[3]]  # the first job of each size
    burst = [job for i, job in enumerate(jobs) if i not in (0, 3)]

    def run(batch):
        tickets = [ctl.submit(d, tenant=t)[1] for t, d in batch]
        return all(
            np.array_equal(ticket.result(timeout=300), np.sort(d))
            for (_, d), ticket in zip(batch, tickets)
        )

    try:
        identical = run(first)
        rungs = {proto.fused_rung_prefix(n, "int32") for n in sizes}
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not all(
            any(v.startswith(r) for a in ctl.agent_info() for v in a["variants"])
            for r in rungs
        ):
            time.sleep(0.05)
        identical = run(burst) and identical
        stats = [a.service.variants.stats() for a in agents]
        hits = sum(st["hits"] for st in stats)
        lookups = hits + sum(st["misses"] for st in stats)
        events = journal.events()
        routed = [e.fields["agent"] for e in events if e.type == "job_routed"]
        types = [e.type for e in events]
        return (identical, hits / lookups, routed,
                types.count("job_rerouted"), types.count("health_verdict"))
    finally:
        _close_all(ctl, agents)


@pytest.fixture(scope="module")
def random_routing_hit_rate():
    """The hit rate random placement reaches on the same jobs: the
    baseline the locality case must beat."""
    from dsort_tpu.obs.prof import LEDGER

    LEDGER.reset()
    identical, hit_rate, _, rerouted, _ = _fleet_mixed("random")
    assert identical and rerouted == 0
    LEDGER.reset()
    return hit_rate


@pytest.mark.parametrize("routing", ["locality", "health"])
def test_fleet_mixed_tenants_two_agents(routing, request):
    """Both routings return every job's sorted keys with nothing rerouted.
    The first pair splits over the two agents, because a job routed but
    not yet accepted counts as load.  Locality beats random placement on
    the fleet-wide variant-cache hit rate, and the health routing folds
    live telemetry into verdicts."""
    baseline = (request.getfixturevalue("random_routing_hit_rate")
                if routing == "locality" else None)
    identical, hit_rate, routed, rerouted, verdicts = _fleet_mixed(routing)
    assert identical
    assert rerouted == 0
    assert len(set(routed[:2])) == 2, routed
    if routing == "locality":
        assert hit_rate > baseline
    else:
        assert verdicts > 0
