"""Fleet transport + failover: framing, fencing, fault injection.

The wire-level acceptance properties as executable tests:

  * framing is robust: torn mid-frame writes and oversize declared
    lengths error cleanly on one connection without wedging the accept
    loop (the next client is still served),
  * arrays survive the wire byte-exactly for every served dtype
    (uint32, float32, bfloat16, bool),
  * the consistent-hash ring is a pure function of the shard count —
    every client derives the same routing with no coordination,
  * scripted faults replay exactly (plan parse/json/seeded round-trips;
    the injector fires each spec exactly once),
  * retries are idempotent: a journaled rid is answered by journal
    replay — bit-identical bytes, never a second counter window,
  * a journal has exactly one writer (flock fencing), and
  * the headline guarantee: a 2-shard burst with a scripted
    kill-mid-burst produces EXACTLY the bytes of the no-fault run —
    the surviving peer fences the dead shard's journal, replays its
    committed windows, and resumes its tenant regions bit-identically.
"""
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.runtime.fault import (FaultInjector, FaultPlan, FaultSpec,
                                 rid_index)
from repro.service import audit, transport
from repro.service.audit import Journal, JournalLockedError
from repro.service.burst import make_requests
from repro.service.fleet import (Fleet, FleetConfig, HashRing,
                                 run_fleet_burst)
from repro.service.frontend import RandRequest
from repro.service.transport import (FrameTooLarge, ShardHost, TornFrame,
                                     decode_array, encode_array,
                                     recv_frame, send_frame)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Framing
# ---------------------------------------------------------------------------

def test_frame_roundtrip():
    a, b = socket.socketpair()
    try:
        msg = {"op": "ping", "nested": {"xs": [1, 2, 3]}}
        send_frame(a, msg)
        assert recv_frame(b) == msg
        # several frames back to back stay in sync
        for i in range(5):
            send_frame(a, {"i": i})
        for i in range(5):
            assert recv_frame(b) == {"i": i}
    finally:
        a.close()
        b.close()


def test_frame_clean_eof_is_none():
    a, b = socket.socketpair()
    a.close()
    try:
        assert recv_frame(b) is None
    finally:
        b.close()


def test_frame_too_large_both_directions():
    a, b = socket.socketpair()
    try:
        with pytest.raises(FrameTooLarge):
            send_frame(a, {"blob": "x" * 256}, max_frame=64)
        # hostile declared length: reader refuses before allocating
        a.sendall(struct.pack("!I", transport.MAX_FRAME + 1))
        with pytest.raises(FrameTooLarge):
            recv_frame(b)
    finally:
        a.close()
        b.close()


def test_torn_frame_mid_body():
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack("!I", 100) + b'{"partial": tru')
        a.close()
        with pytest.raises(TornFrame):
            recv_frame(b)
    finally:
        b.close()


def test_torn_frame_mid_header():
    a, b = socket.socketpair()
    try:
        a.sendall(b"\x00\x00")          # 2 of 4 header bytes
        a.close()
        with pytest.raises(TornFrame):
            recv_frame(b)
    finally:
        b.close()


@pytest.mark.parametrize("dtype,maker", [
    ("uint32", lambda: np.arange(12, dtype=np.uint32).reshape(3, 4)),
    ("float32", lambda: np.linspace(-1, 1, 7, dtype=np.float32)),
    ("bool", lambda: np.array([True, False, True])),
    ("bfloat16", lambda: None),          # built below via ml_dtypes
])
def test_array_wire_roundtrip(dtype, maker):
    if dtype == "bfloat16":
        import ml_dtypes
        a = np.arange(6).astype(ml_dtypes.bfloat16).reshape(2, 3)
    else:
        a = maker()
    back = decode_array(encode_array(a))
    assert str(back.dtype) == str(a.dtype)
    assert back.shape == a.shape
    assert back.tobytes() == a.tobytes()


# ---------------------------------------------------------------------------
# Hash ring
# ---------------------------------------------------------------------------

def test_ring_deterministic_and_covering():
    r1, r2 = HashRing(4), HashRing(4)
    tenants = [f"tenant/{i:05d}" for i in range(512)]
    assert [r1.owner(t) for t in tenants] == [r2.owner(t) for t in tenants]
    owners = {r1.owner(t) for t in tenants}
    assert owners == {0, 1, 2, 3}        # every shard gets traffic
    # peer preference: all other shards, no self, deterministic order
    for s in range(4):
        assert r1.peers(s) == [(s + k) % 4 for k in range(1, 4)]
        assert s not in r1.peers(s)


def test_ring_single_shard():
    ring = HashRing(1)
    assert ring.owner("anyone") == 0
    assert ring.peers(0) == []


# ---------------------------------------------------------------------------
# Fault plans
# ---------------------------------------------------------------------------

def test_fault_plan_parse_and_json_roundtrip():
    plan = FaultPlan.parse("kill@512, hang@40#1, slow@600~0.25, drop@7")
    kinds = [s.kind for s in plan.specs]
    assert kinds == ["kill", "hang", "slow", "drop"]
    assert plan.specs[1].shard == 1
    assert plan.specs[2].seconds == 0.25
    assert FaultPlan.from_json(plan.to_json()) == plan
    assert FaultPlan.parse(plan.to_json()) == plan    # JSON form accepted
    assert not FaultPlan.parse("")                     # empty plan
    with pytest.raises(ValueError):
        FaultPlan.parse("explode@3")


def test_fault_plan_seeded_replays():
    p1 = FaultPlan.seeded(7, burst=1024, kinds=("kill", "drop"), count=3)
    p2 = FaultPlan.seeded(7, burst=1024, kinds=("kill", "drop"), count=3)
    assert p1 == p2 and len(p1.specs) == 3
    assert all(256 <= s.index < 768 for s in p1.specs)
    assert FaultPlan.seeded(8, burst=1024, kinds=("kill", "drop"),
                            count=3) != p1


def test_injector_fires_each_spec_once():
    inj = FaultInjector(FaultPlan.parse("kill@24,drop@24#1"))
    assert inj.fire(1, 24).kind == "kill"   # shard-agnostic spec first
    assert inj.fire(1, 24).kind == "drop"
    assert inj.fire(1, 24) is None          # both consumed
    assert inj.fire(0, 99) is None
    assert rid_index("burst/000512") == 512
    assert rid_index("no-digits") is None
    assert rid_index(None) is None


# ---------------------------------------------------------------------------
# ShardHost over real sockets
# ---------------------------------------------------------------------------

def _req_msg(shard, rid, tenant="alice", n=16):
    return {"op": "request", "shard": shard, "rid": rid,
            "tenant": tenant, "shape": [n], "sampler": "bits",
            "dtype": "float32"}


def test_shardhost_serves_and_replays_idempotently(tmp_path):
    with ShardHost(3) as host:
        host.add_shard(0, str(tmp_path / "j.jsonl"))
        first = transport.rpc(host.address, _req_msg(0, "rid/001"))
        assert first["ok"] and first["replayed"] is False
        again = transport.rpc(host.address, _req_msg(0, "rid/001"))
        assert again["ok"] and again["replayed"] is True
        a1, a2 = decode_array(first["array"]), decode_array(again["array"])
        assert a1.tobytes() == a2.tobytes()     # never a second window
        # and a different rid gets different bytes (fresh window)
        other = transport.rpc(host.address, _req_msg(0, "rid/002"))
        assert decode_array(other["array"]).tobytes() != a1.tobytes()


def test_shardhost_not_owner_and_bad_op(tmp_path):
    with ShardHost(3) as host:
        host.add_shard(0, str(tmp_path / "j.jsonl"))
        r = transport.rpc(host.address, _req_msg(5, "rid/001"))
        assert not r["ok"] and r["kind"] == "not_owner"
        r = transport.rpc(host.address, {"op": "frobnicate"})
        assert not r["ok"] and r["kind"] == "bad_request"
        r = transport.rpc(host.address, {"op": "ping"})
        assert r["ok"] and r["shards"] == [0]


def test_shardhost_survives_torn_and_oversize_clients(tmp_path):
    """One client's torn write or hostile length must not wedge the
    accept loop: the NEXT connection is still served normally."""
    with ShardHost(3) as host:
        host.add_shard(0, str(tmp_path / "j.jsonl"))
        # torn mid-body
        s = socket.create_connection(host.address, timeout=10)
        s.sendall(struct.pack("!I", 500) + b'{"op": "requ')
        s.close()
        # torn mid-header
        s = socket.create_connection(host.address, timeout=10)
        s.sendall(b"\x00")
        s.close()
        # oversize declared length: server answers with an error frame
        # (best effort) and closes
        s = socket.create_connection(host.address, timeout=10)
        s.sendall(struct.pack("!I", transport.MAX_FRAME + 7))
        reply = recv_frame(s)
        assert reply is not None and reply["kind"] == "frame_too_large"
        assert recv_frame(s) is None            # then the conn closes
        s.close()
        # the host is unharmed: a well-behaved client is served
        r = transport.rpc(host.address, _req_msg(0, "rid/ok1"))
        assert r["ok"]


def test_shardhost_close_retires_transport_threads(tmp_path):
    """close() must not leak accept/conn threads into the embedding
    process: blocked accept()/recv() are not woken by a plain close(2)
    on Linux, so the host has to poll the listener and shut down idle
    connections explicitly."""
    host = ShardHost(3)
    host.add_shard(0, str(tmp_path / "j.jsonl"))
    assert transport.rpc(host.address, {"op": "ping"})["ok"]
    idle = socket.create_connection(host.address, timeout=10)
    time.sleep(0.3)                 # let the conn thread park in recv
    host.close()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        left = [t for t in threading.enumerate()
                if t.name.startswith("shardhost") and t.is_alive()]
        if not left:
            break
        time.sleep(0.05)
    assert not left, [t.name for t in left]
    idle.close()


def test_shardhost_drop_fault_retry_is_bit_identical(tmp_path):
    """A drop-frame fault serves+journals but never replies; the retry
    must be answered by replay with exactly the journaled bytes."""
    inj = FaultInjector(FaultPlan.parse("drop@7"))
    with ShardHost(3, injector=inj) as host:
        host.add_shard(0, str(tmp_path / "j.jsonl"))
        s = socket.create_connection(host.address, timeout=30)
        send_frame(s, _req_msg(0, "rid/007"))
        with pytest.raises((TornFrame, OSError)) as _:
            if recv_frame(s) is None:           # clean-EOF variant
                raise TornFrame("dropped")
        s.close()
        retry = transport.rpc(host.address, _req_msg(0, "rid/007"))
        assert retry["ok"] and retry["replayed"] is True
        served = decode_array(retry["array"])
        replayed = audit.replay(str(tmp_path / "j.jsonl"), seed=3)
        assert served.tobytes() == replayed["rid/007"].tobytes()


# ---------------------------------------------------------------------------
# Journal locking (the fencing primitive)
# ---------------------------------------------------------------------------

def test_journal_exclusive_lock(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j1 = Journal(path)
    j1.append_window("c", 0, 8)
    j1.flush()
    # a second writer in another PROCESS is refused while j1 lives
    # (flock is per-open-file, so the check must cross processes)
    code = ("import sys\n"
            "from repro.service.audit import Journal, JournalLockedError\n"
            "try:\n"
            f"    Journal({path!r})\n"
            "except JournalLockedError:\n"
            "    sys.exit(42)\n"
            "sys.exit(0)\n")
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    rc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                        env=env, timeout=120).returncode
    assert rc == 42, "second writer must raise JournalLockedError"
    # a readonly view is always allowed
    ro = Journal(path, readonly=True)
    assert len(ro.windows()) == 1
    # close releases the lock: the next writer proceeds
    j1.close()
    j2 = Journal(path)
    assert len(j2.windows()) == 1
    j2.close()


def test_adopt_refused_while_owner_lives(tmp_path):
    """Fence-gated hedging: adoption reports ``locked`` while the
    journal's owner still holds the flock (cross-process)."""
    path = str(tmp_path / "j.jsonl")
    code = ("import time, sys\n"
            "from repro.service.audit import Journal\n"
            f"j = Journal({path!r})\n"
            "j.append_window('c', 0, 8)\n"
            "j.flush()\n"
            "print('locked', flush=True)\n"
            "time.sleep(300)\n")
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    owner = subprocess.Popen([sys.executable, "-c", code], cwd=REPO,
                             env=env, stdout=subprocess.PIPE, text=True)
    try:
        assert owner.stdout.readline().strip() == "locked"
        with ShardHost(3) as host:
            reply = host._handle_adopt({"shard": 1, "journal": path})
            assert not reply["ok"] and reply["kind"] == "locked"
            # fence the owner (SIGKILL) -> the flock drops -> adoption
            # succeeds and the journaled window is fenced off
            owner.kill()
            owner.wait(timeout=30)
            reply = host._handle_adopt({"shard": 1, "journal": path})
            assert reply["ok"]
            assert 1 in host.shards()
    finally:
        if owner.poll() is None:
            owner.kill()
            owner.wait(timeout=30)


# ---------------------------------------------------------------------------
# Fleet end-to-end (subprocess shards over TCP)
# ---------------------------------------------------------------------------

BURST, TENANTS, SEED = 64, 16, 0


@pytest.mark.parametrize("platforms", [None, "", "tpu", "tpu,cpu"])
def test_fleet_refuses_to_spawn_from_a_tpu_process(platforms, tmp_path,
                                                   monkeypatch):
    """A chip belongs to one process: shards that inherit a platform list
    allowing a TPU would reach for one chip, so the fleet refuses."""
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    cfg = FleetConfig(num_shards=2, seed=SEED,
                      journal_dir=str(tmp_path / "j"))
    with pytest.raises(RuntimeError, match="one process at a time"):
        Fleet(cfg)
    assert not (tmp_path / "j").exists()   # refused before any set-up


def _fleet_digest(tmp_path, name, fault_plan, **client_kw):
    cfg = FleetConfig(num_shards=2, seed=SEED,
                      journal_dir=str(tmp_path / name))
    reqs = make_requests(burst=BURST, tenants=TENANTS, seed=SEED)
    with Fleet(cfg, fault_plan) as fleet:
        client = fleet.client(**client_kw)
        responses = run_fleet_burst(client, reqs)
        stats = client.stats()
        client.close()
        journals = fleet.journals()
    assert len(responses) == BURST
    return audit.response_digest(responses), stats, journals


@pytest.mark.slow
def test_fleet_kill_midburst_digest_equality(tmp_path):
    """The headline failover guarantee: kill a shard mid-burst; the
    surviving peer fences its journal, adopts its tenant regions, and
    the full response set is BIT-IDENTICAL to the no-fault run."""
    baseline, base_stats, _ = _fleet_digest(tmp_path, "nofault",
                                            FaultPlan())
    assert base_stats["failovers"] == 0
    killed, kill_stats, journals = _fleet_digest(
        tmp_path, "kill", FaultPlan.parse(f"kill@{BURST // 2}"))
    assert killed == baseline
    assert kill_stats["failovers"] == 1
    assert kill_stats["recovery_ms"] is not None
    # the union of the shard journals replays the whole burst
    replayed = {}
    for path in journals.values():
        replayed.update(audit.replay(path, seed=SEED))
        audit.verify_ledger_disjoint(Journal(path, readonly=True))
    assert len(replayed) == BURST
    assert audit.response_digest(replayed) == baseline


def test_fleet_json_wire_serves_the_binary_bytes(tmp_path):
    """Wire v1 (JSON) and v2 (binary) are payload-transparent end to
    end: the same burst over a fleet digests the same either way."""
    binary, _, _ = _fleet_digest(tmp_path, "binary", FaultPlan())
    json_v1, stats, _ = _fleet_digest(tmp_path, "json", FaultPlan(),
                                      binary=False)
    assert json_v1 == binary
    assert stats["failovers"] == 0


@pytest.mark.slow
def test_fleet_hang_is_fenced_then_adopted(tmp_path):
    """A hung (alive but wedged) shard: adoption is refused while the
    flock is held, the client fences (SIGKILL) the owner, adoption then
    succeeds — and the bytes still match the no-fault run."""
    baseline, _, _ = _fleet_digest(tmp_path, "nofault", FaultPlan())
    hung, stats, _ = _fleet_digest(
        tmp_path, "hang", FaultPlan.parse(f"hang@{BURST // 2}"),
        deadline_s=8.0)
    assert hung == baseline
    assert stats["failovers"] == 1


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
