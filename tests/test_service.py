"""RandService: tenancy, coalescing, journal replay, drain.

The acceptance properties as executable tests:

  * tenant -> region derivation is injective and regions are pairwise
    disjoint across >= 10^4 sampled ids including adversarial
    near-collisions (property-tested),
  * a concurrent mixed burst (>= 512 requests, >= 10^3 tenants) is
    served with ZERO counter-window overlap (ledger-verified on both
    the live service and the raw journal), with the coalescer issuing
    <= 10% as many engine/lease calls as requests,
  * journal replay after a restart — including a simulated mid-request
    crash (torn journal tail) — reproduces every served byte
    bit-identically, and the restarted service's new windows stay
    disjoint from everything replayed,
  * shutdown is a graceful drain: queued requests are served, late
    submissions are refused, SIGINT or SIGTERM on
    ``python -m repro.service`` drains and exits cleanly, and
    ``drain(timeout=None)`` waits for completion rather than bailing.
"""
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Tuple

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.runtime import BlockService
from repro.service import (Coalescer, Journal, RandRequest, RandServer,
                           ServerConfig, ServiceClosed, TenantRegistry,
                           replay, tenant_region, verify_ledger_disjoint)
from repro.service.audit import response_digest
from repro.service.burst import make_requests, run_burst
from repro.service.frontend import (DEFAULT_MAX_ROWS as DEFAULT_ROWS,
                                    class_channel, request_rows)
from repro.service.tenants import REGION_BITS, QuotaExceeded

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bytes_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and str(a.dtype) == str(b.dtype) \
        and a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# tenants: injective derivation, disjoint regions, quotas
# ---------------------------------------------------------------------------

def _adversarial_ids():
    """Near-collision ids: shared prefixes/suffixes, whitespace, case,
    separator and unicode perturbations of the same stem."""
    stems = ["tenant-0", "user/42", ""]
    ids = set()
    for stem in stems:
        ids.update({stem, stem + " ", " " + stem, stem + "\x00",
                    stem + "0", "0" + stem, stem.upper(), stem * 2,
                    stem + "é", stem[::-1]})
    ids.update(f"tenant-{i:04d}" for i in range(64))   # one-digit deltas
    ids.update("x" * 200 + str(i) for i in range(64))  # long shared prefix
    return sorted(ids)


def test_tenant_region_injective_over_10k_ids():
    ids = [f"tenant/{i}" for i in range(10_000)] + _adversarial_ids()
    bases = [tenant_region(i) for i in ids]
    assert len(set(bases)) == len(ids), "region base collision"
    size = 1 << REGION_BITS
    assert all(b % size == 0 for b in bases)
    # disjointness: bases are distinct multiples of the region size, so
    # sorted regions [b, b + size) cannot overlap
    srt = sorted(bases)
    assert all(srt[k] + size <= srt[k + 1] for k in range(len(srt) - 1))


def test_registry_rejects_region_collision_detectably():
    reg = TenantRegistry(region_bits=0)  # every hash value is a region
    reg.register("a")
    # region_bits=0 makes collisions FINDABLE, not likely; simulate one
    reg._by_region[tenant_region("b", 0)] = "other"
    from repro.service.tenants import TenantCollisionError
    with pytest.raises(TenantCollisionError):
        reg.register("b")


@settings(max_examples=64, deadline=None)
@given(st.integers(0, 2 ** 64 - 1), st.integers(0, 2 ** 64 - 1))
def test_tenant_region_property(a, b):
    """Distinct ids -> disjoint regions; same id -> same region (pure)."""
    ia, ib = f"t{a}", f"t{b}"
    ra, rb = tenant_region(ia), tenant_region(ib)
    assert ra == tenant_region(ia)
    size = 1 << REGION_BITS
    if ia == ib:
        assert ra == rb
    elif ra != rb:
        lo, hi = min(ra, rb), max(ra, rb)
        assert lo + size <= hi  # bases are multiples of size -> disjoint


@settings(max_examples=32, deadline=None)
@given(st.integers(1, 100), st.integers(1, 100))
def test_quota_accounting_property(quota, ask):
    reg = TenantRegistry(default_quota=quota)
    if ask <= quota:
        assert reg.charge("t", ask).served == ask
        left = quota - ask
        with pytest.raises(QuotaExceeded):
            reg.charge("t", left + 1)
        assert reg.get("t").served == ask  # failed charge consumed nothing
    else:
        with pytest.raises(QuotaExceeded):
            reg.charge("t", ask)


def test_request_rows_quantization():
    assert request_rows(1) == 8
    assert request_rows(8) == 8
    assert request_rows(9) == 16
    assert request_rows(2048) == 2048
    assert request_rows(10 ** 9) == 2048          # clamped to max_rows
    assert request_rows(4096, max_rows=4096) == 4096
    with pytest.raises(ValueError):
        request_rows(0)


# ---------------------------------------------------------------------------
# coalescer: determinism, replay parity, mixed classes
# ---------------------------------------------------------------------------

def _mixed_requests(n=24):
    cases = [("bits", "float32"), ("uniform", "float32"),
             ("uniform", "bfloat16"), ("normal", "float32"),
             ("bernoulli(0.25)", "float32")]
    reqs = []
    for i in range(n):
        sampler, dtype = cases[i % len(cases)]
        shape = (3 + i,) if i % 2 else (2 + i % 5, 7 + i)
        reqs.append(RandRequest(f"t{i % 7}", shape, sampler, dtype,
                                rid=f"r{i:03d}"))
    return reqs


def _flush_once(seed=13):
    journal = Journal()
    svc = BlockService(seed, backend="xla")
    co = Coalescer(svc, TenantRegistry(), journal=journal, backend="xla")
    got, asgs, errs = co.flush(_mixed_requests())
    assert not errs
    return got, asgs, journal, svc, co


def test_coalescer_deterministic_and_replay_parity():
    got1, _, journal, svc, co = _flush_once()
    got2, _, _, _, _ = _flush_once()
    assert response_digest(got1) == response_digest(got2)
    # replay regenerates per-request stand-alone plans: a gathered-column
    # slice of the fused batch must equal the request's own plan
    rep = replay(journal, seed=13, backend="xla")
    assert set(rep) == set(got1)
    for rid in rep:
        assert _bytes_equal(got1[rid], rep[rid]), rid
    verify_ledger_disjoint(svc)
    verify_ledger_disjoint(journal)
    # one lease + one engine call per (class) microbatch
    s = co.stats()
    assert s["engine_calls"] == s["lease_calls"] == 5


def test_coalescer_response_shapes_and_dtypes():
    got, asgs, _, _, _ = _flush_once()
    by_rid = {a.rid: a for a in asgs}
    for req in _mixed_requests():
        a = np.asarray(got[req.rid])
        assert a.shape == req.shape
        if req.sampler == "bits":
            assert a.dtype == np.uint32
        elif req.sampler.startswith("bernoulli"):
            assert a.dtype == np.bool_
        elif req.out_dtype == "float32":
            assert a.dtype == np.float32
        asg = by_rid[req.rid]
        assert len(asg.tags) == -(-req.num_samples // asg.rows)


def test_coalescer_tags_disjoint_within_flush():
    _, asgs, _, _, _ = _flush_once()
    per_channel = {}
    for a in asgs:
        seen = per_channel.setdefault((a.channel, a.lo), set())
        for t in a.tags:
            assert t not in seen, "column tag double-assigned"
            seen.add(t)


def test_successive_flushes_get_fresh_windows():
    journal = Journal()
    svc = BlockService(5, backend="xla")
    co = Coalescer(svc, TenantRegistry(), journal=journal, backend="xla")
    reqs = [RandRequest("t", (32,), rid="a")]
    got1, asg1, _ = co.flush(reqs)
    got2, asg2, _ = co.flush([RandRequest("t", (32,), rid="b")])
    assert asg1[0].lo != asg2[0].lo
    assert not _bytes_equal(got1["a"], got2["b"])
    verify_ledger_disjoint(journal)


def test_rejected_request_consumes_no_quota():
    """Admission checks run before charge(): a request refused for
    region capacity must leave the tenant's meter untouched."""
    reg = TenantRegistry(region_bits=2)       # 4 slots per tenant
    svc = BlockService(5, backend="xla")
    co = Coalescer(svc, reg, backend="xla")
    too_big = 5 * DEFAULT_ROWS + 1            # needs 6 columns > 4 slots
    got, _, errs = co.flush([
        RandRequest("t", (too_big,), rid="big"),
        RandRequest("t", (16,), rid="small")])
    assert isinstance(errs["big"], QuotaExceeded)
    assert reg.get("t").served == 16          # only the served request
    assert got["small"].shape == (16,)


def test_registry_refund_restores_quota():
    reg = TenantRegistry(default_quota=100)
    reg.charge("t", 80)
    reg.refund("t", 80)
    assert reg.get("t").served == 0
    assert reg.charge("t", 100).served == 100


def test_deferred_start_is_count_deterministic():
    """start=False + enqueue-all + start(): batch composition is pure
    chunks of max_batch, so two runs agree byte-for-byte even with a
    watermark deadline of ~0."""
    digests = []
    for _ in range(2):
        srv = RandServer(53, config=ServerConfig(max_batch=5,
                                                 max_delay_s=0.0001),
                         start=False)
        reqs = _mixed_requests(17)
        futs = [srv.submit(r) for r in reqs]
        srv.start()
        got = {r.rid: f.result(timeout=60) for r, f in zip(reqs, futs)}
        srv.shutdown()
        digests.append(response_digest(got))
    assert digests[0] == digests[1]


def test_journal_newline_less_tail_survives_reopen(tmp_path):
    """Crash after the closing brace but before the newline: the record
    is kept AND the next append starts on a fresh line."""
    path = str(tmp_path / "j.jsonl")
    j = Journal(path)
    j.append_window("c", 0, 8)
    j.flush()
    j.close()
    with open(path, "r+b") as f:              # chop the trailing newline
        f.truncate(os.path.getsize(path) - 1)
    j2 = Journal(path)
    assert len(j2.windows()) == 1
    j2.append_window("c", 8, 16)
    j2.flush()
    j2.close()
    j3 = Journal(path)                        # both records, two lines
    assert [w["lo"] for w in j3.windows()] == [0, 8]


def test_server_honors_caller_supplied_empty_registry():
    """An empty registry is falsy (__len__) — the server must keep the
    caller's instance anyway, or quotas silently stop applying."""
    reg = TenantRegistry(default_quota=32)
    with RandServer(7, config=ServerConfig(max_batch=1),
                    registry=reg) as srv:
        assert srv.registry is reg
        srv.request("q", (16,))
        with pytest.raises(QuotaExceeded):
            srv.request("q", (32,))
        assert reg.get("q").served == 16


def test_restarted_server_does_not_reuse_journaled_rids(tmp_path):
    path = str(tmp_path / "j.jsonl")
    srv = RandServer(7, config=ServerConfig(max_batch=1),
                     journal=Journal(path))
    a = srv.request("t", (24,))            # auto-rid r00000001
    srv.shutdown()
    j2 = Journal(path)
    srv2 = RandServer(7, config=ServerConfig(max_batch=1), journal=j2)
    b = srv2.request("t", (24,))           # must NOT collide with run 1
    srv2.shutdown()
    rep = replay(Journal(path), seed=7)
    assert len(rep) == 2
    assert any(_bytes_equal(v, a) for v in rep.values())
    assert any(_bytes_equal(v, b) for v in rep.values())


def test_quota_rejection_is_isolated():
    svc = BlockService(5, backend="xla")
    co = Coalescer(svc, TenantRegistry(default_quota=100), backend="xla")
    reqs = [RandRequest("small", (64,), rid="ok"),
            RandRequest("big", (101,), rid="over"),
            RandRequest("small2", (64,), rid="ok2")]
    got, _, errs = co.flush(reqs)
    assert set(got) == {"ok", "ok2"}
    assert isinstance(errs["over"], QuotaExceeded)


# ---------------------------------------------------------------------------
# server: the acceptance burst, pools, crash replay, drain
# ---------------------------------------------------------------------------

def test_acceptance_burst_1024_tenants(tmp_path):
    """>= 512 concurrent mixed requests from >= 10^3 distinct tenants:
    zero window overlap (ledger-verified), <= 10% calls/request,
    bit-identical replay after restart."""
    burst, tenants = 1024, 1024
    path = str(tmp_path / "journal.jsonl")
    cfg = ServerConfig(max_batch=256, max_delay_s=0.25,
                       hot_classes=(("uniform", "float32"),))
    srv = RandServer(17, config=cfg, journal=Journal(path))
    reqs = make_requests(burst=burst, tenants=tenants, seed=17)
    got = run_burst(srv, reqs, submit_threads=16)
    assert len(got) == burst
    assert len(srv.registry) >= 1000
    stats = srv.stats()
    assert stats["requests_failed"] == 0
    assert stats["calls_per_request"] <= 0.10, stats
    verify_ledger_disjoint(srv.block_service)
    verify_ledger_disjoint(srv.journal)
    srv.shutdown()

    # restart: replay the journal in a fresh context -> bit-identical
    j2 = Journal(path)
    rep = replay(j2, seed=17)
    assert set(rep) == set(got)
    for rid in rep:
        assert _bytes_equal(got[rid], rep[rid]), rid
    # ...and a restarted server leases strictly disjoint new windows
    srv2 = RandServer(17, config=cfg, journal=j2)
    run_burst(srv2, make_requests(burst=32, tenants=16, seed=99,
                                  rid_prefix="post-restart"))
    verify_ledger_disjoint(srv2.journal)
    srv2.shutdown()


def test_pool_serves_hot_class_with_replay_parity():
    cfg = ServerConfig(max_batch=16, max_delay_s=0.1, pool_rows=128,
                       pool_cols=8, hot_classes=(("uniform", "float32"),))
    journal = Journal()
    with RandServer(23, config=cfg, journal=journal) as srv:
        reqs = [RandRequest("t/pool", (50 + i,), "uniform", "float32",
                            rid=f"p{i}") for i in range(12)]
        got = run_burst(srv, reqs)
        stats = srv.stats()
        assert stats["pool_requests"] == 12
        verify_ledger_disjoint(srv.block_service)
    rep = replay(journal, seed=23)
    for rid in got:
        assert _bytes_equal(got[rid], rep[rid]), rid
    pool_wins = [w for w in journal.windows()
                 if w["channel"].startswith("service/pool/")]
    assert pool_wins, "pool windows must be journaled"


def test_pool_donation_serves_identical_responses():
    """The donated standing pool (and its fused variant) must be
    response-for-response byte-identical to the plain pool: donation
    only changes WHERE blocks are written, never what they hold."""
    def burst(donate, fuse=1):
        cfg = ServerConfig(max_batch=8, max_delay_s=0.05, pool_rows=64,
                           pool_cols=8, pool_depth=2, pool_donate=donate,
                           pool_fuse=fuse,
                           hot_classes=(("uniform", "float32"),))
        with RandServer(41, config=cfg) as srv:
            reqs = [RandRequest("t/don", (40 + i,), "uniform", "float32",
                                rid=f"d{i}") for i in range(24)]
            got = run_burst(srv, reqs)
            assert srv.stats()["pool_requests"] == 24
            verify_ledger_disjoint(srv.block_service)
        return got

    plain = burst(donate=False)
    for tag, got in (("donated", burst(donate=True)),
                     ("donated+fused", burst(donate=True, fuse=2))):
        for rid in plain:
            assert _bytes_equal(plain[rid], got[rid]), (tag, rid)


def test_mid_request_crash_torn_journal_replays(tmp_path):
    """Kill mid-write: truncate the journal to a torn final line — every
    COMPLETE record must still replay bit-identically."""
    path = str(tmp_path / "journal.jsonl")
    srv = RandServer(31, config=ServerConfig(max_batch=8, max_delay_s=0.05),
                     journal=Journal(path))
    got = run_burst(srv, _mixed_requests())
    srv.shutdown()
    raw = open(path, "rb").read()
    lines = raw.splitlines(keepends=True)
    keep = len(lines) * 2 // 3
    torn = b"".join(lines[:keep]) + lines[keep][: len(lines[keep]) // 2]
    with open(path, "wb") as f:
        f.write(torn)
    j = Journal(path)          # torn trailing line is dropped, not fatal
    rep = replay(j, seed=31)
    assert 0 < len(rep) < len(got)
    for rid in rep:
        assert _bytes_equal(got[rid], rep[rid]), rid
    # restart on the torn journal: new windows disjoint from replayed
    srv2 = RandServer(31, config=ServerConfig(max_batch=8), journal=j)
    run_burst(srv2, [RandRequest("t9", (64,), rid="post-crash")])
    verify_ledger_disjoint(srv2.journal)
    srv2.shutdown()


def test_restart_reserves_journaled_windows(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    srv = RandServer(41, config=ServerConfig(max_batch=4),
                     journal=Journal(path))
    run_burst(srv, [RandRequest("t", (256,), rid="one")])
    state1 = srv.ledger_state()["channels"]
    srv.shutdown()
    srv2 = RandServer(41, config=ServerConfig(max_batch=4),
                      journal=Journal(path))
    chan = class_channel("bits", "float32")
    restored = srv2.ledger_state()["channels"][chan]["committed"]
    assert restored == state1[chan]["committed"]
    srv2.shutdown()


def test_graceful_drain_serves_queued_then_refuses():
    srv = RandServer(7, config=ServerConfig(max_batch=64, max_delay_s=5.0))
    futs = [srv.submit(RandRequest("t", (16,), rid=f"d{i}"))
            for i in range(8)]
    srv.shutdown()             # drain must flush the deadline-waiting batch
    assert all(f.result(timeout=30).shape == (16,) for f in futs)
    with pytest.raises(ServiceClosed):
        srv.submit(RandRequest("t", (16,)))


def test_duplicate_rid_in_one_batch_fails_cleanly():
    with RandServer(7, config=ServerConfig(max_batch=4,
                                           max_delay_s=0.5)) as srv:
        f1 = srv.submit(RandRequest("t", (8,), rid="dup"))
        f2 = srv.submit(RandRequest("t", (8,), rid="dup"))
        ok, bad = ((f1, f2) if f2.exception(timeout=30) is not None
                   else (f2, f1))
        assert ok.result(timeout=30).shape == (8,)
        assert isinstance(bad.exception(timeout=30), ValueError)


def test_server_rejects_invalid_sampler_at_submit():
    with RandServer(7, config=ServerConfig(max_batch=1)) as srv:
        with pytest.raises(ValueError):
            srv.submit(RandRequest("t", (8,), sampler="nonsense"))


def test_journaled_rid_reuse_refused_at_submit():
    with RandServer(7, config=ServerConfig(max_batch=1),
                    journal=Journal()) as srv:
        assert srv.submit(RandRequest("t", (8,), rid="x")).result(30) \
            .shape == (8,)
        with pytest.raises(ValueError, match="already used"):
            srv.submit(RandRequest("t", (8,), rid="x"))


def test_partial_class_failure_preserves_other_classes():
    """One class's engine failure fails ITS requests only; the other
    class is served and its tenants are the only ones billed."""
    reg = TenantRegistry()
    svc = BlockService(5, backend="xla")
    co = Coalescer(svc, reg, backend="xla")
    boom = RuntimeError("backend down")

    def broken(*a, **k):
        raise boom
    good = [RandRequest("a", (16,), "bits", rid="ok")]
    bad = [RandRequest("b", (16,), "uniform", rid="bad")]
    orig = co._window_fn

    def selective(purpose, rows, cols, sampler, dtype):
        return broken if sampler == "uniform" else orig(
            purpose, rows, cols, sampler, dtype)
    co._window_fn = selective
    got, _, errs = co.flush(good + bad)
    assert got["ok"].shape == (16,)
    assert errs["bad"] is boom
    assert reg.get("a").served == 16
    assert reg.get("b").served == 0          # refunded on failure


def test_submit_backpressure_does_not_deadlock_drain():
    """A full queue on a never-started server must not wedge drain()."""
    srv = RandServer(7, config=ServerConfig(max_batch=1, queue_depth=2),
                     start=False)
    futs = [srv.submit(RandRequest("t", (8,), rid=f"q{i}"))
            for i in range(2)]                  # queue now full
    blocked = {}

    def third():
        try:
            blocked["fut"] = srv.submit(RandRequest("t", (8,), rid="q2"))
        except ServiceClosed as e:
            blocked["err"] = e
    th = threading.Thread(target=third, daemon=True)
    th.start()
    time.sleep(0.1)                             # let it hit the full queue
    srv.shutdown(timeout=30)                    # must not deadlock
    th.join(timeout=30)
    assert not th.is_alive()
    assert all(f.result(30).shape == (8,) for f in futs)
    assert "err" in blocked or blocked["fut"].result(30).shape == (8,)


def _drain_via_signal(sig) -> Tuple[int, str]:
    """Run ``python -m repro.service --linger``, deliver ``sig`` once
    ready, return (returncode, output)."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.service", "--burst", "16",
         "--tenants", "4", "--linger", "120"],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 180
        ready = False
        for line in proc.stdout:
            if "ready (SIGINT/SIGTERM to drain)" in line:
                ready = True
                break
            assert time.time() < deadline, "server never became ready"
        assert ready
        proc.send_signal(sig)
        out, _ = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    return proc.returncode, out


def test_sigint_graceful_drain():
    """``python -m repro.service --linger``: SIGINT drains and exits 0."""
    rc, out = _drain_via_signal(signal.SIGINT)
    assert rc == 0, out
    assert "drained" in out


def test_sigterm_graceful_drain():
    """SIGTERM (what supervisors and ``fleet.Fleet.stop`` send) takes
    the same graceful-drain path as SIGINT."""
    rc, out = _drain_via_signal(signal.SIGTERM)
    assert rc == 0, out
    assert "drained" in out


def test_drain_timeout_none_waits_forever():
    """``drain(timeout=None)`` means "wait until drained", not "give
    up immediately" — it must return True with all work served."""
    srv = RandServer(11, config=ServerConfig(max_batch=4), start=False)
    futs = [srv.submit(RandRequest("t", (64,), rid=f"n{i}"))
            for i in range(8)]
    assert srv.drain(timeout=None) is True
    assert all(f.result(30).shape == (64,) for f in futs)
    # a second drain is idempotent and still reports drained
    assert srv.drain(timeout=None) is True


# ---------------------------------------------------------------------------
# scale: million-tenant churn, bounded class-keyed caching, dist classes
# ---------------------------------------------------------------------------

def test_million_tenant_churn_regions_disjoint_and_serving():
    """10**6 tenant registrations (idempotent churn included), sampled
    region disjointness at scale, then live mixed-distribution serving
    from tenants spread across the whole population — registration cost
    must stay O(1) per id and the region map collision-free."""
    reg = TenantRegistry()
    n = 1_000_000
    step = 997  # co-prime stride: re-register every ~1000th id (churn)
    for i in range(n):
        reg.register(f"churn/{i:07d}")
        if i % step == 0:
            reg.register(f"churn/{i % 4096:07d}")  # idempotent re-touch
    assert len(reg) == n
    # sampled disjointness: 20k evenly-spaced ids -> sorted region bases
    # must be distinct multiples of the region size with no overlap
    size = 1 << REGION_BITS
    bases = sorted(tenant_region(f"churn/{i:07d}")
                   for i in range(0, n, n // 20_000))
    assert all(b % size == 0 for b in bases)
    assert all(bases[k] + size <= bases[k + 1]
               for k in range(len(bases) - 1))
    # the registry still serves: mixed distribution classes from tenants
    # sampled across the population, replay-parity checked
    journal = Journal()
    svc = BlockService(29, backend="xla")
    co = Coalescer(svc, reg, journal=journal, backend="xla")
    classes = [("exponential(1.5)", "float32"), ("poisson(3.5)", "bfloat16"),
               ("gamma(2.5)", "float32"),
               ("categorical[0.5,0.25,0.125,0.125]", "float32")]
    reqs = [RandRequest(f"churn/{(j * 77777) % n:07d}", (9 + j,),
                        *classes[j % 4], rid=f"m{j:03d}")
            for j in range(16)]
    got, _, errs = co.flush(reqs)
    assert not errs
    rep = replay(journal, seed=29)
    for rid in got:
        assert _bytes_equal(got[rid], rep[rid]), rid
    verify_ledger_disjoint(journal)


def test_window_fn_cache_bounded_under_class_churn():
    """Every distinct (rows, sampler, dtype) request class keys a jitted
    window fn; unbounded churn (e.g. per-tenant categorical weights)
    must not grow the cache without limit — the coalescer's LRU keeps it
    at ``window_fn_cache_size`` while staying byte-deterministic across
    evict/recompile cycles."""
    journal = Journal()
    svc = BlockService(31, backend="xla")
    co = Coalescer(svc, TenantRegistry(), journal=journal, backend="xla",
                   window_fn_cache_size=4)
    # 12 distinct classes > 4 cache slots, flushed twice (second pass
    # re-derives evicted fns)
    reqs = [RandRequest("t/cache", (8,), f"exponential({1.0 + 0.25 * k})",
                        "float32", rid=f"c{k:02d}")
            for k in range(12)]
    got1, _, errs = co.flush(reqs)
    assert not errs
    assert co.stats()["window_fn_cache"] <= 4
    assert co.stats()["window_fn_cache_max"] == 4
    # replay sees every class, including ones whose fn was evicted
    rep = replay(journal, seed=31)
    for rid in got1:
        assert _bytes_equal(got1[rid], rep[rid]), rid
    with pytest.raises(ValueError, match="window_fn_cache_size"):
        Coalescer(svc, TenantRegistry(), window_fn_cache_size=0)


def test_burst_mixed_distribution_classes_replay(tmp_path):
    """The PR's acceptance criterion in miniature: a burst spanning all
    four distribution classes (plus bf16 poisson) journals and replays
    bit-identically — the shaped-sampler transforms must be stable
    between the coalescer's batched executables and the auditor's
    per-request ones."""
    path = str(tmp_path / "dist.jsonl")
    srv = RandServer(37, config=ServerConfig(max_batch=64, max_delay_s=0.2),
                     journal=Journal(path))
    classes = [("exponential(0.75)", "float32"), ("poisson(7.25)", "bfloat16"),
               ("gamma(3.5)", "float32"), ("gamma(1.0)", "bfloat16"),
               ("categorical[3,1,1,3]", "float32"),
               ("poisson(0.0)", "float32")]
    reqs = [RandRequest(f"d/{i % 13:02d}",
                        (i % 3 + 1, 11 + i) if i % 2 else (23 + 7 * i,),
                        *classes[i % len(classes)], rid=f"x{i:03d}")
            for i in range(96)]
    got = run_burst(srv, reqs, submit_threads=8)
    assert srv.stats()["requests_failed"] == 0
    srv.shutdown()
    rep = replay(Journal(path), seed=37)
    assert set(rep) == set(got)
    for rid in rep:
        assert _bytes_equal(got[rid], rep[rid]), rid
    # shaped responses stay in-domain through the service path
    for r in reqs:
        a = np.asarray(got[r.rid], dtype=np.float64)
        if r.sampler.startswith("poisson(0.0"):
            assert np.all(a == 0.0)
        elif r.sampler.startswith("categorical"):
            assert a.min() >= 0 and a.max() <= 3
        else:
            assert np.all(np.isfinite(a)) and a.min() >= 0.0
