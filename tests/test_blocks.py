"""Block delivery layer: lease accounting (disjoint windows, two-phase
ledger, checkpoint/restore), double-buffered producers, the 2-D
(host, stream) mesh fan-out, and the BlockService-fed training path."""
import json
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, stream as tstream
from repro.kernels import ops
from repro.runtime import BlockProducer, BlockService, Lease, LeaseError
from repro.runtime import blocks as blocks_mod


# ---------------------------------------------------------------------------
# lease accounting
# ---------------------------------------------------------------------------

def test_sequential_leases_are_consecutive_and_disjoint():
    svc = BlockService(seed=1)
    svc.open("a", num_streams=4)
    l1 = svc.lease("a", 10)
    l2 = svc.lease("a", 6)
    assert (l1.lo, l1.hi) == (0, 10)
    assert (l2.lo, l2.hi) == (10, 16)


@pytest.mark.parametrize("at", [0, 5, 9, 15])
def test_overlapping_lease_rejected_reserved_and_committed(at):
    svc = BlockService(seed=1)
    svc.open("a")
    l1 = svc.lease("a", 10)          # [0, 10) reserved
    l2 = svc.lease("a", 6)           # [10, 16) reserved
    svc.commit(l1)                   # [0, 10) committed
    with pytest.raises(LeaseError, match="overlaps"):
        svc.lease("a", 1, at=at)
    # non-overlapping explicit window is fine
    l3 = svc.lease("a", 4, at=100)
    assert (l3.lo, l3.hi) == (100, 104)


def test_release_reopens_window():
    svc = BlockService(seed=1)
    svc.open("a")
    lease = svc.lease("a", 8)
    svc.release(lease)
    again = svc.lease("a", 8, at=0)
    assert (again.lo, again.hi) == (0, 8)


def test_commit_requires_reservation():
    svc = BlockService(seed=1)
    svc.open("a")
    ghost = Lease(channel="a", lo=0, hi=4, service=svc)
    with pytest.raises(LeaseError, match="not reserved"):
        svc.commit(ghost)


def test_lease_validation():
    svc = BlockService(seed=1)
    with pytest.raises(KeyError, match="not open"):
        svc.lease("missing", 4)
    svc.open("a")
    with pytest.raises(ValueError, match="positive"):
        svc.lease("a", 0)


def test_channels_have_independent_ledgers():
    svc = BlockService(seed=1)
    svc.open("a")
    svc.open("b")
    svc.commit(svc.lease("a", 16))
    lb = svc.lease("b", 16)
    assert lb.lo == 0    # channel b unaffected by a's windows


# ---------------------------------------------------------------------------
# ledger checkpoint / restore
# ---------------------------------------------------------------------------

def test_ledger_snapshot_restores_midrun_bit_identically():
    svc = BlockService(seed=5)
    svc.open("a", num_streams=8)
    for _ in range(3):
        svc.commit(svc.lease("a", 16))
    snap = svc.ledger_state()
    # run continues past the snapshot ...
    l4 = svc.lease("a", 16)
    blk4 = np.asarray(svc.generate(l4))
    svc.commit(l4)
    # ... the process dies and restarts from the snapshot: the SAME
    # window is re-leased and regenerates the SAME bits.
    svc2 = BlockService(seed=5)
    svc2.open("a", num_streams=8)
    svc2.restore_ledger(snap)
    l4b = svc2.lease("a", 16)
    assert (l4b.lo, l4b.hi) == (l4.lo, l4.hi)
    assert np.array_equal(np.asarray(svc2.generate(l4b)), blk4)


def test_ledger_snapshot_excludes_reservations():
    svc = BlockService(seed=5)
    svc.open("a")
    svc.commit(svc.lease("a", 8))
    in_flight = svc.lease("a", 8)          # reserved, never committed
    snap = svc.ledger_state()
    assert snap["channels"]["a"]["committed"] == [[0, 8]]
    svc.restore_ledger(snap)
    replay = svc.lease("a", 8)
    assert (replay.lo, replay.hi) == (in_flight.lo, in_flight.hi)


def test_ledger_snapshot_is_json_roundtrippable():
    svc = BlockService(seed=5)
    svc.open("a")
    svc.commit(svc.lease("a", 4))
    snap = json.loads(json.dumps(svc.ledger_state()))
    svc2 = BlockService(seed=5)
    svc2.open("a")
    svc2.restore_ledger(snap)
    assert svc2.lease("a", 4).lo == 4


def test_committed_windows_merge():
    svc = BlockService(seed=5)
    svc.open("a")
    for _ in range(4):
        svc.commit(svc.lease("a", 8))
    assert svc.ledger_state()["channels"]["a"]["committed"] == [[0, 32]]


# ---------------------------------------------------------------------------
# generation parity: traced windows == static plans == stream API
# ---------------------------------------------------------------------------

def test_generate_matches_static_plan_and_stream():
    svc = BlockService(seed=42)
    svc.open("t", num_streams=8)
    lease = svc.lease("t", 16)
    svc.commit(svc.lease("t", 16))  # a second window, out of order is fine
    blk = np.asarray(svc.generate(lease))
    ref = np.asarray(engine.generate(lease.plan(), backend="ref"))
    assert np.array_equal(blk, ref)
    col = np.asarray(tstream.random_bits(lease.stream(3), (16,)))
    assert np.array_equal(col, blk[:, 3])


def test_generate_sampler_override():
    svc = BlockService(seed=42)
    svc.open("u", num_streams=4, sampler="uniform")
    lease = svc.lease("u", 8)
    u = np.asarray(svc.generate(lease))
    assert u.dtype == np.float32 and (u >= 0).all() and (u < 1).all()
    bits = np.asarray(svc.generate(lease, sampler="bits"))
    assert bits.dtype == np.uint32
    ref = np.asarray(engine.generate(lease.plan(sampler="bits"),
                                     backend="ref"))
    assert np.array_equal(bits, ref)


def _windows(kind, svc, name, length):
    """(lease, block) pairs of three delivery paths, all around 2**32 so
    the counter's high word changes inside or between windows."""
    if kind == "generate":                  # carry inside the window
        lease = svc.lease(name, length, at=2**32 - length // 2)
        return [(lease, svc.generate(lease))]
    if kind == "generate_many":             # carry between the windows
        leases = svc.lease_many(name, length, 2, at=2**32 - length)
        return list(zip(leases, svc.generate_many(leases)))
    with svc.producer(name, length, count=4, start=2**32 - 2 * length) as p:
        return [(lease, np.asarray(blk)) for lease, blk in p]


@pytest.mark.parametrize("kind", ["generate", "generate_many", "producer"])
def test_windows_across_the_counter_word_match_static_plan(kind):
    svc = BlockService(seed=31)
    svc.open("w", num_streams=8)
    got = _windows(kind, svc, "w", 16)
    assert got[0][0].lo < 2**32 < got[-1][0].hi
    for lease, blk in got:
        ref = np.asarray(engine.generate(lease.plan(), backend="ref"))
        assert np.array_equal(np.asarray(blk), ref)


def test_warm_window_dispatch_runs_no_eager_primitive(monkeypatch):
    """A warm plan-channel window is one call of its jitted program: the
    counter enters as host scalars, so nothing runs op by op (every
    ``apply_primitive`` looks up ``xla_primitive_callable``), and three
    windows, one past 2**32, reuse one executable."""
    from jax._src import dispatch
    svc = BlockService(seed=5)
    svc.open("d", num_streams=8)
    warm = svc.lease("d", 16)
    svc.generate(warm).block_until_ready()
    (fn,) = svc._window_fns.values()

    calls = []
    orig = dispatch.xla_primitive_callable

    def counting(prim, **params):
        calls.append(prim.name)
        return orig(prim, **params)
    monkeypatch.setattr(dispatch, "xla_primitive_callable", counting)
    leases = [svc.lease("d", 16), svc.lease("d", 16, at=2**32 + 16)]
    blocks = [svc.generate(lease) for lease in [warm] + leases]
    assert calls == []
    monkeypatch.undo()
    assert fn._cache_size() == 1
    for lease, blk in zip([warm] + leases, blocks):
        ref = np.asarray(engine.generate(lease.plan(), backend="ref"))
        assert np.array_equal(np.asarray(blk), ref)
    # host scalars and device scalars lower to one program
    args = svc._ctr_args(leases[-1].lo)
    assert [int(a) for a in args] == [1, 16]
    assert (fn.lower(*args).as_text()
            == fn.lower(*map(jnp.asarray, args)).as_text())


def test_take_commits_and_equal_length_leases_share_one_executable():
    svc = BlockService(seed=9)
    svc.open("t", num_streams=4)
    a = np.asarray(svc.take("t", 8))
    b = np.asarray(svc.take("t", 8))
    assert not np.array_equal(a, b)          # disjoint windows
    assert svc.ledger_state()["channels"]["t"]["committed"] == [[0, 16]]
    # one jitted window fn per (channel, length, sampler, dtype)
    assert len(svc._window_fns) == 1


def test_service_generates_through_mesh():
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()).reshape(1, 1), ("hosts", "streams"))
    svc = BlockService(seed=3, mesh=mesh)
    svc.open("m", num_streams=12)
    blk = np.asarray(svc.take("m", 16))
    plan = engine.make_plan(seed=3, num_streams=12, num_steps=16,
                            purpose=blocks_mod.channel_purpose("m"))
    assert np.array_equal(blk, np.asarray(engine.generate(plan,
                                                          backend="xla")))


# ---------------------------------------------------------------------------
# double-buffered producer
# ---------------------------------------------------------------------------

def test_producer_blocks_match_synchronous_generation():
    svc = BlockService(seed=7)
    svc.open("p", num_streams=8)
    with svc.producer("p", 16, count=4) as prod:
        got = [(lease, np.asarray(block)) for lease, block in prod]
    assert [lease.lo for lease, _ in got] == [0, 16, 32, 48]
    for lease, block in got:
        ref = np.asarray(engine.generate(lease.plan(), backend="xla"))
        assert np.array_equal(block, ref)
    # every handed-out window was committed at handoff
    assert svc.ledger_state()["channels"]["p"]["committed"] == [[0, 64]]


def test_producer_close_releases_prefetched_reservations():
    svc = BlockService(seed=7)
    svc.open("p", num_streams=4)
    prod = svc.producer("p", 8)
    next(prod)            # consume one block; ~depth more are in flight
    prod.close()
    # only the consumed window stays committed; reservations were dropped
    assert svc.ledger_state()["channels"]["p"]["committed"] == [[0, 8]]
    assert svc.lease("p", 8).lo == 8


def test_producer_surfaces_lease_exhaustion():
    svc = BlockService(seed=7)
    svc.open("p")
    svc.commit(svc.lease("p", 8, at=16))   # stale window in the way
    with svc.producer("p", 8, start=8) as prod:
        next(prod)                          # [8, 16) is fine
        with pytest.raises(LeaseError, match="overlaps"):
            for _ in prod:                  # [16, 24) must be refused
                pass


def test_producer_custom_window_fn_channel():
    svc = BlockService(seed=7)
    seen = []

    def window(lo, hi):
        seen.append((lo, hi))
        return jnp.full((hi - lo,), lo, jnp.int32)

    svc.open("custom", window_fn=window)
    with svc.producer("custom", 4, count=3) as prod:
        vals = [int(np.asarray(b)[0]) for _, b in prod]
    assert vals == [0, 4, 8]
    assert seen == [(0, 4), (4, 8), (8, 12)]


# ---------------------------------------------------------------------------
# deep pipelines, donated buffer rings, fused multi-window producers
# ---------------------------------------------------------------------------

needs_donation = pytest.mark.skipif(
    not blocks_mod.donation_supported(),
    reason="jit buffer donation is a no-op on this backend")


def _take_blocks(svc, name, length, n, **kw):
    return [np.array(svc.take(name, length, **kw)) for _ in range(n)]


def test_deep_producer_ordering_and_bit_identity():
    ref_svc = BlockService(seed=13)
    ref_svc.open("p", num_streams=8)
    ref = _take_blocks(ref_svc, "p", 16, 6)
    svc = BlockService(seed=13)
    svc.open("p", num_streams=8)
    with svc.producer("p", 16, count=6, depth=3) as prod:
        got = [(lease.lo, np.array(blk)) for lease, blk in prod]
    assert [lo for lo, _ in got] == [0, 16, 32, 48, 64, 80]
    for (_, blk), expect in zip(got, ref):
        assert np.array_equal(blk, expect)


def test_deep_producer_backpressure_bounds_prefetch():
    """A lagging consumer never lets the producer run away: in-flight
    windows are bounded by queue depth + the block being generated."""
    import time
    depth = 3
    svc = BlockService(seed=13)
    svc.open("p", num_streams=4)
    with svc.producer("p", 8, depth=depth) as prod:
        next(prod)                     # slow consumer: take one, then idle
        time.sleep(0.5)                # let the producer fill the queue
        state = svc.ledger_state()["channels"]["p"]["committed"]
        assert state == [[0, 8]]       # nothing else committed
        # reservations = queue (depth) + at most one being generated +
        # one put-blocked: a fresh lease lands within that bound
        nxt = svc.lease("p", 8)
        assert nxt.lo <= 8 * (1 + depth + 2)


def test_deep_producer_stop_mid_queue_drains_reservations():
    svc = BlockService(seed=13)
    svc.open("p", num_streams=4)
    prod = svc.producer("p", 8, depth=4)
    next(prod)
    next(prod)
    prod.close()                       # queue still holds blocks
    assert svc.ledger_state()["channels"]["p"]["committed"] == [[0, 16]]
    # every undelivered reservation was released: [16, 24) is free again
    lease = svc.lease("p", 8, at=16)
    assert (lease.lo, lease.hi) == (16, 24)


@needs_donation
@pytest.mark.parametrize("depth", [1, 2, 4])
def test_donated_producer_bit_identical_to_plain(depth):
    ref_svc = BlockService(seed=17)
    ref_svc.open("p", num_streams=8, sampler="uniform")
    ref = _take_blocks(ref_svc, "p", 16, 6)
    svc = BlockService(seed=17)
    svc.open("p", num_streams=8, sampler="uniform")
    with svc.producer("p", 16, count=6, depth=depth, donate=True,
                      check_ring=True) as prod:
        # donated contract: a block is valid only until the next pull
        got = [np.array(blk) for _, blk in prod]
    assert len(got) == 6
    for blk, expect in zip(got, ref):
        assert np.array_equal(blk, expect)


@needs_donation
def test_donated_producer_reuses_ring_buffers():
    """Zero-copy steady state: every block the ring yields lives at one
    of depth + 2 pre-allocated addresses."""
    depth, n = 2, 12
    svc = BlockService(seed=17)
    svc.open("p", num_streams=4)
    ptrs = set()
    with svc.producer("p", 8, count=n, depth=depth, donate=True,
                      check_ring=True) as prod:
        for _, blk in prod:
            blk.block_until_ready()
            ptrs.add(blk.unsafe_buffer_pointer())
    assert 1 < len(ptrs) <= depth + 2


def test_donated_producer_refused_where_unsupported(monkeypatch):
    svc = BlockService(seed=17)
    svc.open("p", num_streams=4)
    monkeypatch.setattr(blocks_mod, "donation_supported", lambda: False)
    with pytest.raises(ValueError, match="donation"):
        svc.producer("p", 8, donate=True)


def test_fused_producer_bit_identical_with_per_window_commits():
    ref_svc = BlockService(seed=19)
    ref_svc.open("p", num_streams=8)
    ref = _take_blocks(ref_svc, "p", 12, 6)
    svc = BlockService(seed=19)
    svc.open("p", num_streams=8)
    with svc.producer("p", 12, count=6, fuse=4) as prod:  # 6 = 4 + 2 tail
        got = [(lease, np.array(blk)) for lease, blk in prod]
    assert [lease.lo for lease, _ in got] == [0, 12, 24, 36, 48, 60]
    for (_, blk), expect in zip(got, ref):
        assert np.array_equal(blk, expect)
    assert svc.ledger_state()["channels"]["p"]["committed"] == [[0, 72]]


def test_fused_producer_single_window_tail():
    """count % fuse == 1: the one-lease tail batch must still yield a
    full (L, S) window, not a slice of it."""
    ref_svc = BlockService(seed=19)
    ref_svc.open("p", num_streams=8)
    ref = _take_blocks(ref_svc, "p", 12, 7)
    svc = BlockService(seed=19)
    svc.open("p", num_streams=8)
    with svc.producer("p", 12, count=7, fuse=2) as prod:  # 7 = 3x2 + 1 tail
        got = [np.array(blk) for _, blk in prod]
    assert [g.shape for g in got] == [(12, 8)] * 7
    for blk, expect in zip(got, ref):
        assert np.array_equal(blk, expect)


@needs_donation
def test_fused_donated_producer_bit_identical():
    ref_svc = BlockService(seed=19)
    ref_svc.open("p", num_streams=8, sampler="uniform", out_dtype="bfloat16")
    ref = _take_blocks(ref_svc, "p", 16, 8)
    svc = BlockService(seed=19)
    svc.open("p", num_streams=8, sampler="uniform", out_dtype="bfloat16")
    with svc.producer("p", 16, count=8, fuse=2, donate=True,
                      check_ring=True) as prod:
        got = [np.array(blk) for _, blk in prod]
    for blk, expect in zip(got, ref):
        assert np.array_equal(blk.view(np.uint16), expect.view(np.uint16))


def test_lease_many_contiguous_and_atomic():
    svc = BlockService(seed=23)
    svc.open("a", num_streams=2)
    leases = svc.lease_many("a", 8, 3)
    assert [(l.lo, l.hi) for l in leases] == [(0, 8), (8, 16), (16, 24)]
    svc.commit(svc.lease("a", 8, at=40))   # block the middle of the next run
    with pytest.raises(LeaseError, match="overlaps"):
        svc.lease_many("a", 8, 4, at=24)   # [40, 48) clashes on window 3
    # all-or-nothing: the windows before the clash were rolled back
    ok = svc.lease("a", 16, at=24)
    assert (ok.lo, ok.hi) == (24, 40)


def test_generate_many_matches_per_lease_generate():
    svc = BlockService(seed=23)
    svc.open("a", num_streams=8)
    leases = svc.lease_many("a", 16, 3)
    stack = np.asarray(svc.generate_many(leases))
    assert stack.shape == (3, 16, 8)
    for w, lease in enumerate(leases):
        assert np.array_equal(stack[w], np.asarray(svc.generate(lease)))
    solo = svc.lease("a", 16)
    one = np.asarray(svc.generate_many([solo]))
    assert one.shape == (1, 16, 8)
    assert np.array_equal(one[0], np.asarray(svc.generate(solo)))
    with pytest.raises(ValueError, match="single-window"):
        svc.generate_many([solo], retired=jnp.zeros((1, 16, 8), jnp.uint32))


def test_generate_many_rejects_gaps_and_mixed_lengths():
    svc = BlockService(seed=23)
    svc.open("a", num_streams=4)
    l1 = svc.lease("a", 8)
    svc.lease("a", 8)                       # consumed to create a gap
    l3 = svc.lease("a", 8)
    with pytest.raises(ValueError, match="contiguous"):
        svc.generate_many([l1, l3])
    l4 = svc.lease("a", 4)
    with pytest.raises(ValueError, match="contiguous"):
        svc.generate_many([l3, l4])


def test_donate_and_fuse_require_meshless_service():
    devs = np.array(jax.devices())
    mesh = jax.sharding.Mesh(devs, ("streams",))
    svc = BlockService(seed=23, mesh=mesh)
    svc.open("a", num_streams=4)
    with pytest.raises(ValueError, match="mesh"):
        svc.producer("a", 8, fuse=2)
    with pytest.raises(ValueError, match="mesh"):
        svc.producer("a", 8, donate=True)


# ---------------------------------------------------------------------------
# BlockService-fed training: bit-identity + mid-epoch resume
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_cfg():
    from repro.configs import get_config
    from repro.launch.train import smoke_config
    return smoke_config(get_config("glm4_9b"))


@pytest.mark.slow
def test_train_service_path_bit_identical_to_fused(smoke_cfg):
    """The acceptance bar: BlockService-fed training produces bit-identical
    losses (and params) to the pre-refactor fused per-step derive path."""
    from repro.launch.train import train
    runs = {}
    for use_service in (True, False):
        with tempfile.TemporaryDirectory() as d:
            runs[use_service] = train(
                smoke_cfg, steps=4, global_batch=2, seq_len=32, ckpt_dir=d,
                save_every=2, log_every=1, use_service=use_service)
    p1, _, l1 = runs[True]
    p2, _, l2 = runs[False]
    assert l1 == l2
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_train_resumes_bit_identically_after_failure(smoke_cfg):
    """Lease-ledger checkpoint/restore: a SimulatedFailure mid-epoch
    (between checkpoints) restarts from the ledger snapshot and converges
    to the exact params of an uninterrupted run."""
    from repro.launch.train import train
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        p1, _, _ = train(smoke_cfg, steps=5, global_batch=2, seq_len=32,
                         ckpt_dir=d1, save_every=2, log_every=10,
                         use_service=True, fail_at=3)
        p2, _, _ = train(smoke_cfg, steps=5, global_batch=2, seq_len=32,
                         ckpt_dir=d2, save_every=2, log_every=10,
                         use_service=True)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# leased app consumers
# ---------------------------------------------------------------------------

def test_leased_mc_apps_consume_disjoint_windows():
    svc = BlockService(seed=11)
    e1 = float(blocks_mod.estimate_pi(svc, num_lanes=128,
                                      draws_per_lane=64))
    e2 = float(blocks_mod.estimate_pi(svc, num_lanes=128,
                                      draws_per_lane=64))
    assert abs(e1 - np.pi) < 0.2 and abs(e2 - np.pi) < 0.2
    assert e1 != e2          # fresh randomness per call
    assert svc.ledger_state()["channels"]["mc/pi"]["committed"] == [[0, 128]]
    # the second call is the offset window of the same family
    direct = float(ops.estimate_pi(seed=11, num_lanes=128, draws_per_lane=64,
                                   offset=64))
    assert e2 == direct


def test_mc_offset_window_matches_tail_of_longer_run():
    """offset is real counter addressing: a [64, 128) window equals the
    second half of a 128-draw run (partial sums of the same samples)."""
    full = float(ops.estimate_pi(seed=13, num_lanes=64, draws_per_lane=128,
                                 use_kernel=False))
    head = float(ops.estimate_pi(seed=13, num_lanes=64, draws_per_lane=64,
                                 use_kernel=False))
    tail = float(ops.estimate_pi(seed=13, num_lanes=64, draws_per_lane=64,
                                 offset=64, use_kernel=False))
    total = 64 * 128
    assert abs((head * 64 * 64 + tail * 64 * 64) - full * total) < 1e-3


def test_leased_dropout_matches_stream_and_rejects_short_window():
    svc = BlockService(seed=17)
    svc.open("drop")
    x = jnp.ones((16, 256))
    lease = svc.lease("drop", x.size)
    a = np.asarray(ops.fused_dropout(x, lease, 0.3))
    b = np.asarray(ops.fused_dropout(x, lease.stream(), 0.3))
    assert np.array_equal(a, b)
    with pytest.raises(ValueError, match="smaller than"):
        ops.fused_dropout(x, svc.lease("drop", 16), 0.3)


# ---------------------------------------------------------------------------
# 2-D (host, stream) mesh fan-out — forced 8-device subprocess
# ---------------------------------------------------------------------------

MESH_2D_SUBPROCESS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import numpy as np
from repro.core import engine
from repro.launch.mesh import make_host_mesh, rng_axes
from repro.runtime import BlockService

assert len(jax.devices()) == 8
mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(2, 4),
                         ("hosts", "streams"))
ok = {}
for mode in ("ctr", "faithful"):
    plan = engine.make_plan(seed=29, num_streams=64, num_steps=16, mode=mode)
    single = np.asarray(engine.generate(plan, backend="xla"))
    two_d = np.asarray(engine.generate_sharded(
        plan, mesh=mesh, axis_names=("hosts", "streams")))
    ok[mode] = bool(np.array_equal(single, two_d))
# fmix32 ctr hash + uneven S (50 pads to 56 on 8 devices, sliced back)
plan = engine.make_plan(seed=31, num_streams=50, num_steps=12, deco="fmix32")
ok["fmix32_uneven"] = bool(np.array_equal(
    np.asarray(engine.generate(plan, backend="xla")),
    np.asarray(engine.generate_sharded(plan, mesh=mesh,
                                       axis_names=("hosts", "streams")))))
# a production-style mesh via make_host_mesh + rng_axes
hm = make_host_mesh(model=2)
plan = engine.make_plan(seed=33, num_streams=24, num_steps=8)
ok["host_mesh"] = bool(np.array_equal(
    np.asarray(engine.generate(plan, backend="xla")),
    np.asarray(engine.generate_sharded(plan, mesh=hm,
                                       axis_names=rng_axes(hm)))))
# BlockService riding the 2-D mesh: leased windows == single-device engine
svc = BlockService(seed=35, mesh=mesh)
svc.open("c", num_streams=48)
lease = svc.lease("c", 16)
blk = np.asarray(svc.generate(lease))
ok["service_2d"] = bool(np.array_equal(
    blk, np.asarray(engine.generate(lease.plan(), backend="xla"))))
# make_host_mesh guard: 8 devices cannot split with model=3
try:
    make_host_mesh(model=3)
    ok["mesh_guard"] = False
except ValueError as e:
    ok["mesh_guard"] = "cannot split" in str(e)
print(json.dumps({"devices": len(jax.devices()), **ok}))
"""


def test_mesh_2d_bit_exact_subprocess():
    """Real (2, 4) = (hosts, streams) device grid: the 2-D fan-out is
    bit-exact vs single-device generate for both decorrelator modes, the
    fmix32 hash, uneven S, make_host_mesh production axes, and the
    BlockService window path."""
    out = subprocess.run([sys.executable, "-c", MESH_2D_SUBPROCESS],
                         capture_output=True, text=True, timeout=300,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["devices"] == 8
    for key in ("ctr", "faithful", "fmix32_uneven", "host_mesh",
                "service_2d", "mesh_guard"):
        assert rep[key], key


def test_make_host_mesh_guard_single_device():
    """In this 1-device process any model > 1 must raise, not build a
    (0, model) mesh."""
    from repro.launch.mesh import make_host_mesh
    with pytest.raises(ValueError, match="cannot split"):
        make_host_mesh(model=2)
    with pytest.raises(ValueError, match="cannot split"):
        make_host_mesh(model=0)
