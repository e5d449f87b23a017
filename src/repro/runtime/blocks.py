"""Block delivery: leased counter windows + double-buffered producers.

The paper's deployment story is not "call the generator" — it is a
standing producer streaming decorrelated blocks through on-chip FIFOs
into application kernels, while SOU instances scale with zero extra
root hardware.  ``BlockService`` is the software analogue of that
delivery layer, sitting ABOVE the engine:

  * **Counter-window leases.**  Every consumer (data pipeline, dropout,
    MC apps, serving sampler) names a *channel* (one MISRN family of the
    service seed) and receives disjoint, checkpointable
    ``(ctr_lo, ctr_hi)`` windows of its counter space.  Double-spending
    randomness becomes structurally impossible — an overlapping lease
    raises ``LeaseError`` — instead of a calling convention.
  * **A two-phase ledger.**  ``lease()`` *reserves* a window (in-memory
    only); ``commit()`` moves it into the durable ledger.
    ``ledger_state()`` snapshots committed windows only, so a snapshot
    taken mid-run describes exactly the randomness consumed so far;
    ``restore_ledger()`` rewinds to a snapshot (dropping reservations),
    after which re-leasing replays the SAME windows — bit-identical
    resume falls out of the accounting.
  * **Double-buffered generation.**  ``producer()`` runs a daemon thread
    that leases window ``k+1`` and *dispatches* its generation while the
    consumer still holds block ``k`` — JAX's async dispatch makes the
    handoff ``block_until_ready``-free: the thread enqueues device work
    and puts the (not yet materialized) array in a depth-bounded queue,
    the software analogue of the paper's FIFO into the application.

Generation itself is one jitted window function per (channel, length,
sampler) with a TRACED counter, so successive leases of equal length
re-use one executable (no per-window retrace) — this covers every
sampler stage including the distribution stages (exponential/poisson/
gamma/categorical), whose parsed specs are hashable compile-time
constants — and the service's mesh —
including the 2-D ``(hosts, streams)`` fan-out of
``engine.generate_sharded`` — rides inside the jit.

Layering: ``runtime`` sits above ``core`` and ``kernels``; nothing in
``core``/``kernels`` imports this module.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import hashlib
import queue
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, sampler as sampler_mod, stream as tstream, u64
from repro.runtime import spans

_M64 = (1 << 64) - 1


@functools.lru_cache(maxsize=None)
def donation_supported() -> bool:
    """True if ``jit(..., donate_argnums=...)`` actually aliases here.

    Empirical, not a platform table: donate a buffer into a jitted
    full-overwrite and see whether the runtime deleted the input.  On
    platforms where donation is a no-op jax only warns, the input stays
    live, and the donated producer ring would silently degrade to fresh
    allocations — callers use this to skip/flag rather than pretend.
    """
    import warnings
    probe = jax.jit(
        lambda x: jax.lax.dynamic_update_slice(x, x + jnp.uint32(1), (0,)),
        donate_argnums=(0,))
    x = jnp.zeros((8,), jnp.uint32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        probe(x).block_until_ready()
    return x.is_deleted()


class LeaseError(ValueError):
    """A lease request overlaps randomness that is already spoken for."""


def channel_purpose(name: str) -> int:
    """Deterministic 64-bit purpose tag for a channel name (stable across
    processes — the ledger must mean the same windows after a restart)."""
    return int.from_bytes(
        hashlib.blake2s(name.encode(), digest_size=8).digest(), "little")


# ---------------------------------------------------------------------------
# Lease + ledger
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Lease:
    """One disjoint counter window ``[lo, hi)`` of a channel.

    Units are whatever the channel's window function counts: engine
    plan channels count counter steps along the T axis (x ``num_streams``
    elements per step); the data-pipeline channel counts optimizer steps.

    Example:
        >>> from repro.runtime.blocks import BlockService
        >>> svc = BlockService(seed=11)
        >>> _ = svc.open("docs/demo", num_streams=2)
        >>> lease = svc.lease("docs/demo", 4)
        >>> (lease.lo, lease.hi, lease.length)
        (0, 4, 4)
        >>> lease.commit()                     # window becomes durable
        >>> svc.lease("docs/demo", 4).lo       # next window is disjoint
        4
    """
    channel: str
    lo: int
    hi: int
    service: "BlockService" = dataclasses.field(repr=False, compare=False)

    @property
    def length(self) -> int:
        return self.hi - self.lo

    def plan(self, **overrides) -> engine.GenPlan:
        """The engine plan for this window (plan channels only)."""
        return self.service.plan_for(self, **overrides)

    def stream(self, column: int = 0) -> tstream.ThunderStream:
        """ThunderStream for one column of the window, advanced to ``lo``.

        Bit-parity with the bulk block is the engine's shared-derivation
        guarantee: ``random_bits(lease.stream(s), (L,))`` equals column
        ``s`` of ``service.generate(lease)`` for a bits channel.
        """
        return self.service.stream_for(self, column)

    def commit(self) -> None:
        self.service.commit(self)

    def release(self) -> None:
        self.service.release(self)


class _Ledger:
    """Disjoint-interval bookkeeping for one channel.

    ``committed`` is a sorted list of disjoint ``[lo, hi)`` windows
    (adjacent windows merge); ``reserved`` holds in-flight leases.  The
    sequential high-water ``next`` is ``max(floor, every hi)`` so plain
    ``lease(n)`` calls hand out consecutive windows.
    """

    def __init__(self) -> None:
        self.committed: List[Tuple[int, int]] = []
        self.reserved: List[Tuple[int, int]] = []
        self.floor = 0

    @property
    def next(self) -> int:
        hi = self.floor
        if self.committed:
            hi = max(hi, self.committed[-1][1])
        for _, h in self.reserved:
            hi = max(hi, h)
        return hi

    def _overlaps(self, lo: int, hi: int) -> Optional[Tuple[int, int]]:
        i = bisect.bisect_left(self.committed, (lo, lo)) - 1
        for j in (i, i + 1):
            if 0 <= j < len(self.committed):
                clo, chi = self.committed[j]
                if clo < hi and lo < chi:
                    return (clo, chi)
        for rlo, rhi in self.reserved:
            if rlo < hi and lo < rhi:
                return (rlo, rhi)
        return None

    def reserve(self, lo: int, hi: int) -> None:
        if lo < self.floor:
            raise LeaseError(
                f"window [{lo}, {hi}) starts below the fenced floor "
                f"{self.floor} (counters below the floor may already "
                f"have been served by a previous owner)")
        clash = self._overlaps(lo, hi)
        if clash is not None:
            raise LeaseError(
                f"window [{lo}, {hi}) overlaps existing lease "
                f"[{clash[0]}, {clash[1]})")
        self.reserved.append((lo, hi))

    def commit(self, lo: int, hi: int) -> None:
        try:
            self.reserved.remove((lo, hi))
        except ValueError:
            raise LeaseError(f"window [{lo}, {hi}) is not reserved")
        bisect.insort(self.committed, (lo, hi))
        # merge touching neighbours (overlap is impossible by reserve())
        merged: List[Tuple[int, int]] = []
        for w in self.committed:
            if merged and merged[-1][1] >= w[0]:
                merged[-1] = (merged[-1][0], max(merged[-1][1], w[1]))
            else:
                merged.append(w)
        self.committed = merged

    def release(self, lo: int, hi: int) -> None:
        try:
            self.reserved.remove((lo, hi))
        except ValueError:
            raise LeaseError(f"window [{lo}, {hi}) is not reserved")

    def state(self) -> Dict[str, Any]:
        return {"committed": [[lo, hi] for lo, hi in self.committed],
                "floor": self.floor}

    @classmethod
    def from_state(cls, state: Dict[str, Any]) -> "_Ledger":
        led = cls()
        led.committed = sorted((int(lo), int(hi))
                               for lo, hi in state.get("committed", []))
        led.floor = int(state.get("floor", 0))
        return led


# ---------------------------------------------------------------------------
# Channels
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Channel:
    """One named consumer of the service's MISRN space.

    A *plan channel* (``window_fn is None``) generates ``(L, S)`` engine
    blocks for each leased window; a *custom channel* delegates to
    ``window_fn(lo, hi)`` (e.g. the data pipeline's batch function) and
    uses the ledger for accounting only.
    """
    name: str
    purpose: int
    num_streams: int = 1
    mode: str = "ctr"
    deco: str = "splitmix64"
    sampler: str = "bits"
    out_dtype: str = "float32"
    window_fn: Optional[Callable[[int, int], Any]] = None


class BlockService:
    """Leased-window block delivery over one seed's MISRN stream space.

    ``mesh``/``axis_names`` route every plan-channel window through
    ``engine.generate_sharded`` — 1-D or the 2-D ``(hosts, streams)``
    fan-out — with the root state replicated and zero collectives, so
    adding devices to the service is the paper's "add SOU instances"
    move.  Without a mesh, plans go through ``engine.generate`` with the
    service's backend override (auto-selected when None).

    Example:
        >>> from repro.runtime.blocks import BlockService
        >>> svc = BlockService(seed=11)
        >>> _ = svc.open("docs/demo", num_streams=4)
        >>> blk = svc.take("docs/demo", 8)     # lease + generate + commit
        >>> (blk.shape, str(blk.dtype))
        ((8, 4), 'uint32')
        >>> svc.ledger_state()["channels"]["docs/demo"]["committed"]
        [[0, 8]]
    """

    def __init__(self, seed: int = 0, *,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 axis_names: Optional[Tuple[str, ...]] = None,
                 backend: Optional[str] = None,
                 block_t: int = engine.DEFAULT_BLOCK_T,
                 block_s: int = engine.DEFAULT_BLOCK_S):
        self.seed = seed
        self.mesh = mesh
        self.axis_names = (tuple(axis_names) if axis_names is not None
                           else (tuple(mesh.axis_names) if mesh is not None
                                 else None))
        self.backend = backend
        self.block_t = block_t
        self.block_s = block_s
        self._channels: Dict[str, Channel] = {}
        self._ledgers: Dict[str, _Ledger] = {}
        self._window_fns: Dict[Tuple, Callable] = {}
        self._lock = threading.Lock()

    # -- channels ----------------------------------------------------------

    def open(self, name: str, *, num_streams: int = 1,
             purpose: Optional[int] = None, mode: str = "ctr",
             deco: str = "splitmix64", sampler: str = "bits",
             out_dtype: str = "float32",
             window_fn: Optional[Callable[[int, int], Any]] = None
             ) -> Channel:
        """Open (or return the already-open) channel ``name``."""
        with self._lock:
            if name in self._channels:
                return self._channels[name]
            ch = Channel(name=name,
                         purpose=(channel_purpose(name) if purpose is None
                                  else purpose),
                         num_streams=num_streams, mode=mode, deco=deco,
                         sampler=sampler, out_dtype=out_dtype,
                         window_fn=window_fn)
            self._channels[name] = ch
            self._ledgers.setdefault(name, _Ledger())
            return ch

    def channel(self, name: str) -> Channel:
        return self._channels[name]

    # -- leases ------------------------------------------------------------

    def lease(self, name: str, length: int, *,
              at: Optional[int] = None) -> Lease:
        """Reserve the next (or an explicit) disjoint window of a channel.

        ``at=None`` takes ``length`` units at the channel's high-water
        mark; an explicit ``at`` claims ``[at, at + length)`` and raises
        ``LeaseError`` if ANY part of it is already reserved or
        committed.
        """
        if length <= 0:
            raise ValueError(f"lease length must be positive, got {length}")
        if name not in self._channels:
            raise KeyError(f"channel {name!r} is not open; "
                           f"have {sorted(self._channels)}")
        with spans.span("blocks.lease") as sp, self._lock:
            led = self._ledgers[name]
            lo = led.next if at is None else int(at)
            sp.window = lo
            hi = lo + length
            if hi > _M64:
                raise LeaseError(f"window [{lo}, {hi}) exceeds the u64 "
                                 f"counter space")
            led.reserve(lo, hi)
        return Lease(channel=name, lo=lo, hi=hi, service=self)

    def lease_many(self, name: str, length: int, n: int, *,
                   at: Optional[int] = None) -> List[Lease]:
        """``n`` CONTIGUOUS equal-length windows, reserved atomically.

        All-or-nothing under one lock acquisition: either every window
        ``[lo0 + i*length, lo0 + (i+1)*length)`` is reserved or none is
        (an explicit ``at`` that clashes partway rolls back and raises).
        This is the fused producer's lease shape — one
        ``generate_windows`` dispatch covers all ``n`` windows, but each
        window keeps its own lease so commit-at-handoff accounting stays
        per-block exact.
        """
        if n < 1:
            raise ValueError(f"n must be >= 1, got {n}")
        if length <= 0:
            raise ValueError(f"lease length must be positive, got {length}")
        if name not in self._channels:
            raise KeyError(f"channel {name!r} is not open; "
                           f"have {sorted(self._channels)}")
        with spans.span("blocks.lease") as sp, self._lock:
            led = self._ledgers[name]
            lo0 = led.next if at is None else int(at)
            sp.window = lo0
            if lo0 + n * length > _M64:
                raise LeaseError(f"window [{lo0}, {lo0 + n * length}) "
                                 f"exceeds the u64 counter space")
            done: List[Tuple[int, int]] = []
            try:
                for i in range(n):
                    lo = lo0 + i * length
                    led.reserve(lo, lo + length)
                    done.append((lo, lo + length))
            except LeaseError:
                for lo, hi in done:
                    led.release(lo, hi)
                raise
        return [Lease(channel=name, lo=lo, hi=hi, service=self)
                for lo, hi in done]

    def commit(self, lease: Lease) -> None:
        """Move a reserved window into the durable (checkpointable) ledger."""
        with spans.span("blocks.commit", lease.lo), self._lock:
            self._ledgers[lease.channel].commit(lease.lo, lease.hi)

    def release(self, lease) -> None:
        """Drop an unconsumed reservation — or retire a whole channel.

        With a :class:`Lease`, drops that reservation (its window may be
        re-leased).  With a channel NAME (str), retires the channel —
        the slot-churn primitive the inference tier's slot pool uses
        when a sequence finishes:

          * the channel's lease floor is fenced at its current
            high-water mark, so when a later occupant re-opens the same
            name (``open`` preserves the ledger of a retired channel)
            every window it leases is strictly beyond anything the
            previous occupant consumed — a retired-and-reused region can
            never overlap a lease that was ever live;
          * the ``Channel`` entry and its cached window executables are
            dropped, so churn over many short-lived consumers does not
            grow the channel table or the jit cache without bound;
          * outstanding reservations refuse the retire (``LeaseError``)
            — a live producer must be closed before its channel dies.
        """
        if isinstance(lease, str):
            return self._release_channel(lease)
        with self._lock:
            self._ledgers[lease.channel].release(lease.lo, lease.hi)

    def _release_channel(self, name: str) -> int:
        with self._lock:
            if name not in self._channels:
                raise KeyError(f"channel {name!r} is not open; "
                               f"have {sorted(self._channels)}")
            led = self._ledgers[name]
            if led.reserved:
                raise LeaseError(
                    f"channel {name!r} has {len(led.reserved)} live "
                    f"reservation(s); close its producers before release")
            led.floor = led.next
            del self._channels[name]
            for key in [k for k in self._window_fns if k[0] == name]:
                del self._window_fns[key]
            return led.floor

    # -- ledger checkpointing ---------------------------------------------

    def ledger_state(self) -> Dict[str, Any]:
        """JSON-able snapshot of COMMITTED windows per channel.

        Reservations are deliberately excluded: a snapshot describes the
        randomness actually handed to consumers, so restoring it and
        re-leasing replays in-flight windows bit-identically.
        """
        with self._lock:
            return {"channels": {name: led.state()
                                 for name, led in self._ledgers.items()}}

    def restore_ledger(self, state: Optional[Dict[str, Any]]) -> None:
        """Rewind the ledger to a snapshot (or clear it with ``None``/{}).

        All reservations vanish — producers running at snapshot-restore
        time must be closed first (``BlockProducer.close``).
        """
        chans = (state or {}).get("channels", {})
        with self._lock:
            self._ledgers = {name: _Ledger.from_state(s)
                             for name, s in chans.items()}
            for name in self._channels:
                self._ledgers.setdefault(name, _Ledger())

    def fence(self, name: str, floor: int) -> int:
        """Raise channel ``name``'s lease floor to at least ``floor``.

        Every future lease — including an explicit ``lease(at=...)``
        into a gap between old committed windows — starts at or past
        the floor.  This is the failover primitive: a peer adopting a
        dead shard's journal fences each channel at its journaled
        high-water mark, so no counter the dead shard *might* have
        handed out can ever be re-leased.  Returns the new floor.
        """
        with self._lock:
            led = self._ledgers.setdefault(name, _Ledger())
            led.floor = max(led.floor, int(floor))
            return led.floor

    # -- generation --------------------------------------------------------

    def plan_for(self, lease: Lease, *, sampler: Optional[str] = None,
                 out_dtype: Optional[str] = None) -> engine.GenPlan:
        """Static-offset engine plan for a leased window (plan channels)."""
        ch = self._channels[lease.channel]
        if ch.window_fn is not None:
            raise ValueError(f"channel {lease.channel!r} has a custom "
                             f"window_fn; it has no engine plan")
        return engine.make_plan(
            seed=self.seed, num_streams=ch.num_streams,
            num_steps=lease.length, offset=lease.lo, purpose=ch.purpose,
            mode=ch.mode, deco=ch.deco,
            sampler=ch.sampler if sampler is None else sampler,
            out_dtype=ch.out_dtype if out_dtype is None else out_dtype)

    def stream_for(self, lease: Lease, column: int = 0
                   ) -> tstream.ThunderStream:
        ch = self._channels[lease.channel]
        fam = tstream.new_stream(self.seed, ch.purpose)
        return tstream.advance(tstream.derive(fam, column), lease.lo)

    def _window_fn(self, ch: Channel, length: int, sampler: str,
                   out_dtype: str, *, fuse: int = 1,
                   donate: bool = False) -> Callable:
        """One jitted window executable per (channel, shape, variant).

        The counter is TRACED (plan.offset=None), so every equal-length
        lease of a channel reuses one executable; traced and static
        counters are bit-identical by the engine's parity tests.  It
        enters as two host ``uint32`` scalars from ``_ctr_args``, on
        jit's C++ dispatch path.

        Variants (cache-keyed alongside the shape):

          * ``fuse=1, donate=False`` — ``fn(hi, lo) -> (L, S)``.
          * ``fuse=W``               — ``fn(hi, lo) -> (W, L, S)``, one
            ``engine.generate_windows`` dispatch for W windows.
          * ``donate=True``          — ``fn(hi, lo, retired)`` with
            ``donate_argnums=(2,)``: the retiring block is overwritten
            in place (``dynamic_update_slice`` over the full shape, so
            the values are exactly the fresh block's) and XLA reuses its
            allocation instead of allocating per window.  The donated
            arg MUST participate in the computation or XLA prunes it
            and silently drops the aliasing — hence update, not ignore.

        ``fuse>1``/``donate`` require ``mesh=None`` (the sharded path
        manages its own output layout).
        """
        fuse = int(fuse)
        if fuse < 1:
            raise ValueError(f"fuse must be >= 1, got {fuse}")
        if (fuse > 1 or donate) and self.mesh is not None:
            raise ValueError("fused/donated window functions require "
                             "mesh=None; sharded delivery manages its own "
                             "output buffers")
        key = (ch.name, length, sampler, out_dtype, fuse, donate)
        fn = self._window_fns.get(key)
        if fn is not None:
            return fn
        x0, h_fam = engine.family_from_seed(self.seed, ch.purpose)
        h = engine.leaf_table(h_fam, ch.num_streams)
        mesh, axes, backend = self.mesh, self.axis_names, self.backend
        block_t, block_s = self.block_t, self.block_s
        mode, deco = ch.mode, ch.deco

        def compute(ctr_hi, ctr_lo):
            plan = engine.GenPlan(
                x0=x0, h=h, num_steps=length, ctr=(ctr_hi, ctr_lo),
                offset=None, mode=mode, deco=deco, sampler=sampler,
                out_dtype=out_dtype)
            if fuse > 1:
                return engine.generate_windows(
                    plan, fuse, backend=backend, block_t=block_t,
                    block_s=block_s)
            if mesh is not None:
                # The window leaves sharded by stream, each device
                # holding its own columns, whatever the generation's
                # last op: left to inference, a window built from one
                # shard's columns comes out whole on every device.  (An
                # S that does not divide over the mesh is replicated.)
                return jax.lax.with_sharding_constraint(
                    engine.generate_sharded(
                        plan, mesh=mesh, axis_names=axes, backend=backend,
                        block_t=block_t, block_s=block_s),
                    jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec(None, axes)))
            return engine.generate(plan, backend=backend, block_t=block_t,
                                   block_s=block_s)

        if donate:
            @functools.partial(jax.jit, donate_argnums=(2,))
            def window(ctr_hi, ctr_lo, retired):
                block = compute(ctr_hi, ctr_lo)
                return jax.lax.dynamic_update_slice(
                    retired, block, (0,) * block.ndim)
        else:
            window = jax.jit(compute)

        self._window_fns[key] = window
        return window

    def _ctr_args(self, lo: int) -> Tuple[np.uint32, np.uint32]:
        """Counter ``lo`` as the window program's ``(hi, lo)`` arguments.

        Host ``numpy.uint32`` scalars, not device arrays: jit takes them
        on its C++ dispatch path and places them itself, so a window
        costs no Python-level array construction and no extra device
        program.  The avals (``u32[]``) and the compiled program are
        those of device scalars, but jit caches the two calling
        conventions as separate executables, so every plan-channel
        dispatch (``generate``, ``generate_many``, ``regenerate``) takes
        its counter from here and nowhere else.  ``bench/faults.py``
        patches this method to plant its counter faults.
        """
        return u64.const64(lo)

    def generate(self, lease: Lease, *, sampler: Optional[str] = None,
                 out_dtype: Optional[str] = None,
                 retired: Any = None) -> Any:
        """The block for a leased window (dispatched, not waited on).

        Plan channels return the ``(length, S)`` engine block with the
        channel's (or overridden) sampler stage; custom channels return
        ``window_fn(lo, hi)``.  Passing ``retired`` — a live jax array
        of the output's exact shape and dtype, typically the block the
        consumer just finished with — DONATES it: the result is
        bit-identical but reuses the retired block's allocation, and
        the retired array is deleted (donated producer ring).
        """
        ch = self._channels[lease.channel]
        if ch.window_fn is not None and retired is not None:
            raise ValueError(f"channel {lease.channel!r} has a custom "
                             f"window_fn; donation needs a plan channel")
        with spans.span("blocks.dispatch", lease.lo):
            if ch.window_fn is not None:
                return ch.window_fn(lease.lo, lease.hi)
            s = ch.sampler if sampler is None else sampler
            d = ch.out_dtype if out_dtype is None else out_dtype
            fn = self._window_fn(ch, lease.length, s, d,
                                 donate=retired is not None)
            args = self._ctr_args(lease.lo)
            if retired is not None:
                return fn(*args, retired)
            return fn(*args)

    def generate_many(self, leases: List[Lease], *,
                      sampler: Optional[str] = None,
                      out_dtype: Optional[str] = None,
                      retired: Any = None) -> Any:
        """(W, L, S) stack for W contiguous leases — ONE fused dispatch.

        The leases must be what ``lease_many`` hands out: same plan
        channel, equal length, back-to-back windows.  The stack is
        bit-identical to per-lease ``generate`` calls (the engine's
        ``generate_windows`` parity guarantee) but pays the dispatch
        path once.  ``retired`` donates a (W, L, S) stack as in
        ``generate``.
        """
        if not leases:
            raise ValueError("generate_many needs at least one lease")
        ch = self._channels[leases[0].channel]
        if ch.window_fn is not None:
            raise ValueError(f"channel {leases[0].channel!r} has a custom "
                             f"window_fn; fused generation needs a plan "
                             f"channel")
        L = leases[0].length
        for a, b in zip(leases, leases[1:]):
            if b.channel != a.channel or b.length != L or b.lo != a.hi:
                raise ValueError(
                    "generate_many needs contiguous equal-length leases of "
                    f"one channel; got [{a.lo},{a.hi}) then [{b.lo},{b.hi}) "
                    f"on {a.channel!r}/{b.channel!r}")
        s = ch.sampler if sampler is None else sampler
        d = ch.out_dtype if out_dtype is None else out_dtype
        if len(leases) == 1:
            # the fuse=1 window fn emits (L, S); keep the documented
            # (W, L, S) contract.  Donation of a 1-window stack would
            # alias the wrong shape — the plain path covers it.
            if retired is not None:
                raise ValueError("donating into a single-window stack is "
                                 "not supported; use generate(lease, "
                                 "retired=...) for W=1")
            return self.generate(leases[0], sampler=s, out_dtype=d)[None]
        with spans.span("blocks.dispatch", leases[0].lo):
            fn = self._window_fn(ch, L, s, d, fuse=len(leases),
                                 donate=retired is not None)
            args = self._ctr_args(leases[0].lo)
            if retired is not None:
                return fn(*args, retired)
            return fn(*args)

    def regenerate(self, name: str, lo: int, length: int, *,
                   sampler: Optional[str] = None,
                   out_dtype: Optional[str] = None) -> Any:
        """The block for an ALREADY-durable window — no lease, no ledger.

        Restart/failover re-enters the middle of a journaled window
        (e.g. a standing pool's current block) through this: the window
        is already committed (and fenced) from the journal, so the new
        owner regenerates its bytes — bit-identical by counter
        addressing — without touching the accounting.  Leasing it again
        would (correctly) raise ``LeaseError``; that refusal is exactly
        why this path must not lease.
        """
        ch = self._channels[name]
        if ch.window_fn is not None:
            return ch.window_fn(lo, lo + length)
        s = ch.sampler if sampler is None else sampler
        d = ch.out_dtype if out_dtype is None else out_dtype
        fn = self._window_fn(ch, length, s, d)
        return fn(*self._ctr_args(lo))

    def take(self, name: str, length: int, **kw) -> Any:
        """lease + generate + commit in one call (synchronous consumers)."""
        lease = self.lease(name, length)
        try:
            block = self.generate(lease, **kw)
        except Exception:
            self.release(lease)
            raise
        self.commit(lease)
        return block

    def producer(self, name: str, block_len: int, *, depth: int = 1,
                 count: Optional[int] = None, start: Optional[int] = None,
                 donate: bool = False, fuse: int = 1,
                 check_ring: bool = False, **gen_kw) -> "BlockProducer":
        """Double-buffered producer over successive leased windows.

        ``start`` pins the first window to ``[start, start + block_len)``
        (explicit ``at=`` leases) — the repositioning hook for resume:
        windows already committed beyond ``start`` raise ``LeaseError``
        unless the ledger was rewound first.

        ``donate=True`` runs the allocation-free steady state: blocks
        cycle through a fixed ring of pre-allocated buffers (see
        ``BlockProducer``).  ``fuse=W`` generates W windows per device
        dispatch via ``generate_windows``.  ``check_ring=True`` asserts
        every donated block's ``unsafe_buffer_pointer()`` stays inside
        the ring (debug aid — forces a sync per block).
        """
        return BlockProducer(self, name, block_len, depth=depth,
                             count=count, start=start, donate=donate,
                             fuse=fuse, check_ring=check_ring, **gen_kw)


# ---------------------------------------------------------------------------
# Double-buffered producer
# ---------------------------------------------------------------------------

class BlockProducer:
    """Standing producer thread: block ``k+1`` is leased and dispatched
    while the consumer holds block ``k`` (the paper's FIFO-into-
    application pipeline).

    The queue holds (lease, block) pairs where ``block`` is a live jax
    array whose computation was *dispatched* by the producer thread —
    never waited on (``block_until_ready``-free handoff); the consumer's
    own ops simply enqueue behind it.  Iterating yields the block and
    COMMITS its lease (consumed randomness enters the durable ledger at
    handoff, so a ledger snapshot between iterations is exact).

    Two delivery levers ride on top of the base pipeline:

      * ``donate=True`` — the allocation-free steady state.  The
        producer pre-allocates a ring of ``depth + 2`` buffers (queue
        depth + the consumer's live block + the one being generated)
        and every window is generated INTO a retiring ring buffer via a
        donated jit (``donate_argnums``), so XLA reuses the allocation
        instead of allocating per window.  Bit-identity with the
        non-donated path is structural (the donated fn full-overwrites
        the retired buffer with the fresh block).  The contract: a
        yielded block is valid only until the NEXT ``__next__`` call —
        fetching block ``k+1`` retires block ``k`` into the ring (copy
        out with ``np.array`` if you need it longer).
      * ``fuse=W`` — W windows per dispatch.  The thread leases W
        contiguous windows atomically (``lease_many``), generates their
        ``(W, L, S)`` stack with ONE fused ``generate_windows`` call
        (one wide window of W*L steps), and enqueues per-window slices;
        commit stays per-block at handoff.  With ``donate=True`` the
        stacks alternate through a producer-local two-buffer ring (the
        enqueued slices are fresh arrays, so the consumer never touches
        ring memory and no validity window applies).

    Example:
        >>> from repro.runtime.blocks import BlockService
        >>> svc = BlockService(seed=11)
        >>> _ = svc.open("docs/demo", num_streams=2)
        >>> with svc.producer("docs/demo", 4, count=2) as prod:
        ...     shapes = [blk.shape for _, blk in prod]
        >>> shapes
        [(4, 2), (4, 2)]
        >>> with svc.producer("docs/demo", 4, count=4, fuse=2) as prod:
        ...     lows = [lease.lo for lease, _ in prod]
        >>> lows                               # fused leases stay per-window
        [8, 12, 16, 20]
    """

    def __init__(self, service: BlockService, name: str, block_len: int, *,
                 depth: int = 1, count: Optional[int] = None,
                 start: Optional[int] = None, donate: bool = False,
                 fuse: int = 1, check_ring: bool = False, **gen_kw):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        fuse = int(fuse)
        if fuse < 1:
            raise ValueError(f"fuse must be >= 1, got {fuse}")
        if (donate or fuse > 1) and service.mesh is not None:
            raise ValueError("donate/fuse producers require a mesh-less "
                             "service; sharded delivery manages its own "
                             "buffers")
        if donate and not donation_supported():
            raise ValueError(
                f"buffer donation is a no-op on backend "
                f"{jax.default_backend()!r}; run without donate=True")
        self._service = service
        self._name = name
        self._block_len = block_len
        self._count = count
        self._pos = start
        self._donate = donate
        self._fuse = fuse
        self._check_ring = check_ring
        self._gen_kw = gen_kw
        self._queue: "queue.Queue" = queue.Queue(maxsize=depth)
        self._recycle: "queue.Queue" = queue.Queue()
        self._ring_ptrs: set = set()
        self._held: Any = None
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._produced = 0
        if donate:
            ch = service.channel(name)
            if ch.window_fn is not None:
                raise ValueError(f"channel {name!r} has a custom window_fn; "
                                 f"donation needs a plan channel")
            s = gen_kw.get("sampler") or ch.sampler
            d = gen_kw.get("out_dtype") or ch.out_dtype
            dtype = sampler_mod.result_dtype(sampler_mod.parse(s), d)
            shape = ((block_len, ch.num_streams) if fuse == 1
                     else (fuse, block_len, ch.num_streams))
            # fuse>1: stacks never leave the thread -> 2 buffers alternate;
            # fuse=1: queue depth + consumer's live block + in-flight gen.
            for _ in range(2 if fuse > 1 else depth + 2):
                buf = jnp.zeros(shape, dtype)
                if check_ring:  # pointer reads sync; debug mode only
                    self._ring_ptrs.add(buf.unsafe_buffer_pointer())
                self._recycle.put(buf)
        self._thread = threading.Thread(
            target=self._work, name=f"blocks:{name}", daemon=True)
        self._thread.start()

    def _get_retired(self) -> Any:
        """Next free ring buffer (None once stop is requested)."""
        while not self._stop.is_set():
            try:
                return self._recycle.get(timeout=0.1)
            except queue.Empty:
                continue
        return None

    def _put(self, item) -> bool:
        """queue.put with stop-polling; False once stop is requested."""
        with spans.span("blocks.put", item[0].lo):
            while not self._stop.is_set():
                try:
                    self._queue.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
        return False

    def _work(self) -> None:
        try:
            while not self._stop.is_set():
                if self._count is not None and self._produced >= self._count:
                    break
                n = self._fuse
                if self._count is not None:
                    n = min(n, self._count - self._produced)
                leases = self._service.lease_many(
                    self._name, self._block_len, n, at=self._pos)
                if self._pos is not None:
                    self._pos += n * self._block_len
                # A short tail (n < fuse) has the wrong stack shape for
                # the ring -> generate it undonated.
                retired = None
                if self._donate and n == self._fuse:
                    retired = self._get_retired()
                    if retired is None:  # stopping
                        for lease in leases:
                            self._service.release(lease)
                        break
                try:
                    if n == 1 and self._fuse == 1:
                        block = self._service.generate(
                            leases[0], retired=retired, **self._gen_kw)
                        pairs = [(leases[0], block)]
                        ring_out = block
                    else:
                        stack = self._service.generate_many(
                            leases, retired=retired, **self._gen_kw)
                        pairs = [(leases[w], stack[w]) for w in range(n)]
                        ring_out = stack
                        if retired is not None:
                            # slices are fresh arrays; the stack cycles
                            # producer-locally
                            self._recycle.put(stack)
                except BaseException:
                    if retired is not None and not retired.is_deleted():
                        self._recycle.put(retired)
                    for lease in leases:
                        self._service.release(lease)
                    raise
                if self._check_ring and retired is not None:
                    ptr = ring_out.unsafe_buffer_pointer()
                    if ptr not in self._ring_ptrs:
                        raise AssertionError(
                            f"donated block escaped the buffer ring: "
                            f"{ptr:#x} not in "
                            f"{sorted(map(hex, self._ring_ptrs))}")
                self._produced += n
                stopped = False
                for idx, pair in enumerate(pairs):
                    if not self._put(pair):
                        for lease, _ in pairs[idx:]:
                            self._service.release(lease)
                        stopped = True
                        break
                if stopped:
                    break
        except BaseException as e:  # surface in the consumer thread
            self._error = e
        finally:
            while not self._stop.is_set():
                try:
                    self._queue.put(None, timeout=0.1)  # end-of-stream
                    break
                except queue.Full:
                    continue

    def __iter__(self) -> "BlockProducer":
        return self

    def __next__(self) -> Tuple[Lease, Any]:
        # the queue wait; its note says whether the consumer found the
        # queue empty, i.e. waited on the producer
        with spans.span("blocks.get") as sp:
            if sp.recording and self._queue.empty():
                sp.note = "empty"
            while True:
                if self._error is not None and self._queue.empty():
                    err, self._error = self._error, None
                    raise err
                try:
                    item = self._queue.get(timeout=0.1)
                    break
                except queue.Empty:
                    continue
            if item is not None:
                sp.window = item[0].lo
        if item is None:
            if self._error is not None:
                err, self._error = self._error, None
                raise err
            raise StopIteration
        lease, block = item
        self._service.commit(lease)
        if self._donate and self._fuse == 1:
            if self._held is not None:
                self._recycle.put(self._held)  # retire block k
            self._held = block
        return lease, block

    def close(self) -> None:
        """Stop the thread and release every unconsumed reservation."""
        self._stop.set()
        self._thread.join(timeout=10.0)
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                self._service.release(item[0])

    def __enter__(self) -> "BlockProducer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# Leased Monte-Carlo app entry points (paper Sec. 6 consumers)
# ---------------------------------------------------------------------------

def _leased_app(service: BlockService, channel: str, num_streams: int,
                length: int, fn: Callable[[Lease], Any]) -> Any:
    """open + lease + run + commit (release on failure) — the shared
    lifecycle of every synchronous leased consumer."""
    service.open(channel, num_streams=num_streams)
    lease = service.lease(channel, length)
    try:
        result = fn(lease)
    except Exception:
        service.release(lease)
        raise
    service.commit(lease)
    return result


def estimate_pi(service: BlockService, *, num_lanes: int,
                draws_per_lane: int, **kw) -> Any:
    """MC pi over a leased draw window: repeated calls consume fresh,
    disjoint randomness of the service family (window units = draws per
    lane; the x/y coordinate purposes share the window)."""
    from repro.kernels import ops
    return _leased_app(
        service, "mc/pi", num_lanes, draws_per_lane,
        lambda lease: ops.estimate_pi(
            seed=service.seed, num_lanes=num_lanes,
            draws_per_lane=draws_per_lane, offset=lease.lo, **kw))


def price_option(service: BlockService, *, num_lanes: int,
                 draws_per_lane: int, **kw) -> Any:
    """Leased-window Black-Scholes MC (see ``estimate_pi``)."""
    from repro.kernels import ops
    return _leased_app(
        service, "mc/option", num_lanes, draws_per_lane,
        lambda lease: ops.price_option(
            seed=service.seed, num_lanes=num_lanes,
            draws_per_lane=draws_per_lane, offset=lease.lo, **kw))
