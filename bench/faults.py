"""Faults planted under the timed path, and the control.

The benchmark's own runs plant nothing.  ``bench/control.py`` and the
tests plant one of these to show that the comparison which decides
``correct`` fails when the timed path is wrong:

* ``wrong_counter``  every window served one counter step past its lease
                     (the MISRN cells' control: it breaks counter
                     addressing, the guarantee those configurations state);
* ``stale_state``    the counter never advances: every window or call
                     repeats the first one's randomness;
* ``half_batch``     half of the streams (lanes) left out: zeros in a
                     MISRN window, the other half's values in a pricing
                     call, so its mean is taken over the rest;
* ``altered``        one sample (one lane's payoff sum) altered where it
                     is produced;
* ``shard_identity`` every shard of a sharded window holds shard 0's
                     streams, as if the shards were not told their place;
* ``reference_bf16`` the pricing cell's control: the plain reference, in
                     bfloat16, put in the program's place.

Each fault patches program functions only while it is planted.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict


class Fault:
    name = ""
    drivers: tuple = ()

    def patches(self, cell) -> Dict[Any, Dict[str, Any]]:
        """{module or class: {attribute: replacement}}."""
        return {}

    @contextlib.contextmanager
    def planted(self, cell):
        if cell.driver_name not in self.drivers:
            raise ValueError(f"fault {self.name!r} does not apply to driver "
                             f"{cell.driver_name!r}")
        saved = []
        try:
            for owner, attrs in self.patches(cell).items():
                for attr, new in attrs.items():
                    saved.append((owner, attr, getattr(owner, attr)))
                    setattr(owner, attr, new)
            yield self
        finally:
            for owner, attr, old in reversed(saved):
                setattr(owner, attr, old)

    def after_setup(self, cell, state) -> None:
        pass


def _ctr_shift(fn):
    """Wrap BlockService._ctr_args: counter of a window -> fn(lo)."""
    from repro.runtime import blocks
    orig = blocks.BlockService._ctr_args

    def ctr_args(self, lo):
        return orig(self, fn(lo))
    return {blocks.BlockService: {"_ctr_args": ctr_args}}


def _rows_shift(fn):
    """Wrap engine.root_and_ctr_rows: traced counter -> fn(counter)."""
    from repro.core import engine
    orig = engine.root_and_ctr_rows

    def rows(x0, ctr, n):
        return orig(x0, fn(ctr), n)
    return {engine: {"root_and_ctr_rows": rows}}


def _wrap_generate(post):
    from repro.core import engine
    orig = engine.generate

    def generate(plan, **kw):
        return post(orig(plan, **kw))
    return {engine: {"generate": generate}}


def _wrap_option(post):
    from repro.kernels import mc
    orig = mc.option_partials_from_plans

    def partials(px, py, **kw):
        return post(orig(px, py, **kw))
    return {mc: {"option_partials_from_plans": partials}}


class WrongCounter(Fault):
    name = "wrong_counter"
    drivers = ("misrn", "mc_option")

    def patches(self, cell):
        if cell.driver_name == "misrn":
            return _ctr_shift(lambda lo: lo + 1)
        from repro.core import u64
        return _rows_shift(lambda c: u64.add64(c, u64.const64(1)))


class StaleState(Fault):
    name = "stale_state"
    drivers = ("misrn", "mc_option")

    def patches(self, cell):
        if cell.driver_name == "misrn":
            return _ctr_shift(lambda lo: 0)
        import jax.numpy as jnp
        return _rows_shift(lambda c: (jnp.zeros_like(c[0]),
                                      jnp.zeros_like(c[1])))


class HalfBatch(Fault):
    name = "half_batch"
    drivers = ("misrn", "mc_option")

    def patches(self, cell):
        if cell.driver_name == "misrn":
            def post(out):
                half = out.shape[1] // 2
                return out.at[:, half:].set(0)
            return _wrap_generate(post)

        def post(part):
            half = part.shape[1] // 2
            return part.at[:, half:2 * half].set(part[:, :half])
        return _wrap_option(post)


class Altered(Fault):
    name = "altered"
    drivers = ("misrn", "mc_option")

    def patches(self, cell):
        if cell.driver_name == "misrn":
            return _wrap_generate(lambda out: out.at[0, 0].set(out[0, 0] ^ 1))
        return _wrap_option(lambda part: part.at[:, 0].multiply(2.0))


class ShardIdentity(Fault):
    name = "shard_identity"
    drivers = ("misrn",)

    def patches(self, cell):
        import jax.numpy as jnp
        from repro.core import engine
        orig = engine.generate_sharded
        n = 1
        for d in cell.config["mesh"]["shape"]:
            n *= d

        def generate_sharded(plan, **kw):
            out = orig(plan, **kw)
            local = out.shape[1] // n
            return jnp.tile(out[:, :local], (1, n))
        return {engine: {"generate_sharded": generate_sharded}}


class ReferenceBf16(Fault):
    name = "reference_bf16"
    drivers = ("mc_option",)

    def after_setup(self, cell, state) -> None:
        import jax.numpy as jnp
        import numpy as np
        from bench.reference import ctr, option
        cfg, tr = cell.config, cell.traffic
        cols = np.arange(cfg["num_lanes"])
        x = ctr.Stream(state.seed, cfg["purpose_x"], cols)
        y = ctr.Stream(state.seed, cfg["purpose_y"], cols)
        o = cfg["option"]
        params = (o["s0"], o["strike"], o["r"], o["sigma"], o["t"])
        state.call = lambda lo: option.lane_sums(
            lo, tr["draws_per_call"], x, y, params, dtype=jnp.bfloat16)
        state.call(0).block_until_ready()   # compile before the window


FAULTS = {f.name: f for f in (WrongCounter(), StaleState(), HalfBatch(),
                              Altered(), ShardIdentity(), ReferenceBf16())}
