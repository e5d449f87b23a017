"""The public API surface is documented and its examples actually run.

Two guarantees:

  1. every public symbol carries a substantive docstring (the audit
     list below IS the public surface — extending the API means
     extending the list), and
  2. the ``Example:`` doctest blocks in those docstrings execute
     cleanly, so the documentation can never show code that no longer
     works.
"""
import doctest

import pytest

from repro.core import engine, sampler, stream
from repro.quality import battery, cross, pit
from repro.runtime import blocks, spans
from repro.service import audit, frontend, server, tenants

#: the audited public surface: (symbol, minimum docstring length)
PUBLIC_SYMBOLS = [
    engine.GenPlan,
    engine.make_plan,
    engine.plan_for_stream,
    engine.generate,
    engine.generate_flat,
    engine.generate_sharded,
    engine.generate_windows,
    engine.shift_plan,
    engine.sample,
    engine.family_from_seed,
    engine.derive_leaf,
    engine.leaf_table,
    engine.select_backend,
    stream.ThunderStream,
    stream.new_stream,
    stream.derive,
    stream.split,
    stream.advance,
    stream.random_bits,
    stream.uniforms,
    stream.normals,
    stream.uniform,
    stream.normal,
    stream.bernoulli,
    stream.gumbel,
    stream.categorical,
    sampler.parse,
    sampler.apply,
    sampler.result_dtype,
    sampler.fma_guard,
    sampler.remix_bits,
    sampler.poisson_thresholds,
    sampler.gamma_mt_constants,
    sampler.alias_table,
    sampler.exponential_from_bits,
    sampler.gamma_from_bits,
    sampler.categorical_from_bits,
    pit.regularized_gamma_p,
    pit.continuous_cdf,
    pit.discrete_cdf_table,
    pit.pit_words,
    cross.pairwise_sweep,
    blocks.BlockService,
    blocks.BlockService.open,
    blocks.BlockService.lease,
    blocks.BlockService.lease_many,
    blocks.BlockService.commit,
    blocks.BlockService.release,
    blocks.BlockService.ledger_state,
    blocks.BlockService.restore_ledger,
    blocks.BlockService.generate,
    blocks.BlockService.generate_many,
    blocks.BlockService.take,
    blocks.BlockService.producer,
    blocks.Lease,
    blocks.BlockProducer,
    spans.span,
    spans.recorded,
    spans.clear,
    battery.run_battery,
    tenants.tenant_region,
    tenants.TenantRegistry,
    frontend.RandRequest,
    frontend.Coalescer,
    frontend.class_channel,
    server.ServerConfig,
    server.RandServer,
    server.RandServer.submit,
    server.RandServer.request,
    server.RandServer.stats,
    audit.Journal,
    audit.replay,
    audit.verify_ledger_disjoint,
]

#: symbols whose docstring must include a runnable ``>>>`` example
EXAMPLE_BEARING = [
    engine.GenPlan, engine.generate, engine.generate_sharded,
    engine.generate_windows,
    engine.sample,
    stream.ThunderStream, stream.new_stream, stream.derive, stream.split,
    stream.advance, stream.random_bits, stream.uniforms, stream.normals,
    stream.uniform, stream.normal, stream.bernoulli, stream.gumbel,
    stream.categorical,
    sampler.parse, sampler.apply, sampler.result_dtype,
    sampler.poisson_thresholds, sampler.alias_table,
    pit.regularized_gamma_p, pit.discrete_cdf_table, pit.pit_words,
    blocks.BlockService, blocks.Lease, blocks.BlockProducer, spans.span,
    battery.run_battery,
    tenants.tenant_region, tenants.TenantRegistry,
    frontend.RandRequest, server.RandServer, audit.Journal, audit.replay,
]


@pytest.mark.parametrize("symbol", PUBLIC_SYMBOLS,
                         ids=lambda s: getattr(s, "__qualname__",
                                               getattr(s, "__name__", str(s))))
def test_public_symbol_has_docstring(symbol):
    doc = symbol.__doc__
    assert doc is not None and len(doc.strip()) >= 40, (
        f"{symbol!r} needs a substantive docstring (the public surface is "
        f"documentation-audited; see README / docs/)")


@pytest.mark.parametrize("symbol", EXAMPLE_BEARING,
                         ids=lambda s: getattr(s, "__qualname__",
                                               getattr(s, "__name__", str(s))))
def test_public_symbol_has_example(symbol):
    assert ">>>" in symbol.__doc__, (
        f"{symbol!r} must carry a runnable Example: doctest block")


@pytest.mark.parametrize("module", [engine, sampler, stream, blocks,
                                    spans, tenants, frontend, server, audit,
                                    pit],
                         ids=lambda m: m.__name__)
def test_doctests_run_clean(module):
    results = doctest.testmod(module, verbose=False)
    assert results.failed == 0, (
        f"{results.failed}/{results.attempted} doctests failed in "
        f"{module.__name__}")
    assert results.attempted > 0, f"no doctests collected in {module.__name__}"


def test_quality_battery_doctest():
    """run_battery's example runs a real tiny battery (ref backend +
    raw-LCG ablation) — slowest doctest, kept in its own test node."""
    results = doctest.testmod(battery, verbose=False)
    assert results.failed == 0, f"{results.failed} doctests failed"
    assert results.attempted > 0
