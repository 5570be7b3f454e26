"""Tests of the design-space explorer (``core.hls.dse``): structural
fingerprints, Pareto-front computation, the bank-merging knob, the
``explore_design`` sweep (serial and pooled, with serial fallback when no
process pool is available), and adversarial per-function codegen cache-key
collision checks."""

import numpy as np
import pytest

from repro.core import ir
from repro.core.gallery import GALLERY
from repro.core.hls import (DSEConfig, design_space, erase_schedule,
                            explore_design, hls_schedule, merge_local_banks,
                            pareto_front)
from repro.core.hls.dse import (DSEPoint, dominates, fingerprint_func,
                                fingerprint_module, has_mergeable_banks)
from repro.core.lower import simulate


# ---------------------------------------------------------------------------
# Fingerprints
# ---------------------------------------------------------------------------


def test_fingerprint_stable_across_builds():
    """Two separate builds allocate different global value ids; the
    positional namer must hash them identically."""
    m1, _ = GALLERY["gemm"].build()
    m2, _ = GALLERY["gemm"].build()
    assert fingerprint_module(erase_schedule(m1)) == \
        fingerprint_module(erase_schedule(m2))


def test_fingerprint_differs_on_structural_change():
    m1, _ = GALLERY["gemm"].build(8)
    m2, _ = GALLERY["gemm"].build(4)
    assert fingerprint_module(erase_schedule(m1)) != \
        fingerprint_module(erase_schedule(m2))


def test_fingerprint_extra_distinguishes_options():
    m, entry = GALLERY["transpose"].build()
    f = erase_schedule(m).get(entry)
    assert fingerprint_func(f, extra=("a",)) != fingerprint_func(f, extra=("b",))


# ---------------------------------------------------------------------------
# Pareto front
# ---------------------------------------------------------------------------


def _pt(lat, lut, ff, verified=True, error=None):
    return DSEPoint(config=DSEConfig(), latency_cycles=int(lat),
                    latency_ns=float(lat), lut=lut, ff=ff,
                    verified=verified, error=error)


def test_dominates():
    assert dominates((1.0, 10, 10), (2.0, 10, 10))
    assert not dominates((1.0, 10, 10), (1.0, 10, 10))   # equal: no
    assert not dominates((1.0, 20, 10), (2.0, 10, 10))   # tradeoff: no


def test_pareto_front_filters_and_sorts():
    pts = [
        _pt(100, 50, 50),                    # dominated by the next point
        _pt(100, 40, 40),
        _pt(200, 10, 10),                    # tradeoff: slower but smaller
        _pt(50, 90, 90),                     # tradeoff: faster but bigger
        _pt(10, 1, 1, verified=False),       # would win, but unverified
        _pt(10, 1, 1, error="boom"),         # would win, but errored
        _pt(200, 10, 10),                    # duplicate objective vector
    ]
    front = pareto_front(pts)
    assert [p.objectives() for p in front] == [
        (50.0, 90, 90, 0), (100.0, 40, 40, 0), (200.0, 10, 10, 0)]


def test_pareto_front_keeps_dsp_tradeoff():
    """Same latency/LUT/FF but fewer DSPs (a time-multiplexed candidate)
    must survive as a distinct frontier point — DSP is a real objective."""
    a = _pt(100, 40, 40)
    a.dsp = 48
    b = _pt(120, 40, 40)          # slower ...
    b.dsp = 3                     # ... but 16x fewer multipliers
    front = pareto_front([a, b])
    assert len(front) == 2
    c = _pt(100, 40, 40)
    c.dsp = 3                     # dominates a outright (equal lat, less dsp)
    assert pareto_front([a, c]) == [c]


def test_design_space_dedups_min_ii_when_sequential():
    space = design_space(pipeline=(True, False), min_ii=(1, 2, 4))
    seq = [c for c in space if not c.pipeline]
    assert len(seq) == 1 and seq[0].min_ii == 1   # min_ii collapsed
    assert len([c for c in space if c.pipeline]) == 3
    assert space == design_space(pipeline=(True, False), min_ii=(1, 2, 4))


# ---------------------------------------------------------------------------
# Bank merging
# ---------------------------------------------------------------------------


def test_merge_local_banks_retypes_and_stays_correct():
    gal = GALLERY["gemm"]
    m, entry = gal.build(4)
    um = erase_schedule(m)
    assert has_mergeable_banks(um)
    n = merge_local_banks(um)
    assert n > 0
    for f in um.funcs.values():
        for op in f.body.walk():
            if op.opname == "alloc":
                for r in op.results:
                    mt = r.type
                    if isinstance(mt, ir.MemrefType) and mt.kind in (
                            ir.KIND_LUTRAM, ir.KIND_BRAM):
                        assert not mt.distributed   # fully packed now
    # the serialized-bank design still schedules and computes correctly
    hls_schedule(um)
    from repro.core.passes import run_pipeline

    run_pipeline(um)
    ins = gal.make_inputs(4)
    simulate(um, entry, ins)
    np.testing.assert_array_equal(ins[-1], gal.oracle(*ins[:2]))


# ---------------------------------------------------------------------------
# explore_design
# ---------------------------------------------------------------------------


def _gemm_setup(n=4):
    gal = GALLERY["gemm"]
    m, entry = gal.build(n)
    ins = gal.make_inputs(n)
    return m, entry, ins, gal.oracle(*ins[:2])


def test_explore_design_serial_smoke():
    m, entry, ins, exp = _gemm_setup()
    space = design_space(clock_ns=(10.0, 5.0), merge_banks=(False, True))
    res = explore_design(m, space, entry=entry, inputs=ins, expected=exp)
    assert len(res.points) == len(space)
    assert all(p.verified for p in res.points), \
        [p.error for p in res.points if not p.verified]
    assert res.front, "empty Pareto frontier"
    assert all(p.verified for p in res.front)
    # frontier points are mutually non-dominated
    for p in res.front:
        assert not any(dominates(q.objectives(), p.objectives())
                       for q in res.front if q is not p)


def test_explore_design_pool_matches_serial():
    m, entry, ins, exp = _gemm_setup()
    space = design_space(clock_ns=(10.0, 5.0))
    r1 = explore_design(m, space, entry=entry, inputs=ins, expected=exp,
                        max_workers=1)
    r2 = explore_design(m, space, entry=entry, inputs=ins, expected=exp,
                        max_workers=2)
    assert [p.as_dict() for p in r1.points] == [p.as_dict() for p in r2.points]
    assert [p.as_dict() for p in r1.front] == [p.as_dict() for p in r2.front]


def test_explore_design_input_module_untouched():
    m, entry, ins, exp = _gemm_setup()
    from repro.core.printer import print_module

    before = print_module(m)
    explore_design(m, design_space(), entry=entry, inputs=ins, expected=exp)
    assert print_module(m) == before


def test_explore_design_scores_out_bad_candidate():
    """A candidate that cannot compile lands in the cloud with its error and
    stays off the frontier instead of killing the sweep."""
    m, entry, ins, exp = _gemm_setup()
    space = [DSEConfig(clock_ns=5.0), DSEConfig(clock_ns=-1.0)]
    res = explore_design(m, space, entry=entry, inputs=ins, expected=exp)
    good = [p for p in res.points if p.error is None]
    bad = [p for p in res.points if p.error is not None]
    assert len(good) >= 1 and len(bad) >= 1
    assert all(p.config.clock_ns > 0 for p in res.front)


# ---------------------------------------------------------------------------
# Pool fallback: a broken process pool degrades to serial, never crashes
# ---------------------------------------------------------------------------


def test_pool_map_warns_and_returns_none_when_pool_broken(monkeypatch):
    from repro.core import pool

    def boom(*a, **kw):
        raise OSError("no semaphores here")

    monkeypatch.setattr(pool, "ProcessPoolExecutor", boom)
    with pytest.warns(RuntimeWarning, match="falling back to serial"):
        assert pool.pool_map(len, ["ab", "cde"], max_workers=4) is None


def test_explore_design_pooled_falls_back_serially(monkeypatch):
    m, entry, ins, exp = _gemm_setup()
    space = design_space(clock_ns=(10.0, 5.0))
    r1 = explore_design(m, space, entry=entry, inputs=ins, expected=exp)

    from repro.core import pool

    def boom(*a, **kw):
        raise OSError("no semaphores here")

    monkeypatch.setattr(pool, "ProcessPoolExecutor", boom)
    with pytest.warns(RuntimeWarning, match="falling back to serial"):
        r2 = explore_design(m, space, entry=entry, inputs=ins, expected=exp,
                            max_workers=4)
    assert [p.as_dict() for p in r1.points] == [p.as_dict() for p in r2.points]


#: set by the test below in the parent only; a forked worker would inherit
#: it, a spawned one imports this module afresh
_PARENT_ONLY = False


def _worker_report(_):
    import jax

    return _PARENT_ONLY, jax.config.jax_platforms, jax.default_backend()


def test_pool_after_parent_jax_call_uses_fresh_cpu_workers(monkeypatch):
    """A pool opened after the parent touched its device spawns workers (no
    forked copy of the parent's device client) that run on the CPU, and the
    DSE sweep on it completes with the serial sweep's result."""
    import warnings

    import jax.numpy as jnp

    from repro.core import pool

    jnp.ones(4).block_until_ready()
    monkeypatch.setattr(__import__(__name__), "_PARENT_ONLY", True)
    m, entry, ins, _exp = _gemm_setup()
    space = design_space(clock_ns=(10.0, 5.0))
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)  # no serial fallback
        reports = pool.pool_map(_worker_report, [0, 1], 2)
        pooled = explore_design(m, space, entry=entry, inputs=ins,
                                max_workers=2)
    assert reports == [(False, "cpu", "cpu")] * 2
    serial = explore_design(m, space, entry=entry, inputs=ins)
    assert [p.as_dict() for p in pooled.points] == \
        [p.as_dict() for p in serial.points]
    assert all(p.verified for p in pooled.points)


# ---------------------------------------------------------------------------
# Adversarial cache-key collisions: modules sharing a function fingerprint
# but differing in pipeline spec, scheduler options, clock, backend or
# hierarchy must never share a per-function codegen cache entry
# ---------------------------------------------------------------------------


@pytest.fixture()
def _fresh_func_cache():
    from repro.core.hls.dse import (COMPILE_CACHE, FUNC_CODEGEN_CACHE,
                                    SCHEDULE_CACHE)

    for c in (SCHEDULE_CACHE, COMPILE_CACHE, FUNC_CODEGEN_CACHE):
        c.clear()
    yield FUNC_CODEGEN_CACHE
    for c in (SCHEDULE_CACHE, COMPILE_CACHE, FUNC_CODEGEN_CACHE):
        c.clear()


def _compile_ctx(**kw):
    from repro.core.hls.scheduler import hls_compile

    m, entry = GALLERY["gemm"].build(4)
    return hls_compile(m, entry=entry, **kw)


@pytest.mark.parametrize("first,second", [
    (dict(hierarchy="modules"), dict(hierarchy="modules", backend="vhdl")),
    (dict(hierarchy="modules"), dict(hierarchy="inline")),
    (dict(hierarchy="modules"), dict(hierarchy="modules", pipeline="")),
    (dict(hierarchy="modules"),
     dict(hierarchy="modules", pipeline_loops=False)),
], ids=["backend", "hierarchy", "pipeline-spec", "sched-opts"])
def test_func_cache_keys_never_collide_across_context(
        first, second, _fresh_func_cache):
    _compile_ctx(**first)
    h0 = _fresh_func_cache.hits
    _compile_ctx(**second)
    assert _fresh_func_cache.hits == h0, (first, second)


def test_func_cache_keys_never_collide_across_clock(_fresh_func_cache):
    from repro.core.hls.scheduler import SchedulerOptions

    _compile_ctx(hierarchy="modules", options=SchedulerOptions(clock_ns=4.0))
    h0 = _fresh_func_cache.hits
    _compile_ctx(hierarchy="modules", options=SchedulerOptions(clock_ns=2.0))
    assert _fresh_func_cache.hits == h0
