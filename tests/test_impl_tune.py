"""Impl axis (xla|pallas) + the block-size autotune stage (schema v6)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.engine import Engine
from repro.core.plan import ExecutionPlan, PlanError
from repro.kernels import ops, ref

FAST = dict(preset=0, iters=1, warmup=0)


def _plan(**kw):
    return ExecutionPlan(**{**FAST, **kw})


# -- plan / dispatch plumbing ------------------------------------------------


def test_plan_rejects_unknown_impl():
    with pytest.raises(PlanError, match="impl"):
        _plan(impl="cuda")


def test_tune_space_registry_covers_every_pallas_op():
    for op in ops.PALLAS_OPS:
        space = ops.tune_space(op)
        assert space and all(isinstance(c, dict) for c in space), op
    with pytest.raises(KeyError, match="unknown pallas op"):
        ops.tune_space("not_a_kernel")


def test_force_impl_scopes_params_to_the_named_op():
    # Params merge only into the named op; other ops still switch to the
    # forced mode but keep their own defaults. Explicit call-site modes
    # always win over the ambient force.
    with ops.force_impl("pallas", "matmul", block_m=8):
        use, _, blocks = ops._resolve("matmul", "auto", {})
        assert use and blocks == {"block_m": 8}
        use, _, blocks = ops._resolve("softmax", "auto", {})
        assert use and blocks == {}
        use, _, _ = ops._resolve("matmul", "ref", {})
        assert not use
    # Outside the context auto-dispatch is back to the backend default.
    use, _, blocks = ops._resolve("matmul", "auto", {})
    assert use == ops.on_tpu() and blocks == {}


# -- numerical agreement across the whole tune space -------------------------

_RTOL = dict(matmul=2e-4, attention=2e-4)


def _agreement_cases():
    key = jax.random.key(0)
    ka, kb, kc = jax.random.split(key, 3)
    a = jax.random.normal(ka, (48, 40), jnp.float32)
    b = jax.random.normal(kb, (40, 56), jnp.float32)
    x4 = jax.random.normal(kc, (2, 16, 8, 8), jnp.float32)
    q = jax.random.normal(ka, (1, 2, 32, 16), jnp.float32)
    kv = jax.random.normal(kb, (1, 2, 32, 16), jnp.float32)
    xs = jax.random.normal(kc, (1000,), jnp.float32)
    xm = 5.0 * jax.random.normal(ka, (33, 130), jnp.float32)
    return {
        "matmul": ((a, b), lambda *t: ops.matmul(*t), lambda *t: ref.matmul_ref(*t)),
        "attention": (
            (q, kv, kv),
            lambda *t: ops.attention(*t),
            lambda *t: ref.attention_ref(*t),
        ),
        "softmax": ((xm,), lambda *t: ops.softmax(*t), lambda *t: ref.softmax_ref(*t)),
        "lrn": ((x4,), lambda *t: ops.lrn(*t), lambda *t: ref.lrn_ref(*t)),
        "avgpool": ((x4,), lambda *t: ops.avgpool(*t), lambda *t: ref.avgpool_ref(*t)),
        "prefix_scan": (
            (xs,),
            lambda *t: ops.prefix_scan(*t),
            lambda *t: ref.prefix_scan_ref(*t),
        ),
    }


@pytest.mark.parametrize("op", sorted(_agreement_cases()))
def test_pallas_agrees_with_ref_for_every_tune_candidate(op):
    # The tuner may pick any candidate; each one must be a correct
    # implementation (the block clamps make oversized candidates legal on
    # small shapes), exercised through the same force_impl path the
    # engine's trace-time context uses.
    args, fn, oracle = _agreement_cases()[op]
    want = np.asarray(oracle(*args), np.float32)
    for cand in ops.tune_space(op):
        with ops.force_impl("pallas", op, **cand):
            got = np.asarray(fn(*args), np.float32)
        tol = _RTOL.get(op, 1e-5)
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol, err_msg=str(cand))


# -- engine: impl joins the cache key, fallbacks are recorded -----------------


def test_impl_joins_compile_cache_key():
    eng = Engine()
    for impl, misses in (("xla", 1), ("pallas", 2)):
        res = eng.run(_plan(names=("gemm_f32_nn",), include_backward=False, impl=impl))
        (rec,) = res.records
        assert rec.status == "ok" and rec.impl == impl
        assert eng.cache.misses == misses
    # Same pallas plan against the warm engine: pure hits.
    eng.run(_plan(names=("gemm_f32_nn",), include_backward=False, impl="pallas"))
    assert eng.cache.misses == 2 and eng.cache.hits > 0


def test_pallas_record_fields_and_interpret_flag():
    res = Engine().run(_plan(names=("softmax",), include_backward=False, impl="pallas"))
    (rec,) = res.records
    assert rec.status == "ok" and rec.impl == "pallas"
    assert rec.impl_fallback is None
    # Off-TPU the kernel runs in interpreter mode and the record says so;
    # xla rows carry no flag at all.
    assert rec.impl_interpret == (jax.default_backend() != "tpu")
    assert rec.tuned_params is None and rec.tune_trials is None
    assert res.metadata.impl == "pallas" and res.metadata.tune is False
    xla = Engine().run(_plan(names=("softmax",), include_backward=False))
    assert xla.records[0].impl == "xla" and xla.records[0].impl_interpret is None


def test_fallbacks_are_recorded_not_silent():
    # No Pallas variant: the pass runs as xla and says why.
    res = Engine().run(_plan(names=("pathfinder",), include_backward=False, impl="pallas"))
    (rec,) = res.records
    assert rec.status == "ok"
    assert rec.impl == "xla" and rec.impl_fallback == "no_pallas_variant"
    # Backward passes fall back per-pass: forward is pallas, backward xla.
    res = Engine().run(_plan(names=("softmax",), impl="pallas"))
    fwd, bwd = res.records
    assert fwd.impl == "pallas" and fwd.impl_fallback is None
    assert bwd.impl == "xla" and bwd.impl_fallback == "backward_pass"


# -- the tune stage -----------------------------------------------------------


def _tune_plan(**kw):
    return _plan(names=("softmax",), include_backward=False, impl="pallas",
                 tune=True, **kw)


def test_tuner_is_deterministic_for_a_fixed_seed(monkeypatch):
    # Pin the trial timer (the seam _stage_tune documents): candidate i of
    # the sweep costs times[i]. Two fresh engines must elect the same
    # winner — the sweep order is the declared tune_space order and ties
    # break to the earliest candidate.
    space = ops.tune_space("softmax")
    times = [5.0, 1.0, 3.0, 4.0][: len(space)]
    calls = []

    def fake_trial(self, entry, args, plan):
        calls.append(None)
        return times[(len(calls) - 1) % len(space)]

    monkeypatch.setattr(Engine, "_time_tune_trial", fake_trial)
    recs = []
    for _ in range(2):
        res = Engine().run(_tune_plan())
        (rec,) = res.records
        assert rec.status == "ok", rec.error
        recs.append(rec)
    assert recs[0].tuned_params == recs[1].tuned_params == dict(space[1])
    assert all(r.tune_trials == len(space) for r in recs)
    assert all(r.tune_trials_us is not None and r.tune_trials_us > 0 for r in recs)


def test_tuner_tie_keeps_the_earliest_candidate(monkeypatch):
    monkeypatch.setattr(Engine, "_time_tune_trial", lambda self, e, a, p: 1.0)
    res = Engine().run(_tune_plan())
    (rec,) = res.records
    assert rec.tuned_params == dict(ops.tune_space("softmax")[0])


def test_tuned_winner_persists_and_warm_run_skips_the_sweep(tmp_path, monkeypatch):
    monkeypatch.setattr(Engine, "_time_tune_trial", lambda self, e, a, p: 1.0)
    cold = Engine(cache_dir=str(tmp_path))
    (rec,) = cold.run(_tune_plan()).records
    assert rec.status == "ok", rec.error
    assert rec.tune_trials == len(ops.tune_space("softmax"))
    assert rec.tuned_params is not None
    assert cold.disk_cache.tune_stores == 1
    # A new engine against the same --cache-dir restores the winner (zero
    # trials) AND the executable (zero retraces, zero XLA compiles).
    warm = Engine(cache_dir=str(tmp_path))
    (rec2,) = warm.run(_tune_plan()).records
    assert rec2.status == "ok", rec2.error
    assert rec2.tune_trials == 0 and rec2.tune_trials_us == 0.0
    assert rec2.tuned_params == rec.tuned_params
    assert warm.disk_cache.tune_hits == 1 and warm.disk_cache.tune_stores == 0
    assert warm.disk_cache.misses == 0 and warm.disk_cache.fallback_count == 0
    assert warm.disk_cache.hits > 0


def test_tune_is_a_noop_for_xla_and_untunable_passes():
    # tune on an xla plan: no sweep, no tune columns.
    res = Engine().run(_plan(names=("softmax",), include_backward=False, tune=True))
    (rec,) = res.records
    assert rec.tuned_params is None and rec.tune_trials is None
    # A kernel with a single-candidate space wins by default at 0 trials.
    res = Engine().run(
        _plan(names=("srad",), include_backward=False, impl="pallas", tune=True)
    )
    (rec,) = res.records
    assert rec.status == "ok", rec.error
    assert rec.tuned_params == {} and rec.tune_trials == 0
