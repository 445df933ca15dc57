"""jaxcheck: the cost model's exactness and donation proof, the
MEMPLAN_r01 artifact contract (anchors ±10%, measured fit/OOM
verdicts all reproduced, the 2.7B OOM explained), the recompile
sentinel's bucketed/unbucketed A/B storm, and the hostsync probe."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_rm_tpu.analysis.jaxcheck import (
    costmodel,
    hostsync,
    memplan,
    recompile,
)

REPO = Path(__file__).parent.parent
BUDGET_BYTES = memplan.USABLE_GIB * (2 ** 30)


# -- cost model --------------------------------------------------------------

def test_selfcheck_is_green():
    assert costmodel.selfcheck() == []


def test_matmul_flops_exact():
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    est = costmodel.estimate(jnp.matmul, a, b)
    assert est.flops == 2 * 64 * 32 * 128
    assert est.unknown_primitives == {}


def test_donation_prevents_double_buffering():
    """The tentpole claim in miniature: without donation the update's
    input AND output buffers are live together; donating the argument
    lets the output alias it."""
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)  # 4 MiB
    nbytes = 1024 * 1024 * 4

    donated = costmodel.estimate(
        jax.jit(lambda v: v + 1.0, donate_argnums=(0,)), x)
    plain = costmodel.estimate(jax.jit(lambda v: v + 1.0), x)
    assert donated.peak_bytes < 2 * nbytes
    assert plain.peak_bytes >= 2 * nbytes
    assert donated.donation_savings_bytes > 0


def test_train_step_donation_savings_cover_state():
    """A NON-donated train step double-buffers the TrainState: the
    walker prices the real jitted step both ways and the gap is at
    least the params bytes (state out cannot alias state in)."""
    rung = memplan.Rung("tiny", "tiny", "adafactor", 4, 2, "dots")
    cfg, state, step, batch = memplan._build_step(rung)
    est = costmodel.estimate(step, state, batch)
    params_bytes = memplan._tree_bytes(state.params)
    assert est.donation_savings_bytes >= params_bytes
    assert est.unknown_primitives == {}


# -- the MEMPLAN artifact ----------------------------------------------------

@pytest.fixture(scope="module")
def plan():
    with open(REPO / "MEMPLAN_r01.json", encoding="utf-8") as f:
        return json.load(f)


def test_memplan_anchor_deltas_within_10pct(plan):
    anchored = [r for r in plan["rungs"] if "anchor" in r]
    assert len(anchored) >= 4   # 1.2B x2, 2.7B, 3.1B
    for r in anchored:
        assert abs(r["anchor"]["delta_pct"]) <= 10.0, \
            f"{r['name']}: {r['anchor']}"


def test_memplan_reproduces_every_measured_verdict(plan):
    """Predicted fit/OOM matches the measured BENCH_SWEEP_r05 outcome
    on every scale row — including the mb1-vs-mb2 and remat-policy
    flips at 2.1B, which a state-bytes-only model cannot get right."""
    measured = [r for r in plan["rungs"] if "measured" in r]
    assert len(measured) >= 10
    assert {r["measured"].get("ran", False) for r in measured} == \
        {True, False}  # both outcomes represented
    for r in measured:
        assert r["verdict_matches_measured"], r["name"]


def test_memplan_explains_2_7b_oom(plan):
    rows = [r for r in plan["rungs"] if r["preset"] == "bench_2_7b"]
    assert rows, "2.7B rungs missing"
    for r in rows:
        assert not r["predicted"]["fit"]
        assert r["predicted"]["peak_gb"] * 1e9 * \
            (1 + memplan.HBM_MARGIN) > BUDGET_BYTES
    assert "2.7B" in plan["oom_explanation"]


def test_memplan_extrapolation_rows(plan):
    offload = {o["name"]: o for o in
               plan["extrapolation"]["host_offload"]}
    row_27 = next(v for k, v in offload.items() if k.startswith("2.7B"))
    row_7b = next(v for k, v in offload.items() if k.startswith("7B"))
    # host-streamed optimizer update fits the rung that OOMs today...
    assert row_27["fit"]
    # ...but cannot rescue 7B: params+grads alone exceed the chip
    assert not row_7b["fit"]
    assert row_7b["params_plus_grads_gb"] * 1e9 > BUDGET_BYTES
    star = plan["extrapolation"]["north_star_v5p8"]
    assert star["predicted_per_chip_peak_gb"] < star["per_chip_hbm_gb"]
    full_7b = next(r for r in plan["rungs"]
                   if r["preset"] == "llama2_7b")
    assert not full_7b["predicted"]["fit"]


def test_memplan_artifact_is_not_stale():
    """Re-run the planner on one rung and compare to the checked-in
    artifact — a drifted cost model or config fails here, not in CI
    archaeology."""
    with open(REPO / "MEMPLAN_r01.json", encoding="utf-8") as f:
        plan = json.load(f)
    rung = memplan.LADDER[0]
    fresh = memplan.plan_rung(rung)
    stored = next(r for r in plan["rungs"] if r["name"] == rung.name)
    assert fresh["predicted"]["peak_gb"] == \
        pytest.approx(stored["predicted"]["peak_gb"], rel=5e-3)
    assert fresh["predicted"]["fit"] == stored["predicted"]["fit"]


# -- recompile sentinel ------------------------------------------------------

@pytest.fixture()
def sentinel():
    recompile.set_enabled(True)
    recompile.reset()
    yield recompile
    recompile.set_enabled(False)
    recompile.reset()


@pytest.fixture(scope="module")
def model():
    from kubeflow_rm_tpu.models import LlamaConfig, init_params
    cfg = LlamaConfig.tiny()
    return cfg, init_params(cfg, jax.random.key(0))


def test_sentinel_bucketed_storm_stays_bounded(sentinel, model):
    """Green arm: a ragged-length prefill storm through the engine
    holds the signature count at <= log2(slot_len)+1 — the invariant
    the prefill buckets exist to enforce — and the REAL jit cache
    grows by no more than that."""
    from kubeflow_rm_tpu.models import paging
    from kubeflow_rm_tpu.models.generate import ContinuousBatchingEngine

    cfg, params = model
    slot_len = 32
    cache_before = paging.paged_prefill._cache_size()
    decode_before = paging.paged_decode_step._cache_size()
    eng = ContinuousBatchingEngine(params, cfg, slots=2,
                                   slot_len=slot_len)
    rng = np.random.default_rng(7)
    for n in (1, 2, 3, 5, 7, 9, 11, 13, 15, 16):   # 10 ragged lengths
        eng.submit(rng.integers(1, cfg.vocab_size, size=n).tolist(),
                   max_new_tokens=2)
    eng.run()

    limit = slot_len.bit_length()
    rep = sentinel.report()
    assert rep["engine.prefill"]["calls"] == 10
    assert rep["engine.prefill"]["signatures"] <= limit
    assert rep["engine.decode_step"]["signatures"] == 1
    assert sentinel.over_limit() == []
    assert paging.paged_prefill._cache_size() - cache_before <= limit
    # the step hands the decode program two host arrays (tokens int32,
    # live mask bool): one signature for the sentinel, one program
    assert paging.paged_decode_step._cache_size() - decode_before <= 1
    (sig,) = recompile._entries["engine.decode_step"]["signatures"]
    assert sig == (((2,), "int32"), ((2,), "bool"))


def test_sentinel_unbucketed_storm_grows_unbounded(sentinel):
    """Red arm (lockgraph A/B convention): the same storm WITHOUT
    bucketing compiles once per distinct length — the sentinel flags
    it with witness stacks and the real jit cache shows the growth."""
    f = jax.jit(lambda x: x.sum())
    sentinel.set_limit("unbucketed.prefill", 6)
    sentinel.track("unbucketed.prefill", f)
    for n in range(1, 11):
        x = jnp.zeros((1, n), jnp.int32)
        sentinel.note("unbucketed.prefill", x)
        f(x).block_until_ready()

    findings = sentinel.over_limit()
    assert len(findings) == 1
    hit = findings[0]
    assert hit["signatures"] == 10 and hit["limit"] == 6
    assert hit["jit_cache_size"] == 10      # one real compile per length
    assert hit["witnesses"] and "test_jaxcheck" in \
        hit["witnesses"][0]["stack"]


def test_sentinel_off_records_nothing():
    recompile.set_enabled(False)
    recompile.reset()
    recompile.note("ghost", jnp.zeros((3,)))
    recompile.set_limit("ghost", 1)
    assert recompile.report() == {}


# -- hostsync probe ----------------------------------------------------------

@pytest.fixture()
def probe():
    hostsync.set_enabled(True)
    hostsync.reset()
    assert hostsync.install()
    yield hostsync
    hostsync.uninstall()
    hostsync.set_enabled(False)
    hostsync.reset()


def test_hostsync_witnesses_implicit_syncs_in_region(probe):
    x = jnp.asarray(1.0)
    with probe.region("decode-loop"):
        bool(x > 0)
        float(x)
        np.asarray(x)
    kinds = [w["kind"] for w in probe.witnesses()]
    assert "__bool__" in kinds and "__float__" in kinds \
        and "np.asarray" in kinds
    w = probe.witnesses()[0]
    assert w["region"] == "decode-loop"
    assert "test_jaxcheck" in w["stack"]


def test_engine_dispatch_region_holds_no_implicit_sync(probe, model):
    """The step loop's hot regions launch programs and read nothing
    back: the pick's one transfer a step is outside them, deliberate,
    and counted by the engine itself. A sync planted inside the decode
    call is witnessed under ``engine.dispatch``."""
    from unittest import mock

    from kubeflow_rm_tpu.models import paging
    from kubeflow_rm_tpu.models.generate import ContinuousBatchingEngine

    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, slots=4, slot_len=32)
    rng = np.random.default_rng(9)
    for n in (3, 5, 9, 4, 7):
        eng.submit(rng.integers(1, cfg.vocab_size, size=n).tolist(),
                   max_new_tokens=4)
    eng.run()
    assert eng.stats()["host_syncs_total"] >= 4
    assert probe.witnesses() == []

    real = paging.paged_decode_step

    def leaky(params, cfg, cache, tokens, active):
        np.asarray(cache.pos_next)
        return real(params, cfg, cache, tokens, active)

    with mock.patch.object(paging, "paged_decode_step", leaky):
        eng.submit([1, 2, 3], max_new_tokens=3)
        eng.run()
    seen = probe.witnesses()
    assert seen and {w["region"] for w in seen} == {"engine.dispatch"}
    assert {w["kind"] for w in seen} == {"np.asarray"}


def test_hostsync_ignores_syncs_outside_regions(probe):
    x = jnp.asarray(2.0)
    float(x)                      # a deliberate log-boundary sync
    assert probe.witnesses() == []


def test_hostsync_disabled_region_is_free():
    hostsync.set_enabled(False)
    cm = hostsync.region("anything")
    assert cm is hostsync.region("anything-else")   # shared null CM


def test_hostsync_sanctioned_tallies_instead_of_witnessing(probe):
    """The offload stream's escape hatch: syncs under sanctioned() are
    counted per (site, kind), not witnessed — the probe stays useful
    as observability while the deliberate transfers stop tripping it."""
    x = jnp.asarray(3.0)
    with probe.region("train.step"):
        with probe.sanctioned("train.offload_stream"):
            np.asarray(x)
            float(x)
    assert probe.witnesses() == []
    counts = probe.sanctioned_counts()
    assert counts[("train.offload_stream", "np.asarray")] == 1
    assert counts[("train.offload_stream", "__float__")] == 1


def test_hostsync_unsanctioned_sync_still_trips(probe):
    """Teeth check: a sync in the same hot region but OUTSIDE the
    sanctioned context is still a witness — sanctioning one site must
    not blanket the whole region."""
    x = jnp.asarray(4.0)
    with probe.region("train.step"):
        with probe.sanctioned("train.offload_stream"):
            np.asarray(x)
        float(x)                  # the bug the probe exists to catch
    kinds = [w["kind"] for w in probe.witnesses()]
    assert kinds == ["__float__"]
    assert probe.witnesses()[0]["region"] == "train.step"


def test_hostsync_sanctioned_disabled_is_free():
    hostsync.set_enabled(False)
    cm = hostsync.sanctioned("any-site")
    assert cm is hostsync.sanctioned("other-site")  # shared null CM


def test_hostsync_reset_clears_sanctioned_tallies(probe):
    x = jnp.asarray(5.0)
    with probe.region("r"), probe.sanctioned("s"):
        float(x)
    assert probe.sanctioned_counts()
    probe.reset()
    assert probe.sanctioned_counts() == {}
