"""Property-based tests (hypothesis) for the system's invariants.

Covers the paper's lemmas at the data-structure level (FIFO queue order,
single-signal), the GCR admission state machine (work conservation,
active-set bound modulo transient promotion, no stream lost), simulator
determinism, the dropless MoE (every routed row computed, tokens
independent of one another),
and the L2 cluster layer: for random seeds, workloads, router policies,
scale-event schedules, staleness, and truncation points - routers never
place onto a retired replica, ``completed + live + migrating == offered``
everywhere, telemetry percentiles are monotone in q, and fleet runs are
pure functions of their seeds.  The L2 cases all flow through
``repro.cluster.invariants.guarded_case``, the same driver
``tests/test_cluster.py`` pins on a deterministic grid.
"""

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.admission import GCRAdmission
from repro.core.pod_aware import GCRPod
from repro.core.simulator import run_sim

# ---------------------------------------------------------------------------
# GCR admission state machine
# ---------------------------------------------------------------------------

ops = st.lists(
    st.one_of(
        st.tuples(st.just("offer"), st.integers(0, 49), st.integers(0, 3)),
        st.tuples(st.just("release"), st.integers(0, 49), st.integers(0, 3)),
    ),
    min_size=1, max_size=200)


@settings(max_examples=200, deadline=None)
@given(ops=ops, limit=st.integers(1, 8), promote=st.integers(2, 32))
def test_admission_invariants(ops, limit, promote):
    adm = GCRAdmission(active_limit=limit, promote_every=promote)
    offered = set()
    for op, sid, _pod in ops:
        if op == "offer" and sid not in offered and sid not in adm.active:
            adm.offer(sid)
            offered.add(sid)
        elif op == "release" and sid in adm.active:
            adm.release(sid)
            offered.discard(sid)
        # invariant: active set bounded by limit + 1 (transient promotion)
        assert adm.num_active <= limit + 1
        # invariant: no stream both active and parked
        parked_ids = {s.stream_id for s in adm.queue}
        assert not (set(adm.active) & parked_ids)
    # work conservation: if below limit, nothing is parked
    if adm.num_active < limit:
        assert adm.num_parked == 0


@settings(max_examples=100, deadline=None)
@given(ops=ops, limit=st.integers(1, 8), pods=st.integers(1, 4))
def test_pod_admission_invariants(ops, limit, pods):
    adm = GCRPod(active_limit=limit, n_pods=pods, promote_every=8,
                 pod_rotate_every=16)
    offered = set()
    for op, sid, pod in ops:
        if op == "offer" and sid not in offered and sid not in adm.active:
            adm.offer(sid, pod)
            offered.add(sid)
        elif op == "release" and sid in adm.active:
            adm.release(sid)
            offered.discard(sid)
        assert adm.num_active <= limit + 1
        parked = {s.stream_id for q in adm.pod_queues for s in q}
        assert not (set(adm.active) & parked)
    if adm.num_active < limit:
        assert adm.num_parked == 0


@settings(max_examples=50, deadline=None)
@given(n=st.integers(2, 30), n_offer=st.integers(2, 40))
def test_admission_fifo_order(n, n_offer):
    """Parked streams are admitted in FIFO order (queue Lemma 4 analogue)."""
    adm = GCRAdmission(active_limit=1, promote_every=10**9)
    adm.offer(0)
    for sid in range(1, n_offer):
        adm.offer(sid)
    order = []
    cur = 0
    while True:
        newly = adm.release(cur)
        if not newly:
            break
        order.extend(newly)
        cur = newly[-1]
    assert order == sorted(order)


# ---------------------------------------------------------------------------
# Simulator determinism + monotone sanity
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.sampled_from([2, 8, 40, 64]),
       lock=st.sampled_from(["ttas", "mcs_spin", "gcr(mcs_spin)",
                             "gcr_numa(pthread)"]))
def test_simulator_deterministic(seed, n, lock):
    a = run_sim(lock, n, seed=seed, duration_us=5_000)
    b = run_sim(lock, n, seed=seed, duration_us=5_000)
    assert a.total_ops == b.total_ops
    assert a.per_thread_ops == b.per_thread_ops
    assert a.handoff_sum_us == b.handoff_sum_us


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 100))
def test_simulator_ops_conserved(seed):
    r = run_sim("gcr(ttas)", 16, seed=seed, duration_us=10_000)
    assert sum(r.per_thread_ops) == r.total_ops
    assert 0.5 <= r.unfairness <= 1.0


# ---------------------------------------------------------------------------
# Dropless MoE properties
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 1000), routing=st.sampled_from([(4, 1), (8, 2)]),
       skew=st.sampled_from([0.0, 6.0]))
def test_dropless_tokens_are_independent(seed, routing, skew):
    """Every (token, expert) row is computed, and a token's output is what
    it gets when run alone: no token's routing takes an expert from
    another, also when most tokens pick the same one (``skew``)."""
    import jax
    import jax.numpy as jnp

    from repro.models.moe import moe_mlp, moe_params

    E, k = routing
    B, S, D = 2, 32, 16   # a skewed expert gets more than S*k/E rows
    kp, kx = jax.random.split(jax.random.key(seed))
    p = moe_params(kp, D, 32, E, jnp.float32)
    base = jnp.ones((D,)) / jnp.sqrt(D)
    x = jax.random.normal(kx, (B, S, D)) + skew * base
    p["router"] = p["router"].at[:, 0].add(skew * base)
    # one wrapper over the same function: compiled once per shape and (E, k)
    moe = jax.jit(moe_mlp, static_argnames=("n_experts", "top_k"))
    out, aux = moe(p, x, n_experts=E, top_k=k)
    assert int(aux["moe_rows"]) == B * S * k
    alone = np.stack([
        np.asarray(moe(p, x[b:b + 1, s:s + 1], n_experts=E, top_k=k)[0])[0, 0]
        for b in range(B) for s in range(S)])
    np.testing.assert_allclose(np.asarray(out).reshape(B * S, D), alone,
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# L2 cluster fleet invariants (random seeds x workloads x routers x
# scale-event schedules x staleness x truncation)
# ---------------------------------------------------------------------------

_schedules = st.lists(
    st.tuples(st.sampled_from(["out", "in", "out_pod", "in_pod", "none"]),
              st.integers(0, 3)),
    min_size=0, max_size=6)


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000),
       kind=st.sampled_from(["poisson", "bursty", "diurnal", "sessions"]),
       router=st.sampled_from(
           ["round_robin", "least_outstanding", "p2c", "gcr_aware",
            "affinity", "prefix_aware"]),
       schedule=_schedules,
       cut=st.sampled_from([400.0, 900.0, 2_000.0, 60_000.0]),
       staleness=st.sampled_from([0.0, 80.0]))
def test_fleet_invariants_fuzzed(seed, kind, router, schedule, cut,
                                 staleness):
    """guarded_case asserts: placement liveness (PlacementGuard), request
    conservation at the cutoff, and percentile monotonicity."""
    from repro.cluster.invariants import guarded_case
    guarded_case(seed, kind, router, tuple(schedule), max_ms=cut,
                 staleness_ms=staleness)


def _fault_schedules():
    """Random ``FaultSchedule``s over a 4-replica pool: limp/blackout
    windows inside the 900 ms workload, crashes with optional restart,
    both loss policies, plus out-of-pool replica ids (must be inert)."""
    from repro.cluster import Blackout, Crash, FaultSchedule, Limplock
    rep = st.integers(0, 5)                  # 4..5 are out-of-pool
    win = st.tuples(st.floats(0.0, 700.0), st.floats(20.0, 400.0))
    limps = st.lists(
        st.builds(lambda r, w, f: Limplock(r, w[0], w[0] + w[1], factor=f),
                  rep, win, st.floats(2.0, 12.0)),
        min_size=0, max_size=2)
    crashes = st.lists(
        st.builds(lambda r, t, dt, pol: Crash(
            r, t, restart_ms=(None if dt is None else t + dt), policy=pol),
            rep, st.floats(50.0, 800.0),
            st.one_of(st.none(), st.floats(50.0, 500.0)),
            st.sampled_from(["requeue", "lose"])),
        min_size=0, max_size=2)
    blks = st.lists(
        st.builds(lambda r, w: Blackout(r, w[0], w[0] + w[1]), rep, win),
        min_size=0, max_size=2)
    return st.builds(FaultSchedule, limplocks=limps, crashes=crashes,
                     blackouts=blks)


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000),
       router=st.sampled_from(
           ["round_robin", "least_outstanding", "p2c", "gcr_aware",
            "affinity", "prefix_aware"]),
       schedule=_schedules,
       faults=_fault_schedules(),
       hedge_ms=st.sampled_from([0.0, 250.0, 600.0]),
       cut=st.sampled_from([400.0, 900.0, 2_000.0, 60_000.0]),
       staleness=st.sampled_from([0.0, 80.0]))
def test_fault_plane_invariants_fuzzed(seed, router, schedule, faults,
                                       hedge_ms, cut, staleness):
    """Copy-space conservation, placement liveness, and percentile
    monotonicity hold under arbitrary interleavings of scale events,
    limplock, crash/restart (both policies), signal blackouts, hedging,
    and health-driven ejection (health only when the bus is periodic)."""
    from repro.cluster import HealthPolicy, HedgePolicy
    from repro.cluster.invariants import guarded_case
    guarded_case(
        seed, "sessions", router, tuple(schedule), max_ms=cut,
        staleness_ms=staleness, n_replicas=4, faults=faults,
        health=(HealthPolicy(stale_ms=200.0) if staleness else None),
        hedge=(HedgePolicy(delay_ms=hedge_ms) if hedge_ms else None))


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 5_000),
       router=st.sampled_from(["gcr_aware", "affinity", "p2c"]),
       n_replicas=st.sampled_from([32, 48, 64]),
       cut=st.sampled_from([173.5, 411.25, 902.125, 60_000.0]))
def test_fleet_invariants_at_scale_knobs(seed, router, n_replicas, cut):
    """The vectorized-core scale regime: >= 32-replica fleets with the
    virtual clock truncated at fractional-millisecond cuts (mid
    calendar-bucket, mid step, mid migration) - placement liveness,
    conservation, and percentile monotonicity must all hold, and the run
    must be a pure function of its seeds (bit-identical re-run)."""
    import dataclasses

    from repro.cluster.invariants import guarded_case

    def go():
        return guarded_case(seed, "sessions", router, (), max_ms=cut,
                            duration_ms=700.0, n_replicas=n_replicas)

    assert dataclasses.asdict(go()) == dataclasses.asdict(go())


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1_000),
       router=st.sampled_from(["p2c", "affinity", "gcr_aware"]),
       staleness=st.sampled_from([0.0, 60.0]))
def test_fleet_runs_are_pure_functions_of_seeds(seed, router, staleness):
    import dataclasses

    from repro.cluster import (FleetConfig, WorkloadSpec, knee_cost,
                               run_fleet, sessions)
    spec = WorkloadSpec(prompt_range=(128, 512), gen_range=(32, 128),
                        n_pods=2)
    cfg = FleetConfig(n_replicas=3, admission="gcr", active_limit=32,
                      n_pods=2, cost=knee_cost(spec, 32, oversub=2.0),
                      prefix_cache_tokens=50_000)
    reqs = sessions(300.0, 700.0, spec, seed=seed)

    def go():
        return run_fleet(reqs, router, cfg, max_ms=60_000.0,
                         staleness_ms=staleness,
                         jitter_ms=(10.0 if staleness else 0.0),
                         signal_seed=seed)

    assert dataclasses.asdict(go()) == dataclasses.asdict(go())


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000),
       kind=st.sampled_from(["poisson", "bursty", "diurnal", "sessions",
                             "uniform"]),
       rps=st.floats(50.0, 400.0))
def test_workload_generators_fuzzed(seed, kind, rps):
    """Same seed => identical stream; arrivals sorted and in-window; rids
    unique; session prefix chains are exact conversation histories."""
    from repro.cluster import WorkloadSpec, make_workload
    spec = WorkloadSpec(prompt_range=(128, 512), gen_range=(32, 128),
                        n_pods=2)
    a = make_workload(kind, rps, 800.0, spec, seed)
    b = make_workload(kind, rps, 800.0, spec, seed)
    assert a == b
    assert all(0.0 <= r.arrive_ms < 800.0 for r in a)
    assert [r.arrive_ms for r in a] == sorted(r.arrive_ms for r in a) \
        or kind == "uniform"      # uniform keeps legacy draw order
    assert len({r.rid for r in a}) == len(a)
    if kind == "sessions":
        by_sess = {}
        for r in a:
            assert r.prefix_id == r.session_id >= 0
            by_sess.setdefault(r.session_id, []).append(r)
        for turns in by_sess.values():
            assert turns[0].prefix_len == 0
            assert len({t.pod for t in turns}) == 1
            for prev, cur in zip(turns, turns[1:]):
                assert cur.prefix_len == prev.prompt_len + prev.gen_len
    else:
        assert all(r.session_id == -1 and r.prefix_len == 0 for r in a)


@settings(max_examples=50, deadline=None)
@given(vals=st.lists(st.floats(0.0, 1e6), max_size=60),
       q1=st.floats(0.01, 1.0), q2=st.floats(0.01, 1.0))
def test_percentile_monotone_in_q(vals, q1, q2):
    from repro.cluster import percentile
    lo, hi = min(q1, q2), max(q1, q2)
    svals = sorted(vals)
    assert percentile(svals, lo) <= percentile(svals, hi)
    if svals:
        assert percentile(svals, 1.0) == svals[-1]
        assert min(svals) <= percentile(svals, lo) <= max(svals)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 60))
def test_session_trace_replay_roundtrip(seed, n):
    from repro.cluster import WorkloadSpec, replay, sessions, to_trace
    spec = WorkloadSpec(prompt_range=(128, 512), gen_range=(32, 128),
                        n_pods=2)
    reqs = sessions(float(10 * n), 900.0, spec, seed=seed)
    assert replay(to_trace(reqs)) == reqs


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), groups=st.integers(1, 12),
       zipf=st.floats(0.5, 2.0))
def test_shared_prefix_group_sessions_fuzzed(seed, groups, zipf):
    """Grouped sessions: prefix_id is a valid group for every turn, one
    group and one system-prompt length per session, history chains on
    top of the shared prefix, and the trace round-trips."""
    from repro.cluster import WorkloadSpec, replay, sessions, to_trace
    spec = WorkloadSpec(prompt_range=(128, 512), gen_range=(32, 128),
                        n_pods=2)
    reqs = sessions(250.0, 900.0, spec, seed=seed, prefix_groups=groups,
                    group_zipf=zipf)
    assert replay(to_trace(reqs)) == reqs
    by_sess = {}
    for r in reqs:
        assert 0 <= r.prefix_id < groups
        by_sess.setdefault(r.session_id, []).append(r)
    for turns in by_sess.values():
        assert len({t.prefix_id for t in turns}) == 1
        assert turns[0].prefix_len > 0
        assert turns[0].prompt_len > turns[0].prefix_len
        for prev, cur in zip(turns, turns[1:]):
            assert cur.prefix_len == prev.prompt_len + prev.gen_len


# ---------------------------------------------------------------------------
# Gradient compression properties
# ---------------------------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(seed=st.integers(0, 10_000), scale=st.floats(1e-4, 1e3))
def test_int8_quantization_bounded_error(seed, scale):
    import jax.numpy as jnp

    from repro.optim.compression import dequantize_int8, quantize_int8

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(128).astype(np.float32) * scale)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_error_feedback_reduces_bias():
    import jax.numpy as jnp

    from repro.optim.compression import (compress_with_feedback,
                                         dequantize_int8,
                                         init_error_feedback)

    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.standard_normal(256).astype(np.float32))}
    err = init_error_feedback(g)
    acc_plain = np.zeros(256, np.float32)
    acc_fb = np.zeros(256, np.float32)
    for _ in range(50):
        (qs, e_new) = compress_with_feedback(g, err)
        err = e_new
        acc_fb += np.asarray(dequantize_int8(*qs["w"]))
        q, s = __import__("repro.optim.compression",
                          fromlist=["quantize_int8"]).quantize_int8(g["w"])
        acc_plain += np.asarray(dequantize_int8(q, s))
    true = np.asarray(g["w"]) * 50
    assert np.abs(acc_fb - true).mean() <= np.abs(acc_plain - true).mean() + 1e-4
