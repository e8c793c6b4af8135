"""Serving-tier pins: kernel parity, vote semantics, snapshot-under-churn.

Three contracts. (1) The Pallas ``voted_predict_batched`` path answers
bitwise identically to the jnp ``serve_voted`` einsum path and to the
``cache.voted_predict`` oracle — across cache fill levels (count 1,
partial, wrapped ring), odd query-batch sizes and N not a multiple of the
node block. (2) The exact voting semantics of ``voted_predict``: the
``p_ratio == 0.5`` tie goes positive, a zero score votes positive, and the
rule intentionally diverges from ``ensemble._weighted_vote_err``'s
score-sum vote. (3) Serving never perturbs the protocol: a run with a
serve hook produces bitwise the curves of a run without one, snapshots are
bitwise identical across engines, and served answers are reproducible for
a fixed seed."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import numpy.testing as npt
import pytest

from repro.configs.gossip_linear import GossipLinearConfig
from repro.core import cache as cache_mod
from repro.core import serving
from repro.core.cache import ModelCache, cache_add, init_cache
from repro.core.simulation import run_simulation
from repro.kernels.voted_predict import voted_predict_batched
from repro.launch.gossip_serve import GossipServer


def _filled_cache(n: int, c: int, d: int, adds: int, seed: int) -> ModelCache:
    """A cache after ``adds`` all-node cache_add rounds (count = 1 + adds,
    saturating at c; adds > c wraps the ring so ptr has lapped it)."""
    cache = init_cache(n, c, d)
    key = jax.random.key(seed)
    for i in range(adds):
        key, sub = jax.random.split(key)
        w_new = jax.random.normal(sub, (n, d), jnp.float32)
        cache = cache_add(cache, jnp.ones(n, bool), w_new,
                          jnp.full((n,), i + 1, jnp.int32))
    return cache


def _queries(m: int, n: int, d: int, seed: int):
    key = jax.random.key(seed)
    X = jax.random.normal(jax.random.fold_in(key, 0), (m, d), jnp.float32)
    assign = jax.random.randint(jax.random.fold_in(key, 1), (m,), 0, n)
    return X, assign.astype(jnp.int32)


# --------------------------------------------------------------------------
# (1) batched-vote parity: kernel == jnp path == voted_predict oracle
# --------------------------------------------------------------------------

# (n, c, d, m, adds): count=1 fresh cache, partially filled, wrapped ring;
# odd m; n=33 not a multiple of the node block; d off the 128-lane grid
PARITY_CASES = [
    (8, 4, 8, 8, 0),        # count == 1 everywhere (init model only)
    (33, 5, 57, 11, 3),     # partial fill, odd batch, off-block n and d
    (16, 4, 16, 37, 9),     # ring wrapped twice
    (10, 3, 128, 1, 2),     # single-query batch, lane-aligned d
    (9, 8, 30, 5, 20),      # deep wrap, c on the sublane boundary
]


@pytest.mark.parametrize("n,c,d,m,adds", PARITY_CASES)
def test_serve_voted_matches_oracle(n, c, d, m, adds):
    """jnp serve path == row-gathered cache.voted_predict, bitwise."""
    cache = _filled_cache(n, c, d, adds, seed=n * d + adds)
    X, assign = _queries(m, n, d, seed=m)
    got = serving.serve_voted(cache.w, cache.count, X, assign)
    full = cache_mod.voted_predict(cache, X)        # (N, m)
    exp = full[assign, jnp.arange(m)]
    npt.assert_array_equal(np.asarray(got), np.asarray(exp))


@pytest.mark.parametrize("n,c,d,m,adds", PARITY_CASES)
def test_kernel_matches_jnp_path(n, c, d, m, adds):
    """Pallas voted_predict_batched == serve_voted, bitwise, and the
    serve_voted_kernel wrapper == the direct kernel call."""
    cache = _filled_cache(n, c, d, adds, seed=n * d + adds)
    X, assign = _queries(m, n, d, seed=m + 1)
    exp = serving.serve_voted(cache.w, cache.count, X, assign)
    direct = voted_predict_batched(cache.w[assign], cache.count[assign], X,
                                   interpret=True)
    wrapped = serving.serve_voted_kernel(cache.w, cache.count, X, assign,
                                         interpret=True)
    npt.assert_array_equal(np.asarray(direct), np.asarray(exp))
    npt.assert_array_equal(np.asarray(wrapped), np.asarray(exp))


def test_serve_fresh_matches_predict_fresh():
    cache = _filled_cache(12, 4, 19, 6, seed=5)
    X, assign = _queries(23, 12, 19, seed=9)
    fresh_w, _ = cache_mod.freshest(cache)
    got = serving.serve_fresh(fresh_w, X, assign)
    exp = cache_mod.predict_fresh(cache, X)[assign, jnp.arange(23)]
    npt.assert_array_equal(np.asarray(got), np.asarray(exp))


# --------------------------------------------------------------------------
# (2) voting semantics of cache.voted_predict — edge cases pinned
# --------------------------------------------------------------------------

def _cache_with_scores(first_coords, count=None):
    """One node whose cache slots score exactly ``first_coords`` against the
    query x = e_0 — every score is an exactly-representable small float, so
    the sign tests below are free of rounding."""
    first_coords = np.asarray(first_coords, np.float32)
    c = len(first_coords)
    w = np.zeros((1, c, 4), np.float32)
    w[0, :, 0] = first_coords
    cnt = np.array([c if count is None else count], np.int32)
    return ModelCache(jnp.asarray(w), jnp.zeros((1, c), jnp.int32),
                      jnp.asarray(cnt), jnp.asarray(cnt))


X_E0 = jnp.asarray(np.eye(1, 4, dtype=np.float32))      # the query e_0


def test_voted_predict_tie_breaks_positive():
    """p_ratio == 0.5 exactly (2 of 4 votes positive) predicts +1: the
    ``p_ratio - 0.5 >= 0`` rule at cache.py:81 ties up, never -1."""
    cache = _cache_with_scores([1.0, 2.0, -1.0, -2.0])
    pred = cache_mod.voted_predict(cache, X_E0)
    npt.assert_array_equal(np.asarray(pred), [[1.0]])
    # the serving paths inherit the same tie-break
    got = serving.serve_voted(cache.w, cache.count, X_E0,
                              jnp.zeros(1, jnp.int32))
    gotk = serving.serve_voted_kernel(cache.w, cache.count, X_E0,
                                      jnp.zeros(1, jnp.int32),
                                      interpret=True)
    npt.assert_array_equal(np.asarray(got), [1.0])
    npt.assert_array_equal(np.asarray(gotk), [1.0])


def test_voted_predict_one_below_tie_is_negative():
    """1 of 4 votes positive -> p_ratio 0.25 < 0.5 -> -1 (the tie-break
    boundary is sharp: exactly half is +1, strictly below is -1)."""
    cache = _cache_with_scores([1.0, -1.0, -1.0, -2.0])
    pred = cache_mod.voted_predict(cache, X_E0)
    npt.assert_array_equal(np.asarray(pred), [[-1.0]])


def test_voted_predict_zero_score_votes_positive():
    """score == 0 votes +1 (the ``scores >= 0`` sign convention): a zero
    model — every node's cache slot 0 at init — is a positive voter, so
    the init-only cache predicts +1 everywhere."""
    cache = _cache_with_scores([0.0, -1.0], count=2)
    # zero score votes +1 -> 1 of 2 positive -> tie -> +1
    pred = cache_mod.voted_predict(cache, X_E0)
    npt.assert_array_equal(np.asarray(pred), [[1.0]])
    init = init_cache(3, 4, 4)
    npt.assert_array_equal(
        np.asarray(cache_mod.voted_predict(init, X_E0)), np.ones((3, 1)))
    npt.assert_array_equal(
        np.asarray(serving.serve_voted_kernel(
            init.w, init.count, X_E0, jnp.zeros(1, jnp.int32),
            interpret=True)), [1.0])


def test_voted_predict_diverges_from_score_sum_vote():
    """Algorithm 4 counts ±1 votes; ``ensemble._weighted_vote_err`` sums raw
    scores (ensemble.py:45) — intentionally different rules. A cache of two
    weak positives and one strong negative splits them: majority vote +1,
    score sum 1 + 1 - 10 < 0 -> -1."""
    from repro.core.ensemble import _weighted_vote_err
    coords = [1.0, 1.0, -10.0]
    cache = _cache_with_scores(coords)
    pred_vote = cache_mod.voted_predict(cache, X_E0)
    npt.assert_array_equal(np.asarray(pred_vote), [[1.0]])
    # same three models through the score-sum rule: predicts -1, so its
    # error against the +1 label is 1.0
    W = cache.w[0]                                   # (3, 4) model bank
    err_sum = _weighted_vote_err(W, X_E0, jnp.ones(1, jnp.float32))
    assert float(err_sum) == 1.0


# --------------------------------------------------------------------------
# (3) snapshots: non-perturbing, engine-parity, reproducible answers
# --------------------------------------------------------------------------

N_NODES, DIM, CYCLES, EVAL_EVERY = 96, 12, 12, 4


def _sim_setup(seed=0):
    from repro.data.synthetic import make_linear_dataset
    rng = np.random.default_rng(seed)
    X, y = make_linear_dataset(rng, N_NODES + 64, DIM, noise=0.07,
                               separation=2.5)
    cfg = GossipLinearConfig(name="serve-test", dim=DIM, n_nodes=N_NODES,
                             n_test=64, class_ratio=(1, 1), lam=1e-3,
                             cache_size=4)
    return cfg, X[:N_NODES], y[:N_NODES], X[N_NODES:], y[N_NODES:]


def _run(engine, serve_hook=None, scenario=None):
    """``engine`` is ``"reference"``, ``"sharded"`` (the packing chosen per
    chunk) or ``"sharded-<packing>"`` (one packing forced for every
    chunk)."""
    cfg, X, y, Xt, yt = _sim_setup()
    if scenario is not None:
        from repro.configs.gossip_linear import with_failure_scenario
        cfg = with_failure_scenario(cfg, scenario)
    engine, _, packing = engine.partition("-")
    kw = dict(compact_mode=packing) if packing else {}
    return run_simulation(cfg, X, y, Xt, yt, cycles=CYCLES,
                          eval_every=EVAL_EVERY, seed=3, engine=engine,
                          serve_hook=serve_hook, **kw)


def _result_fields(res):
    """Every field of a SimResult but its span record."""
    return {f.name: getattr(res, f.name) for f in dataclasses.fields(res)
            if f.name != "trace"}


@pytest.fixture(scope="module")
def reference_snapshots():
    """``get(scenario)``: the reference engine's snapshots at every eval
    point, on the host, computed once a scenario."""
    by_scenario = {}

    def get(scenario):
        if scenario not in by_scenario:
            snaps = by_scenario[scenario] = {}
            _run("reference", scenario=scenario,
                 serve_hook=lambda cycle, snap: snaps.__setitem__(
                     cycle, jax.tree.map(np.array, snap)))
        return by_scenario[scenario]
    return get


@pytest.mark.parametrize("engine", ["reference", "sharded", "sharded-dense",
                                    "sharded-compact", "sharded-compact_all"])
@pytest.mark.parametrize("scenario", [None, "extreme"])
def test_serving_does_not_perturb_the_run(engine, scenario,
                                          reference_snapshots):
    """A hooked run (snapshots taken AND queries served at every eval
    point) produces bitwise the same SimResult as an unhooked run, under
    every packing of the sharded engine, and its snapshots are bitwise the
    reference engine's at every eval point. The sharded engine routes the
    next chunk before it calls the hook, so this also pins that the hook
    sees nothing of that routing."""
    srv = GossipServer(batch_size=16, seed=1)
    qX = np.asarray(_sim_setup()[3][:24], np.float32)
    snaps = {}

    def hook(cycle, snap):
        snaps[cycle] = jax.tree.map(np.array, snap)
        srv.serve_hook(cycle, snap)
        srv.submit(qX)

    hooked = _run(engine, serve_hook=hook, scenario=scenario)
    srv.flush()
    assert srv.stats().queries == 24 * len(hooked.cycles)
    clean = _run(engine, scenario=scenario)
    npt.assert_array_equal(np.asarray(hooked.err_fresh),
                           np.asarray(clean.err_fresh))
    npt.assert_array_equal(np.asarray(hooked.err_voted),
                           np.asarray(clean.err_voted))
    assert _result_fields(hooked) == _result_fields(clean)
    if "-" in engine:
        assert hooked.compaction["chunk_modes"][engine.split("-")[1]] == len(
            hooked.cycles)
    ref = reference_snapshots(scenario)
    assert sorted(snaps) == sorted(ref) == hooked.cycles
    for cyc in ref:
        for field, a, b in zip(serving.QuerySnapshot._fields, ref[cyc],
                               snaps[cyc]):
            npt.assert_array_equal(a, b, err_msg=f"cycle {cyc}: {field}")


def test_snapshot_parity_reference_vs_sharded():
    """Snapshots at every eval point are bitwise identical across engines
    (the serving-tier extension of the engine parity contract). The hook
    copies to host immediately: the sharded scan donates its carry."""
    def collect(store):
        def hook(cycle, snap):
            store[cycle] = jax.tree.map(np.array, snap)
        return hook

    ref_snaps, sh_snaps = {}, {}
    _run("reference", serve_hook=collect(ref_snaps))
    _run("sharded", serve_hook=collect(sh_snaps))
    assert sorted(ref_snaps) == sorted(sh_snaps) and ref_snaps
    for cyc in ref_snaps:
        for field, a, b in zip(serving.QuerySnapshot._fields,
                               ref_snaps[cyc], sh_snaps[cyc]):
            npt.assert_array_equal(a, b, err_msg=f"cycle {cyc}: {field}")


def test_sharded_snapshot_refers_to_the_carry():
    """``snapshot_from_carry`` builds nothing on the device: each leaf is
    a lane of the carry (freshest model 2-3, cache 4, 5 and 7, clock 13);
    that lanes 2-3 hold ``cache.freshest`` is pinned by the parity tests
    above."""
    carry = tuple(jnp.full((2,), i) for i in range(14))
    snap = serving.snapshot_from_carry(carry)
    lanes = [next(i for i, a in enumerate(carry) if a is leaf)
             for leaf in snap]
    assert lanes == [4, 5, 7, 2, 3, 13]


def test_last_hook_runs_without_the_payload_buffer():
    """The sharded engine frees the state the last snapshot does not read
    (models, payload buffer) before its last hook, so that what the hook
    allocates stays under the run's peak: the (D, N, d) buffer is live at
    every hook but the last."""
    cfg = _sim_setup()[0]
    from repro.configs.gossip_linear import with_failure_scenario
    D = with_failure_scenario(cfg, "extreme").delay_max_cycles
    seen = {}

    def hook(cycle, snap):
        seen[cycle] = any(a.shape == (D, N_NODES, DIM)
                          for a in jax.live_arrays())

    res = _run("sharded", serve_hook=hook, scenario="extreme")
    assert D > 1 and len(res.cycles) > 1
    assert seen == {c: c != res.cycles[-1] for c in res.cycles}


@pytest.mark.parametrize("use_kernel", [False, True])
def test_served_answers_reproducible_and_kernel_invariant(use_kernel):
    """Same seed + same submission order -> bitwise identical answers, on
    both serve paths — and the kernel path answers == the jnp path."""
    def serve_once(kernel):
        srv = GossipServer(batch_size=16, policy="uniform", seed=5,
                           use_kernel=kernel, interpret=kernel)
        qX = np.asarray(_sim_setup()[3][:40], np.float32)

        def hook(cycle, snap):
            srv.serve_hook(cycle, snap)
            srv.submit(qX)

        _run("sharded", serve_hook=hook)
        srv.flush()
        return srv.answers(), srv.answers_fresh()

    a1, f1 = serve_once(use_kernel)
    a2, f2 = serve_once(use_kernel)
    npt.assert_array_equal(a1, a2)
    npt.assert_array_equal(f1, f2)
    if use_kernel:
        aj, fj = serve_once(False)
        npt.assert_array_equal(a1, aj)
        npt.assert_array_equal(f1, fj)


def test_gossip_server_batching_and_order():
    """Batch accumulation: submits below batch_size stay pending, crossing
    it serves exactly batch_size, flush pads + serves the tail, and
    answers() returns submission order regardless of batch boundaries."""
    cache = _filled_cache(6, 3, 5, 4, seed=2)
    snap = serving._snapshot(cache, jnp.int32(7))
    srv = GossipServer(batch_size=8, policy="round_robin")
    srv.serve_hook(3, snap)

    key = jax.random.key(11)
    X = np.asarray(jax.random.normal(key, (13, 5)), np.float32)
    srv.submit(X[:5])
    assert not srv.batches                       # 5 < 8: still pending
    srv.submit(X[5:11])
    assert [b.size for b in srv.batches] == [8]  # crossed once, served 8
    srv.submit(X[11:])
    srv.flush()
    assert [b.size for b in srv.batches] == [8, 5]
    assert all(b.cycle == 3 for b in srv.batches)
    # answers in submission order == serving the whole set in one shot
    assign = serving.assign_queries(16, 6, policy="round_robin")
    exp = serving.serve_voted(cache.w, cache.count,
                              jnp.asarray(np.concatenate(
                                  [X, np.zeros((3, 5), np.float32)])),
                              jnp.asarray(assign))
    npt.assert_array_equal(srv.answers(), np.asarray(exp)[:13])
    with pytest.raises(RuntimeError):
        GossipServer(batch_size=2).submit(X[:2])  # no snapshot adopted


def test_assign_queries_policies():
    rr = serving.assign_queries(7, 3, policy="round_robin", offset=2)
    npt.assert_array_equal(rr, [2, 0, 1, 2, 0, 1, 2])
    u1 = serving.assign_queries(64, 9, policy="uniform", seed=4, offset=0)
    u2 = serving.assign_queries(64, 9, policy="uniform", seed=4, offset=0)
    npt.assert_array_equal(u1, u2)               # deterministic per (seed, offset)
    u3 = serving.assign_queries(64, 9, policy="uniform", seed=4, offset=64)
    assert not np.array_equal(u1, u3)            # offset advances the stream
    assert u1.min() >= 0 and u1.max() < 9
    with pytest.raises(ValueError):
        serving.assign_queries(4, 3, policy="nope")
