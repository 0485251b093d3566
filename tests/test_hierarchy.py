"""Two-tier hierarchical aggregation + virtualized cohort state
(fl/cohort.py, fl/hierarchy.py, fl/state.py): seeded cohort determinism,
EFStore round-trips, single-edge/full-cohort bitwise equivalence with the
pre-hierarchy loops, tiered-vs-reference tolerance under compression,
two-hop comm accounting, and checkpoint-resume of sampled runs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vgg import VGG5
from repro.data.synthetic import make_cifar_like, split_clients
from repro.data.loader import ClientLoader, FleetLoader
from repro.fl.cohort import CohortSampler, EFStore
from repro.fl.comm import Transport, indexed_bandwidths
from repro.fl.fedavg import model_bytes
from repro.fl.flatbuf import (
    get_root_step,
    get_server_step,
    layout_of,
    reference_server_step,
)
from repro.fl.hierarchy import assign_edges, hierarchical_apply
from repro.fl.loop import FLConfig, run_federated
from repro.fl.async_loop import run_federated_async


# =============================================================================
# cohort sampling: pure function of (seed, round)
# =============================================================================
def test_cohort_sampler_deterministic_and_bounded():
    a = CohortSampler(100, 16, seed=3)
    b = CohortSampler(100, 16, seed=3)
    for r in range(5):
        m = a.members(r)
        np.testing.assert_array_equal(m, b.members(r))   # stateless replay
        assert len(m) == 16 and len(np.unique(m)) == 16  # no replacement
        assert m.min() >= 0 and m.max() < 100
        assert (np.sort(m) == m).all()
        mask = a.member_mask(r)
        assert mask.sum() == 16
        np.testing.assert_array_equal(np.flatnonzero(mask), m)
    # consecutive rounds draw different cohorts (whp at 16-of-100)
    assert not np.array_equal(a.members(0), a.members(1))
    # a different seed walks a different stream
    assert not np.array_equal(a.members(0),
                              CohortSampler(100, 16, seed=4).members(0))


def test_cohort_sampler_validates_size():
    with pytest.raises(ValueError, match="cohort_size"):
        CohortSampler(10, 0)
    with pytest.raises(ValueError, match="cohort_size"):
        CohortSampler(10, 11)
    CohortSampler(10, 10)          # cohort == fleet is legal (degenerate)


def test_cohort_pick_degenerates_when_cohort_is_fleet():
    s = CohortSampler(8, 8, seed=0)
    cand = np.asarray([5, 1, 7, 3])
    # taking every candidate == sorted(candidates): the legacy async
    # redispatch order, which is what keeps cohort_size=K bitwise
    np.testing.assert_array_equal(s.pick(2, cand, 4), [1, 3, 5, 7])
    with pytest.raises(ValueError, match="pick"):
        s.pick(0, cand, 5)
    sub = s.pick(4, cand, 2)
    assert set(sub) <= {1, 3, 5, 7} and len(sub) == 2
    np.testing.assert_array_equal(sub, s.pick(4, cand, 2))   # keyed replay


def test_assign_edges_partition_properties():
    for count, e in [(7, 3), (4, 4), (10, 1), (3, 8)]:
        parts = assign_edges(count, e)
        assert len(parts) == min(e, count)
        flat = np.concatenate(parts)
        np.testing.assert_array_equal(flat, np.arange(count))  # contiguous
        sizes = [len(p) for p in parts]
        assert max(sizes) - min(sizes) <= 1                    # balanced
    assert assign_edges(0, 3) == []
    with pytest.raises(ValueError, match="num_edges"):
        assign_edges(5, 0)


# =============================================================================
# EFStore: virtualized error-feedback rows
# =============================================================================
def test_efstore_roundtrip_and_prefetch_bitwise():
    st = EFStore(1000, 64)
    rows = np.random.RandomState(0).randn(3, 64).astype(np.float32)
    st.store([7, 500, 999], rows)
    assert st.touched == 3
    assert st.host_bytes == 3 * 64 * 4
    assert st.dense_bytes() == 1000 * 64 * 4      # what dense would cost
    # direct gather: stored rows bitwise, untouched ids are zero
    out = np.asarray(st.fetch([999, 3, 7]))
    np.testing.assert_array_equal(out[0], rows[2])
    np.testing.assert_array_equal(out[1], np.zeros(64, np.float32))
    np.testing.assert_array_equal(out[2], rows[0])
    # prefetch consumed on exact id match
    st.prefetch([7, 500])
    np.testing.assert_array_equal(np.asarray(st.fetch([7, 500])), rows[:2])
    # prefetch consumed when the fetch is a reordered subset (survivors
    # of the prefetched cohort)
    st.prefetch([7, 500, 999])
    np.testing.assert_array_equal(np.asarray(st.fetch([999, 7])),
                                  rows[[2, 0]])
    # uncovered fetch degrades to a synchronous gather, still bitwise
    st.prefetch([7])
    np.testing.assert_array_equal(np.asarray(st.fetch([500, 999])),
                                  rows[1:])


def test_efstore_snapshot_restore_bitwise():
    st = EFStore(50, 8)
    r = np.random.RandomState(1).randn(2, 8).astype(np.float32)
    st.store([30, 4], r)
    ids, rows = st.snapshot()
    np.testing.assert_array_equal(ids, [4, 30])   # sorted by id
    st2 = EFStore(50, 8)
    st2.restore(ids, rows)
    np.testing.assert_array_equal(np.asarray(st2.fetch([30, 4])),
                                  np.asarray(st.fetch([30, 4])))
    # empty snapshot round-trips as (0,), (0, padded)
    ids0, rows0 = EFStore(5, 8).snapshot()
    assert ids0.shape == (0,) and rows0.shape == (0, 8)


def test_efstore_rejects_bad_shape():
    st = EFStore(10, 16)
    with pytest.raises(ValueError, match="shape"):
        st.store([1, 2], np.zeros((2, 8), np.float32))


# =============================================================================
# tiered aggregation vs flat / reference (unit level)
# =============================================================================
def _toy(K=6, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2 * K + 1)
    g = {"a": jax.random.normal(ks[0], (1500,)),
         "b": jax.random.normal(ks[1], (100,)),
         "c": jax.random.normal(ks[2], (4, 8))}
    layout = layout_of(g)
    deltas = [jax.tree_util.tree_map(
        lambda x, kk=k: 0.1 * jax.random.normal(kk, x.shape), g)
        for k in ks[3:3 + K]]
    return layout, g, deltas


def test_single_edge_bitwise_equals_flat_step():
    """num_edges=1 is the degenerate hierarchy: it runs the flat fused
    program itself, so equality is bitwise for every compression mode."""
    layout, g, deltas = _toy()
    w = [3.0, 1.0, 2.0, 1.0, 4.0, 2.0]
    root = get_root_step(layout)
    for density, quantize in [(1.0, False), (1.0, True),
                              (0.05, False), (0.05, True)]:
        step = get_server_step(layout, density, quantize)
        err = (jnp.ones((len(deltas), layout.padded), jnp.float32) * 0.01
               if density < 1.0 else None)
        stacked = jnp.stack([layout.flatten(d) for d in deltas])
        g_flat = layout.flatten(g)
        # the step donates its error rows: hand it a copy, the hierarchy
        # below consumes the originals
        ref_g, ref_err = step(g_flat, stacked, w,
                              None if err is None else jnp.copy(err))
        hg, herr, used = hierarchical_apply(step, root, g_flat, stacked, w,
                                            err, num_edges=1)
        assert used == 1
        np.testing.assert_array_equal(np.asarray(hg), np.asarray(ref_g))
        if err is not None:
            np.testing.assert_array_equal(np.asarray(herr),
                                          np.asarray(ref_err))


@pytest.mark.parametrize("density,quantize", [(1.0, False), (1.0, True),
                                              (0.05, False), (0.05, True)])
@pytest.mark.parametrize("num_edges", [2, 3])
def test_tiered_matches_reference_within_fp32(density, quantize, num_edges):
    """>= 2 edges: per-edge reduce + root combine matches the per-client
    reference oracle up to fp32 summation order (ISSUE acceptance)."""
    layout, g, deltas = _toy()
    w = [3.0, 1.0, 2.0, 1.0, 4.0, 2.0]
    track = density < 1.0
    err = (jnp.stack([layout.flatten(jax.tree_util.tree_map(
        lambda x, i=i: 0.01 * (i + 1) * jnp.ones_like(x), g))
        for i in range(len(deltas))]) if track else None)
    ref_params, ref_err = reference_server_step(
        layout, g, deltas, w, err, density=density, quantize=quantize)
    step = get_server_step(layout, density, quantize)
    root = get_root_step(layout)
    hg, herr, used = hierarchical_apply(
        step, root, layout.flatten(g),
        jnp.stack([layout.flatten(d) for d in deltas]), w, err,
        num_edges=num_edges)
    assert used == num_edges
    for a, b in zip(jax.tree_util.tree_leaves(layout.unflatten(hg)),
                    jax.tree_util.tree_leaves(ref_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    if track:
        # EF rows come back in the caller's survivor order: each client's
        # residual is computed inside exactly one edge from the same
        # compression decisions the reference made
        np.testing.assert_allclose(np.asarray(herr), np.asarray(ref_err),
                                   atol=1e-6)


# =============================================================================
# through the real loops: cohort_size=K + num_edges<=1 is the legacy run
# =============================================================================
def _testbed(K=4):
    clients = split_clients(make_cifar_like(30 * K, seed=0), K)
    test = make_cifar_like(40, seed=9)
    base = dict(rounds=3, local_iters=1, batch_size=20, mode="sfl",
                static_op=2, augment=True, seed=0)
    return clients, test, base


@pytest.mark.parametrize("over", [
    dict(),
    dict(delta_density=0.25, quantize_deltas=True),
])
def test_full_cohort_single_edge_bitwise_sync(over):
    clients, test, base = _testbed()
    legacy = run_federated(VGG5, clients, test, FLConfig(**base, **over))
    tiered = run_federated(VGG5, clients, test,
                           FLConfig(**base, **over, cohort_size=4,
                                    num_edges=1))
    for key in ("accuracy", "ops", "dropped", "round_time"):
        np.testing.assert_array_equal(legacy[key], tiered[key], err_msg=key)
    for a, b in zip(jax.tree_util.tree_leaves(legacy["params"]),
                    jax.tree_util.tree_leaves(tiered["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (tiered["edge_time"] == 0).all()   # no edge_transport: free hop


def test_full_cohort_bitwise_async():
    clients, test, base = _testbed()
    over = dict(delta_density=0.25, staleness_discount=0.5)
    legacy = run_federated_async(VGG5, clients, test,
                                 FLConfig(**base, **over))
    cohort = run_federated_async(VGG5, clients, test,
                                 FLConfig(**base, **over, cohort_size=4,
                                          num_edges=1))
    for key in ("accuracy", "virtual_time", "staleness", "dropped",
                "agg_weight_sum"):
        np.testing.assert_array_equal(legacy[key], cohort[key], err_msg=key)


def test_sampled_cohort_sync_deterministic_and_drops_rest():
    clients, test, base = _testbed()
    cfg = dict(**base, delta_density=0.25, cohort_size=2, num_edges=2)
    h1 = run_federated(VGG5, clients, test, FLConfig(**cfg))
    h2 = run_federated(VGG5, clients, test, FLConfig(**cfg))
    # seeded cohorts: the sampled run replays bitwise
    np.testing.assert_array_equal(h1["accuracy"], h2["accuracy"])
    np.testing.assert_array_equal(h1["ops"], h2["ops"])
    # non-members are accounted as dropped every round
    np.testing.assert_array_equal(h1["dropped"], [2, 2, 2])


def test_cohort_size_one_runs():
    clients, test, base = _testbed()
    h = run_federated(VGG5, clients, test,
                      FLConfig(**base, cohort_size=1))
    np.testing.assert_array_equal(h["dropped"], [3, 3, 3])
    assert len(h["accuracy"]) == 3


def test_hierarchy_requires_fused_server():
    clients, test, base = _testbed()
    with pytest.raises(ValueError, match="fused"):
        run_federated(VGG5, clients, test,
                      FLConfig(**base, server_step="reference", num_edges=2))
    with pytest.raises(ValueError, match="fused"):
        run_federated_async(VGG5, clients, test,
                            FLConfig(**base, server_step="reference",
                                     num_edges=2))


# =============================================================================
# two-hop comm accounting
# =============================================================================
def test_edge_hop_charged_per_edge_hand_computed():
    clients, test, base = _testbed()
    bws = [50e6, 10e6]           # edge 1 is the straggler uplink
    et = Transport(indexed_bandwidths(bws))
    cfg = dict(**base, cohort_size=4, num_edges=2)
    free = run_federated(VGG5, clients, test, FLConfig(**cfg))
    paid = run_federated(VGG5, clients, test, FLConfig(**cfg),
                         edge_transport=et)
    # the hop changes accounting only: training itself is identical
    np.testing.assert_array_equal(free["accuracy"], paid["accuracy"])
    # one pre-reduced fp32 row up + the model broadcast down, per edge;
    # the round waits on the slowest edge
    mb = model_bytes(paid["params"])
    expected = max((mb + mb) * 8.0 / bw for bw in bws)
    np.testing.assert_allclose(paid["edge_time"],
                               [expected] * 3, rtol=1e-9)
    np.testing.assert_allclose(paid["round_time"],
                               free["round_time"] + expected, rtol=1e-9)
    assert (free["edge_time"] == 0).all()


def test_edge_hop_async_reported_not_clocked():
    clients, test, base = _testbed()
    et = Transport(indexed_bandwidths([40e6, 40e6]))
    cfg = dict(**base, cohort_size=4, num_edges=2)
    free = run_federated_async(VGG5, clients, test, FLConfig(**cfg))
    paid = run_federated_async(VGG5, clients, test, FLConfig(**cfg),
                               edge_transport=et)
    # the virtual clock is event-driven: the hop is reported as its own
    # column and does not perturb the event stream
    np.testing.assert_array_equal(free["virtual_time"], paid["virtual_time"])
    mb = model_bytes(paid["params"])
    np.testing.assert_allclose(paid["edge_time"],
                               [(mb + mb) * 8.0 / 40e6] * 3, rtol=1e-9)
    assert (free["edge_time"] == 0).all()


# =============================================================================
# checkpoint/resume of sampled runs
# =============================================================================
def test_cohort_resume_bitwise_sync(tmp_path):
    clients, test, base = _testbed()
    over = dict(delta_density=0.25, quantize_deltas=True, cohort_size=2,
                num_edges=2)

    def cfg(sub, rounds=4):
        return FLConfig(**{**base, "rounds": rounds}, **over,
                        checkpoint_dir=str(tmp_path / sub),
                        checkpoint_every=2)

    full = run_federated(VGG5, clients, test, cfg("full"))
    run_federated(VGG5, clients, test, cfg("resume", rounds=2))
    resumed = run_federated(VGG5, clients, test, cfg("resume"), resume=True)
    # rounds 2..3 of the resumed run replay bitwise: the EFStore snapshot
    # restored the touched rows and the keyed RNG re-derived cohorts 0..1
    # for the loader fast-forward
    np.testing.assert_array_equal(resumed["accuracy"], full["accuracy"][-2:])
    np.testing.assert_array_equal(resumed["dropped"], full["dropped"][-2:])
    for a, b in zip(jax.tree_util.tree_leaves(resumed["params"]),
                    jax.tree_util.tree_leaves(full["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cohort_resume_bitwise_async(tmp_path):
    clients, test, base = _testbed()
    et = Transport(indexed_bandwidths([50e6, 40e6]))
    cfg = FLConfig(**{**base, "rounds": 4}, delta_density=0.25,
                   cohort_size=2, num_edges=2,
                   checkpoint_dir=str(tmp_path), checkpoint_every=2)
    full = run_federated_async(VGG5, clients, test, cfg,
                               edge_transport=et)
    resumed = run_federated_async(VGG5, clients, test, cfg,
                                  edge_transport=et, resume=True)
    # the checkpoint froze C=2 in-flight events + the EFStore; versions
    # 2..3 replay bitwise including the seeded cohort refill draws
    np.testing.assert_array_equal(resumed["accuracy"], full["accuracy"][-2:])
    np.testing.assert_array_equal(resumed["virtual_time"],
                                  full["virtual_time"][-2:])
    np.testing.assert_allclose(resumed["edge_time"], full["edge_time"][-2:],
                               rtol=1e-12)


# =============================================================================
# lazy fleet loader: registration is free, participation materializes
# =============================================================================
def test_fleet_loader_materializes_on_demand():
    data = split_clients(make_cifar_like(120, seed=0), 6)
    fleet = FleetLoader.for_clients(data, batch_size=10, seed=0)
    assert fleet.materialized == 0           # registration costs nothing
    fleet.next_batch(3)
    fleet.next_batch(5)
    assert fleet.materialized == 2
    # state/restore round-trips without touching idle clients
    st = fleet.state()
    assert st[0] == (0, 0) and st[3] != (0, 0)
    fleet.restore(st)
    assert fleet.materialized == 2
    # a materialized client's stream matches a standalone loader bitwise
    solo = ClientLoader(data[3], 10, seed=0 + 3)
    solo.next_batch()                        # fleet already consumed one
    np.testing.assert_array_equal(fleet.next_batch(3)["images"],
                                  solo.next_batch()["images"])
