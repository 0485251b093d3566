"""Fleet execution engines: batched-vs-sequential equivalence (same seed =>
same history), FleetLoader stream determinism + resume, stacked FedAvg."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.lm_small import LM16M
from repro.configs.vgg import VGG5
from repro.data.loader import ClientLoader, FleetLoader
from repro.data.synthetic import make_cifar_like, split_clients, token_dataset
from repro.fl.fedavg import fedavg_delta, fedavg_delta_stacked
from repro.fl.fleet import StackedRows, get_engine, rows_as_list, take_rows
from repro.fl.loop import FLConfig, run_federated
from repro.models.split_program import get_split_program

KEY = jax.random.PRNGKey(0)


def _max_leaf_diff(a, b) -> float:
    return max(float(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))
                     .max())
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# =============================================================================
# FleetLoader: per-client streams identical to the sequential loaders
# =============================================================================
def test_fleet_loader_next_batches_matches_sequential_streams():
    data = make_cifar_like(120, seed=0)
    clients = split_clients(data, 4)
    fleet = FleetLoader.for_clients(clients, 10, seed=7)
    solo = [ClientLoader(d, 10, seed=7 + k) for k, d in enumerate(clients)]
    for _ in range(8):                       # crosses an epoch boundary
        stacked = fleet.next_batches([0, 1, 2, 3])
        refs = [ld.next_batch() for ld in solo]
        for k, ref in enumerate(refs):
            for key in ref:
                np.testing.assert_array_equal(stacked[key][k], ref[key])


def test_fleet_loader_grouping_never_perturbs_a_client_stream():
    """Drawing clients in different groupings (the batched engine re-groups
    by OP every round) must not change any single client's stream."""
    clients = split_clients(make_cifar_like(90, seed=1), 3)
    a = FleetLoader.for_clients(clients, 10, seed=0)
    b = FleetLoader.for_clients(clients, 10, seed=0)
    got_a = [a.next_batches([0, 1, 2]) for _ in range(4)]
    got_b = []
    for _ in range(4):                       # same draws, different grouping
        g02 = b.next_batches([0, 2])
        g1 = b.next_batches([1])
        got_b.append({k: np.stack([g02[k][0], g1[k][0], g02[k][1]])
                      for k in g02})
    for x, y in zip(got_a, got_b):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])


def test_fleet_loader_skip_is_bitwise_resume():
    clients = split_clients(make_cifar_like(60, seed=2), 2)
    a = FleetLoader.for_clients(clients, 7, seed=3)
    b = FleetLoader.for_clients(clients, 7, seed=3)
    for _ in range(11):
        a.next_batches([0, 1])
    b.skip(11)
    assert a.state() == b.state()
    na, nb = a.next_batches([0, 1]), b.next_batches([0, 1])
    for k in na:
        np.testing.assert_array_equal(na[k], nb[k])


def test_fleet_loader_state_restore_roundtrip():
    clients = split_clients(make_cifar_like(60, seed=2), 2)
    fleet = FleetLoader.for_clients(clients, 7, seed=3)
    fleet.next_batches([0, 1])
    st = fleet.state()
    want = fleet.next_batches([0, 1])
    fleet.restore(st)
    got = fleet.next_batches([0, 1])
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


def test_fleet_loader_restore_rejects_wrong_fleet_size():
    clients = split_clients(make_cifar_like(60, seed=2), 2)
    fleet = FleetLoader.for_clients(clients, 7, seed=3)
    with pytest.raises(ValueError, match="refusing a partial restore"):
        fleet.restore(fleet.state()[:1])


def test_fleet_loader_rejects_ragged_batch_sizes():
    clients = [make_cifar_like(40, seed=0), make_cifar_like(5, seed=1)]
    with pytest.raises(ValueError, match="uniform batch size"):
        FleetLoader.for_clients(clients, 10, seed=0)


# =============================================================================
# stacked FedAvg + batched init + row adapters
# =============================================================================
def test_fedavg_delta_stacked_matches_list_fedavg():
    prog = get_split_program(VGG5)
    g = prog.init(KEY)
    stacked = prog.init_batched(jax.random.PRNGKey(1), 3)
    clients = rows_as_list(StackedRows(stacked), [0, 1, 2])
    w = [3.0, 1.0, 2.0]
    assert _max_leaf_diff(fedavg_delta_stacked(g, stacked, w),
                          fedavg_delta(g, clients, w)) < 1e-6


def test_init_batched_rows_are_independent_inits():
    prog = get_split_program(LM16M)
    stacked = prog.init_batched(KEY, 2)
    keys = jax.random.split(KEY, 2)
    for i in range(2):
        row = jax.tree_util.tree_map(lambda a: a[i], stacked)
        assert _max_leaf_diff(row, prog.init(keys[i])) == 0.0
    assert _max_leaf_diff(
        jax.tree_util.tree_map(lambda a: a[0], stacked),
        jax.tree_util.tree_map(lambda a: a[1], stacked)) > 0.0


def test_take_rows_preserves_representation():
    tree = {"w": jnp.arange(12.0).reshape(4, 3)}
    rows = StackedRows(tree)
    sub = take_rows(rows, [2, 0])
    assert isinstance(sub, StackedRows) and len(sub) == 2
    np.testing.assert_array_equal(np.asarray(sub.tree["w"][0]),
                                  np.asarray(tree["w"][2]))
    lst = [{"w": jnp.ones(3) * i} for i in range(3)]
    assert take_rows(lst, [1]) == [lst[1]]
    assert get_engine.__name__  # keep import used
    with pytest.raises(ValueError, match="unknown fleet engine"):
        get_engine("warp", get_split_program(VGG5), 1, 0, False, False)


# =============================================================================
# engine equivalence: same seed => same history, sequential vs batched
# =============================================================================
def _histories(cfg, clients, test, **kw):
    out = []
    for engine in ("sequential", "batched"):
        fl = FLConfig(engine=engine, **kw)
        out.append(run_federated(cfg, clients, test, fl))
    return out


def test_batched_equals_sequential_vgg():
    """The paper's model, augmentation on, two OP groups via mixed planner
    input is covered by the static-OP path here; per-round history must
    match the sequential engine within float32 tolerance."""
    clients = split_clients(make_cifar_like(240, seed=0), 4)
    test = make_cifar_like(60, seed=9)
    seq, bat = _histories(VGG5, clients, test, rounds=3, local_iters=2,
                          batch_size=15, mode="sfl", static_op=2,
                          augment=True)
    np.testing.assert_array_equal(seq["ops"], bat["ops"])
    np.testing.assert_allclose(seq["accuracy"], bat["accuracy"], atol=0.02)
    assert _max_leaf_diff(seq["params"], bat["params"]) < 1e-4


def test_batched_equals_sequential_lm_small():
    clients = split_clients(token_dataset(64, 32, LM16M.vocab_size, seed=0),
                            4)
    test = token_dataset(8, 32, LM16M.vocab_size, seed=9)
    seq, bat = _histories(LM16M, clients, test, rounds=3, local_iters=2,
                          batch_size=4, lr=0.3, augment=False, mode="sfl",
                          static_op=3)
    np.testing.assert_array_equal(seq["ops"], bat["ops"])
    np.testing.assert_allclose(seq["accuracy"], bat["accuracy"], atol=5e-3)
    assert (seq["dropped"] == bat["dropped"]).all()


def test_batched_engine_with_failures_and_stragglers():
    """Dead clients draw no batches; straggler-dropped clients train but are
    excluded from FedAvg — identical aliveness bookkeeping in both engines
    (fail/drop masks are seeded, so the two runs see the same masks)."""
    clients = split_clients(make_cifar_like(160, seed=0), 4)
    test = make_cifar_like(40, seed=9)
    seq, bat = _histories(VGG5, clients, test, rounds=4, local_iters=2,
                          batch_size=10, mode="sfl", static_op=2,
                          augment=False, fail_prob=0.3, deadline_factor=1.5)
    np.testing.assert_array_equal(seq["dropped"], bat["dropped"])
    np.testing.assert_allclose(seq["accuracy"], bat["accuracy"], atol=0.03)
    assert _max_leaf_diff(seq["params"], bat["params"]) < 1e-4


def test_batched_engine_group_chunking_matches_unchunked():
    """max_group splits a big OP group into several dispatches; the trained
    rows must be identical (per-client math is independent)."""
    from repro.fl.fleet import BatchedEngine, SequentialEngine

    prog = get_split_program(VGG5)
    params = prog.init(KEY)
    clients = split_clients(make_cifar_like(120, seed=0), 6)

    def rows_for(engine):
        loader = FleetLoader.for_clients(clients, 10, seed=0)
        idxs, rows = engine.run_round(params, loader, [2] * 6,
                                      list(range(6)), 0, 0.05)
        assert idxs == list(range(6))
        return rows

    chunked = rows_for(BatchedEngine(prog, 2, 0, True, False, max_group=2))
    # max_group=4 on 6 clients: one full chunk + a tail padded back up to 4
    # (repeated data rows, trained outputs discarded) so compiled shapes
    # never depend on K % max_group
    padded = rows_for(BatchedEngine(prog, 2, 0, True, False, max_group=4))
    whole = rows_for(BatchedEngine(prog, 2, 0, True, False, max_group=64))
    seq = rows_for(SequentialEngine(prog, 2, 0, True, False))
    assert len(chunked) == len(padded) == len(whole) == 6
    assert _max_leaf_diff(padded.tree, whole.tree) < 1e-6
    assert _max_leaf_diff(chunked.tree, whole.tree) < 1e-6
    for i in range(6):
        assert _max_leaf_diff(
            jax.tree_util.tree_map(lambda a: a[i], chunked.tree),
            seq[i]) < 1e-5


def test_batched_engine_multiple_op_groups():
    """A planner that assigns different OPs per client exercises the
    group-by-OP path (one compiled step per OP, concatenated rows)."""
    from repro.fl.planner import Planner

    class AlternatingPlanner(Planner):
        def plan(self, round_idx, last_times, bandwidths):
            return [2 if k % 2 == 0 else 4
                    for k in range(len(last_times))]

    clients = split_clients(make_cifar_like(160, seed=0), 4)
    test = make_cifar_like(40, seed=9)
    out = []
    for engine in ("sequential", "batched"):
        fl = FLConfig(rounds=2, local_iters=2, batch_size=10, augment=False,
                      engine=engine)
        out.append(run_federated(VGG5, clients, test, fl,
                                 planner=AlternatingPlanner()))
    seq, bat = out
    np.testing.assert_array_equal(seq["ops"], bat["ops"])
    assert set(np.asarray(seq["ops"][0])) == {2, 4}
    assert _max_leaf_diff(seq["params"], bat["params"]) < 1e-4


# =============================================================================
# device-resident fleet data: the gather path builds the host path's bytes
# =============================================================================
def _vgg_fleet():
    return split_clients(make_cifar_like(100, seed=3, hw=8), 5)


def _lm_fleet():
    return split_clients(token_dataset(40, 16, LM16M.vocab_size, seed=4), 5)


def _dirichlet_fleet():
    from repro.data.loader import dirichlet_partition
    return dirichlet_partition(make_cifar_like(150, seed=5, hw=8), 5,
                               alpha=0.5, seed=1, min_per_client=8)


# (fleet, batch, augment, chunks as (clients, pad_to)): VGG with flips, a
# padded chunk and two OP groups; LM tokens; Dirichlet shards of unequal size
RESIDENT_CASES = {
    "vgg-flips-pad-two-groups": (_vgg_fleet, 6, True,
                                 [([0, 2, 4], 4), ([1, 3], None)]),
    "lm-tokens": (_lm_fleet, 3, False, [([0, 1, 2, 3, 4], None)]),
    "dirichlet-sizes": (_dirichlet_fleet, 4, True,
                        [([3, 0], 3), ([4, 1, 2], None)]),
}


def _both_paths(case, rounds=3, iters=2):
    """Each round's chunk batches and the streams' states after it, from a
    resident engine and from a host-path engine on twin loaders."""
    from repro.fl.fleet import BatchedEngine
    fleet, batch, augment, chunks = RESIDENT_CASES[case]
    clients = fleet()
    prog = get_split_program(LM16M if "tokens" in clients[0] else VGG5)
    out = []
    for resident in (True, False):
        eng = BatchedEngine(prog, iters, 11, augment, False,
                            resident=resident)
        loader = FleetLoader.for_clients(clients, batch, seed=2)
        got = []
        for r in range(rounds):            # crosses epoch boundaries
            for ks, pad_to in chunks:
                b = eng._stack_round(loader, ks, r, pad_to=pad_to)
                got.append(({k: np.asarray(v) for k, v in b.items()},
                            loader.state()))
        assert (eng._slab is not None) == resident
        out.append(got)
    return out


@pytest.mark.parametrize("case", sorted(RESIDENT_CASES))
def test_resident_gather_is_bitwise_the_host_stack(case):
    resident, host = _both_paths(case)
    for (a, _), (b, _) in zip(resident, host):
        assert set(a) == set(b)
        for key in a:
            assert a[key].dtype == b[key].dtype
            np.testing.assert_array_equal(a[key], b[key])


@pytest.mark.parametrize("case", sorted(RESIDENT_CASES))
def test_resident_gather_advances_streams_as_the_host_path(case):
    resident, host = _both_paths(case)
    assert [s for _, s in resident] == [s for _, s in host]


@pytest.mark.parametrize("cohort_size", [0, 3])
@pytest.mark.parametrize("loop", ["sync", "async"])
def test_only_a_full_fleet_builds_a_device_slab(monkeypatch, loop,
                                                cohort_size):
    """Every client every round: the fleet's data goes to the device once
    and each chunk gathers there; a sampled cohort stacks on the host."""
    from repro.configs.vgg import VGGConfig
    from repro.fl import fleet as fleet_mod
    from repro.fl.async_loop import run_federated_async

    calls = {"build": 0, "gather": 0}
    build, gather = fleet_mod.FleetSlab.build, fleet_mod._fleet_gather

    def counted_build(*a, **kw):
        calls["build"] += 1
        return build(*a, **kw)

    def counted_gather(*a, **kw):
        calls["gather"] += 1
        return gather(*a, **kw)

    monkeypatch.setattr(fleet_mod.FleetSlab, "build", counted_build)
    monkeypatch.setattr(fleet_mod, "_fleet_gather", counted_gather)
    tiny = VGGConfig(name="vgg-tiny", layers=("C4", "MP", "FC10"), ops=(2,),
                     input_hw=8)
    clients = split_clients(make_cifar_like(60, seed=0, hw=8), 6)
    test = make_cifar_like(10, seed=9, hw=8)
    fl = FLConfig(rounds=2, local_iters=1, batch_size=5, engine="batched",
                  cohort_size=cohort_size)
    run = run_federated if loop == "sync" else run_federated_async
    run(tiny, clients, test, fl)
    if cohort_size:
        assert calls == {"build": 0, "gather": 0}
    else:
        assert calls["build"] == 1 and calls["gather"] > 0


@pytest.mark.parametrize("seed,round_idx,client,it",
                         [(0, 0, 0, 0), (7, 3, 5, 1), (2 ** 40 + 3, 9, 63, 4)])
def test_flip_mask_is_flip_augments_mask(seed, round_idx, client, it):
    from repro.fl.fleet import flip_augment, flip_mask
    images = np.arange(6 * 2 * 3 * 1, dtype=np.float32).reshape(6, 2, 3, 1)
    # the augmentation stream's seeding, written out as the reference
    want = np.random.RandomState(
        (seed * 1_000_003 + round_idx * 1009 + client * 31 + it)
        % (2 ** 31)).rand(len(images)) < 0.5
    mask = flip_mask(seed, round_idx, client, it, len(images))
    np.testing.assert_array_equal(mask, want)
    got = flip_augment(images, seed, round_idx, client, it)
    flipped = [np.array_equal(g, x[:, ::-1]) for g, x in zip(got, images)]
    kept = [np.array_equal(g, x) for g, x in zip(got, images)]
    assert flipped == list(mask) and kept == list(~mask)
