"""Heterogeneity suite: Dirichlet non-IID partitions, HeteroFL width-scaled
clients, and the cross-width coverage-count aggregation (fused server step
vs the per-leaf reference oracle)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.configs.vgg import VGG5
from repro.data.loader import dirichlet_indices, dirichlet_partition
from repro.data.synthetic import make_cifar_like, split_clients
from repro.fl.flatbuf import get_server_step, reference_server_step
from repro.fl.hetero import HeteroSpec
from repro.fl.loop import FLConfig, run_federated
from repro.models.split_program import get_split_program


# =============================================================================
# Dirichlet non-IID partitions
# =============================================================================
def test_dirichlet_exact_cover_and_determinism():
    labels = np.random.RandomState(0).randint(0, 10, 400)
    for alpha in (0.05, 0.5, 10.0):
        parts = dirichlet_indices(labels, 6, alpha, seed=3)
        np.testing.assert_array_equal(
            np.sort(np.concatenate(parts)), np.arange(400))
        assert min(len(p) for p in parts) >= 1
        again = dirichlet_indices(labels, 6, alpha, seed=3)
        for a, b in zip(parts, again):
            np.testing.assert_array_equal(a, b)
        other = dirichlet_indices(labels, 6, alpha, seed=4)
        assert any(not np.array_equal(a, b)
                   for a, b in zip(parts, other))


def test_dirichlet_skew_grows_as_alpha_shrinks():
    """Small alpha concentrates labels: per-client label entropy is lower
    than at large alpha (the defining property of the protocol)."""
    labels = np.random.RandomState(1).randint(0, 10, 2000)

    def mean_entropy(alpha):
        parts = dirichlet_indices(labels, 8, alpha, seed=0)
        ents = []
        for idx in parts:
            p = np.bincount(labels[idx], minlength=10) / len(idx)
            p = p[p > 0]
            ents.append(-(p * np.log(p)).sum())
        return np.mean(ents)

    assert mean_entropy(0.05) < mean_entropy(100.0) - 0.5


def test_dirichlet_partition_carries_every_key():
    data = make_cifar_like(200, seed=0)
    clients = dirichlet_partition(data, 5, alpha=0.3, seed=7)
    assert len(clients) == 5
    assert sum(len(c["labels"]) for c in clients) == 200
    for c in clients:
        assert set(c) == set(data)
        assert len(c["images"]) == len(c["labels"])
    # shard contents come from the source rows
    flat = np.sort(np.concatenate([c["labels"] for c in clients]))
    np.testing.assert_array_equal(flat, np.sort(data["labels"]))


def test_dirichlet_rejects_bad_args():
    labels = np.zeros(10, np.int64)
    with pytest.raises(ValueError):
        dirichlet_indices(labels, 0, 0.5)
    with pytest.raises(ValueError):
        dirichlet_indices(labels, 2, 0.0)
    with pytest.raises(ValueError):
        dirichlet_indices(labels, 20, 0.5)   # fewer samples than clients


# =============================================================================
# width masks
# =============================================================================
def test_vgg_width_mask_channel_structure():
    prog = get_split_program(VGG5)
    params = prog.init(jax.random.PRNGKey(0))
    mask = prog.width_mask(params, 0.5)
    # conv layers keep ceil(0.5 * C) output channels
    for spec, m in zip(VGG5.layers, mask):
        if spec.startswith("C"):
            cout = m["w"].shape[-1]
            keep = -(-cout // 2)
            assert float(m["bn_scale"].sum()) == keep
            # kept output channels are a prefix
            np.testing.assert_array_equal(
                np.asarray(m["b"]), (np.arange(cout) < keep).astype(np.float32))
    # the logits layer keeps every class column
    last = mask[-1]
    assert float(np.asarray(last["b"]).min()) == 1.0
    # width=1.0 is the all-ones mask
    full = prog.width_mask(params, 1.0)
    assert all(float(l.min()) == 1.0
               for l in jax.tree_util.tree_leaves(full))
    with pytest.raises(ValueError):
        prog.width_mask(params, 0.0)


def test_width_masks_are_nested():
    """HeteroFL nesting: a narrower mask is a subset of a wider one, for
    every family (cross-width averaging needs prefix slices)."""
    for cfg in [VGG5, get_smoke_config("llama3-8b"),
                get_smoke_config("mamba2-780m")]:
        prog = get_split_program(cfg)
        params = prog.init(jax.random.PRNGKey(0))
        lo = jax.tree_util.tree_leaves(prog.width_mask(params, 0.25))
        hi = jax.tree_util.tree_leaves(prog.width_mask(params, 0.75))
        for a, b in zip(lo, hi):
            assert float((a * (1 - b)).sum()) == 0.0   # lo subset of hi


# =============================================================================
# cross-width aggregation: fused == reference oracle
# =============================================================================
@pytest.mark.parametrize("density,quantize", [(1.0, False), (0.25, False),
                                              (0.25, True), (1.0, True)])
def test_masked_server_step_matches_reference(density, quantize):
    prog = get_split_program(VGG5)
    params = prog.init(jax.random.PRNGKey(0))
    layout = prog.flat_layout(params)
    spec = HeteroSpec(prog, params, [0.25, 0.5, 1.0, 1.0])
    g = layout.flatten(params)
    K = 4
    rng = np.random.RandomState(0)
    masks = spec.rows(range(K))
    deltas = jnp.asarray(rng.randn(K, layout.padded).astype(np.float32)
                         * 0.01) * masks
    w = [120.0, 80.0, 200.0, 100.0]
    err = (jnp.zeros((K, layout.padded), jnp.float32)
           if density < 1 else None)
    step = get_server_step(layout, density, quantize)
    # the step donates its error rows: hand it a copy
    g2, e2 = step(g, deltas, w, None if err is None else jnp.copy(err),
                  masks=masks)
    p_ref, e_ref = reference_server_step(
        layout, params, [layout.unflatten(deltas[i]) for i in range(K)],
        w, err, density=density, quantize=quantize, masks=masks)
    g_ref = layout.flatten(p_ref)
    scale = float(jnp.abs(g_ref).max())
    assert float(jnp.abs(g2 - g_ref).max()) <= 1e-5 * max(1.0, scale)
    if density < 1:
        assert float(jnp.abs(e2 - e_ref).max()) <= 1e-5
    # coordinates no client covers keep the global bitwise
    den = np.asarray(jnp.asarray(w, jnp.float32) @ masks)
    uncovered = den == 0
    assert uncovered.any()
    np.testing.assert_array_equal(np.asarray(g2)[uncovered],
                                  np.asarray(g)[uncovered])


def test_hetero_spec_validates():
    prog = get_split_program(VGG5)
    params = prog.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        HeteroSpec(prog, params, [0.5, 1.5])
    spec = HeteroSpec(prog, params, [0.5, 0.5, 1.0])
    assert len(spec) == 3
    np.testing.assert_allclose(spec.compute_scale, [0.25, 0.25, 1.0])
    # mask rows are the flattened mask trees (0/1 exact)
    row = spec.mask_row(0)
    assert set(np.unique(np.asarray(row))) <= {0.0, 1.0}


# =============================================================================
# e2e: width-scaled federated training
# =============================================================================
def _mini(seed=0):
    clients = split_clients(make_cifar_like(120, seed=seed), 4)
    test = make_cifar_like(40, seed=9)
    return clients, test


def _fl(**kw):
    base = dict(rounds=3, local_iters=2, batch_size=10, mode="sfl",
                static_op=2, augment=False, seed=0,
                client_widths=(0.25, 0.5, 1.0, 1.0))
    base.update(kw)
    return FLConfig(**base)


def test_hetero_run_learns_and_fused_matches_reference():
    clients, test = _mini()
    h_fused = run_federated(VGG5, clients, test, _fl())
    h_ref = run_federated(VGG5, clients, test, _fl(server_step="reference"))
    assert h_fused["accuracy"][-1] > 0.15       # better than chance-ish
    np.testing.assert_allclose(h_fused["accuracy"], h_ref["accuracy"],
                               atol=5e-3)
    for a, b in zip(jax.tree_util.tree_leaves(h_fused["params"]),
                    jax.tree_util.tree_leaves(h_ref["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_hetero_engines_agree():
    clients, test = _mini()
    h_seq = run_federated(VGG5, clients, test, _fl())
    h_bat = run_federated(VGG5, clients, test, _fl(engine="batched"))
    np.testing.assert_allclose(h_seq["accuracy"], h_bat["accuracy"],
                               atol=5e-3)


def test_hetero_full_width_matches_homogeneous():
    """All-1.0 widths go through the mask path but must reproduce the
    homogeneous run (coverage division is by the total weight ~ 1.0)."""
    clients, test = _mini()
    h_w = run_federated(VGG5, clients, test,
                        _fl(client_widths=(1.0,) * 4))
    h_plain = run_federated(VGG5, clients, test, _fl(client_widths=None))
    np.testing.assert_allclose(h_w["accuracy"], h_plain["accuracy"],
                               atol=5e-3)


def test_hetero_uncovered_coordinates_never_move():
    """With every client narrower than 1.0, the coordinates outside the
    widest mask must stay bitwise at their initial values."""
    clients, test = _mini()
    fl = _fl(client_widths=(0.25, 0.25, 0.5, 0.5))
    prog = get_split_program(VGG5)
    p0 = prog.init(jax.random.PRNGKey(fl.seed))
    layout = prog.flat_layout(p0)
    spec = HeteroSpec(prog, p0, fl.client_widths)
    h = run_federated(VGG5, clients, test, fl)
    covered = np.asarray(spec.rows(range(4)).sum(axis=0)) > 0
    flat0 = np.asarray(layout.flatten(p0))
    flat1 = np.asarray(layout.flatten(h["params"]))
    assert (~covered).any()
    np.testing.assert_array_equal(flat1[~covered], flat0[~covered])
    assert np.any(flat1[covered] != flat0[covered])    # training moved


def test_hetero_same_seed_is_bitwise_deterministic():
    clients, test = _mini()
    h1 = run_federated(VGG5, clients, test, _fl())
    h2 = run_federated(VGG5, clients, test, _fl())
    np.testing.assert_array_equal(h1["accuracy"], h2["accuracy"])
    for a, b in zip(jax.tree_util.tree_leaves(h1["params"]),
                    jax.tree_util.tree_leaves(h2["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hetero_widths_scale_round_times():
    """A width-w client's modeled compute shrinks by w**2 through the
    RoundClock (visible in the per-device times with a cost model)."""
    from repro.core.env import SimulatedCluster
    from repro.core import costmodel as cm
    clients, test = _mini()
    wl = cm.vgg_workload(VGG5, batch_size=10)
    devs = [cm.DeviceProfile(f"d{i}", 1e9, 75e6) for i in range(4)]
    sim = SimulatedCluster(wl, devs, 8e9, VGG5.ops, iterations=2,
                           jitter=0.0)
    fl = _fl(client_widths=(0.5, 1.0, 1.0, 1.0), rounds=2)
    h = run_federated(VGG5, clients, test, fl, sim=sim)
    times = np.asarray(h["times"][-1])
    # same device profile, same OP: the width-0.5 client is ~4x cheaper on
    # the compute term (total time also has the Eq.1 network term)
    assert times[0] < times[1]


def test_hetero_async_runs_and_learns():
    from repro.fl.async_loop import run_federated_async
    from repro.runtime.chaos import check_invariants
    clients, test = _mini()
    fl = _fl(buffer_size=2, rounds=4)
    h = run_federated_async(VGG5, clients, test, fl)
    assert len(h["accuracy"]) == 4
    assert check_invariants(h, 4) == []
    assert h["accuracy"][-1] > 0.1


def test_client_widths_length_mismatch_raises():
    clients, test = _mini()
    with pytest.raises(ValueError):
        run_federated(VGG5, clients, test, _fl(client_widths=(0.5, 1.0)))
