"""Sharded flat-buffer ServerStep (fl/flatbuf.ShardedFlatLayout /
ShardedServerStep over parallel.sharding.make_flat_mesh).

Contracts under test (ISSUE 9):

* ``mesh_shape=None`` is the exact legacy single-device path — plain
  FlatLayout / ServerStep classes, and a ``mesh_shape=(1, 1)`` run is
  bitwise identical to a ``None`` run.
* sharded step == single-device fused step: bitwise for plain averaging
  and the top-k path (g, error-feedback rows and the reduce-only edge
  mode) at data=1 mesh widths; fp32 tolerance for int8-quantized paths
  (XLA retunes the quantize tile for the per-shard row count — the scale
  can move by 1 ulp) and for ``data > 1`` (psum reassociates the weighted
  accumulation).
* divisibility fallback: where ``AxisRules.resolve`` would *replicate* a
  non-dividing leaf, ``ShardedFlatLayout`` pads the final model-axis shard
  in whole blocks and masks the tail out of the compression metadata —
  per-shard byte accounting proves every shard owns distinct elements.

Multi-device cases run in subprocesses with
``--xla_force_host_platform_device_count=8`` (tests themselves must see
one CPU device, per the conftest isolation rule); the CI lane
``test-multidevice`` sets the same flag process-wide.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vgg import VGG5
from repro.data.synthetic import make_cifar_like, split_clients
from repro.fl.flatbuf import (
    FlatLayout,
    ServerStep,
    ShardedFlatLayout,
    ShardedServerStep,
    get_server_step,
    layout_of,
)
from repro.fl.loop import FLConfig, run_federated
from repro.models.split_program import get_split_program
from repro.parallel.sharding import flat_shard_tail, make_flat_mesh

KEY = jax.random.PRNGKey(0)


def _run_subprocess(script: str, timeout: int = 600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, (out.stdout[-2000:] + out.stderr[-4000:])
    return out.stdout


# =============================================================================
# mesh / tail helpers
# =============================================================================
def test_make_flat_mesh_validation():
    with pytest.raises(ValueError, match="two positive ints"):
        make_flat_mesh((2,))
    with pytest.raises(ValueError, match="two positive ints"):
        make_flat_mesh((0, 4))
    # more devices than the host exposes: the error names the XLA flag fix
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_flat_mesh((64, 64))
    mesh = make_flat_mesh((1, 1))
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_flat_shard_tail_values():
    assert flat_shard_tail(593920, 1024, 8) == 4096     # vgg5: 580 % 8 == 4
    assert flat_shard_tail(593920, 1024, 1) == 0
    assert flat_shard_tail(593920, 1024, 4) == 0        # 580 % 4 == 0
    with pytest.raises(ValueError, match="block-aligned"):
        flat_shard_tail(1000, 1024, 2)


# =============================================================================
# mesh=None stays the exact legacy classes (the bitwise pre-PR path)
# =============================================================================
def test_layout_dispatch_and_cache():
    prog = get_split_program(VGG5)
    params = prog.init(KEY)
    plain = prog.flat_layout(params)
    assert type(plain) is FlatLayout
    assert type(get_server_step(plain, 1.0, False)) is ServerStep
    mesh = make_flat_mesh((1, 1))
    sharded = prog.flat_layout(params, mesh=mesh)
    assert type(sharded) is ShardedFlatLayout
    assert type(get_server_step(sharded, 1.0, False)) is ShardedServerStep
    # distinct cache keys, stable on re-resolve
    assert sharded is not plain
    assert prog.flat_layout(params) is plain
    assert prog.flat_layout(params, mesh=mesh) is sharded


def test_flconfig_mesh_default_is_none():
    assert FLConfig().mesh_shape is None


def test_mesh_requires_fused_server_step():
    clients = split_clients(make_cifar_like(60, seed=0), 3)
    test = make_cifar_like(20, seed=9)
    cfg = FLConfig(rounds=1, local_iters=1, batch_size=20, mode="sfl",
                   static_op=2, seed=0, server_step="reference",
                   mesh_shape=(1, 1))
    with pytest.raises(ValueError, match="fused"):
        run_federated(VGG5, clients, test, cfg)


# =============================================================================
# (1, 1) mesh in-process: sharded == legacy, bitwise
# =============================================================================
def _battery_inputs(layout, K=4):
    g = layout.flatten(get_split_program(VGG5).init(KEY))
    keys = jax.random.split(jax.random.PRNGKey(1), K)
    deltas = jnp.stack([0.01 * jax.random.normal(k, (layout.padded,),
                                                 jnp.float32) for k in keys])
    weights = list(np.arange(1, K + 1, dtype=np.float64))
    err = jnp.zeros((K, layout.padded), jnp.float32)
    return g, deltas, weights, err


def test_sharded_step_1x1_bitwise_vs_legacy():
    prog = get_split_program(VGG5)
    params = prog.init(KEY)
    base = prog.flat_layout(params)
    lay = prog.flat_layout(params, mesh=make_flat_mesh((1, 1)))
    assert lay.tail == 0 and lay.padded == base.padded
    g, deltas, weights, err = _battery_inputs(base)
    np.testing.assert_array_equal(np.asarray(lay.flatten(params)),
                                  np.asarray(g))
    for density, quant in ((1.0, False), (0.05, False), (0.05, True)):
        ref = get_server_step(base, density, quant)
        step = get_server_step(lay, density, quant)
        e = err if density < 1 else None
        # both steps donate their error rows: hand them copies
        cp = lambda a: None if a is None else jnp.copy(a)  # noqa: E731
        e_ref, e_mesh = cp(e), cp(e)
        rg, re = ref(g, deltas, weights, e_ref)
        sg, se = step(lay.flatten(params), deltas, weights, e_mesh)
        if e is not None:       # one donation contract, mesh or not
            assert e_ref.is_deleted() and e_mesh.is_deleted()
        np.testing.assert_array_equal(np.asarray(sg), np.asarray(rg))
        if re is not None:
            np.testing.assert_array_equal(np.asarray(se), np.asarray(re))
        ra = ref.reduce(deltas, weights, cp(e))[0]
        sa = step.reduce(deltas, weights, cp(e))[0]
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(ra))


def test_run_federated_mesh_1x1_bitwise_vs_none():
    """mesh_shape=(1,1) through the whole sync loop reproduces the
    mesh_shape=None run bitwise — params and history."""
    clients = split_clients(make_cifar_like(90, seed=0), 3)
    test = make_cifar_like(30, seed=9)

    def cfg(mesh_shape):
        return FLConfig(rounds=2, local_iters=1, batch_size=20, mode="sfl",
                        static_op=2, seed=0, delta_density=0.5,
                        mesh_shape=mesh_shape)

    h_none = run_federated(VGG5, clients, test, cfg(None))
    h_mesh = run_federated(VGG5, clients, test, cfg((1, 1)))
    np.testing.assert_array_equal(h_none["accuracy"], h_mesh["accuracy"])
    for a, b in zip(jax.tree_util.tree_leaves(h_none["params"]),
                    jax.tree_util.tree_leaves(h_mesh["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# =============================================================================
# multi-device battery (subprocess, 8 virtual CPU devices)
# =============================================================================
BATTERY = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.vgg import VGG5
    from repro.models.split_program import get_split_program
    from repro.fl.flatbuf import get_server_step, ShardedFlatLayout
    from repro.parallel.sharding import make_flat_mesh

    prog = get_split_program(VGG5)
    params = prog.init(jax.random.PRNGKey(0))
    base = prog.flat_layout(params)
    K = 5                                     # odd: pads to 6 rows at data=2
    keys = jax.random.split(jax.random.PRNGKey(1), K)
    g0 = base.flatten(params)
    deltas0 = jnp.stack([0.01 * jax.random.normal(k, (base.padded,),
                                                  jnp.float32) for k in keys])
    weights = list(np.arange(1, K + 1, dtype=np.float64))
    err0 = jnp.zeros((K, base.padded), jnp.float32)

    for density, quant in ((1.0, False), (0.05, False), (0.05, True)):
        ref = get_server_step(base, density, quant)
        e0 = err0 if density < 1 else None
        # every step donates its error rows: hand them copies
        cp = lambda a: None if a is None else jnp.copy(a)
        rg, re = ref(g0, deltas0, weights, cp(e0))
        ra = ref.reduce(deltas0, weights, cp(e0))[0]
        rg, ra = np.asarray(rg), np.asarray(ra)
        for shape in ((1, 2), (1, 8), (2, 4)):
            mesh = make_flat_mesh(shape)
            lay = prog.flat_layout(params, mesh=mesh)
            assert isinstance(lay, ShardedFlatLayout)
            sp = prog.shard_params(params, mesh)
            g = lay.flatten(sp)
            np.testing.assert_array_equal(
                np.asarray(g)[:base.padded], np.asarray(g0))
            d = jnp.pad(deltas0, ((0, 0), (0, lay.tail)))
            e = (jnp.pad(err0, ((0, 0), (0, lay.tail)))
                 if density < 1 else None)
            step = get_server_step(lay, density, quant)
            sg, se = step(g, d, weights, cp(e))
            sa = step.reduce(d, weights, cp(e))[0]
            sg = np.asarray(sg)[:base.padded]
            sa = np.asarray(sa)[:base.padded]
            bitwise = shape[0] == 1 and not quant
            if bitwise:
                np.testing.assert_array_equal(sg, rg)
                np.testing.assert_array_equal(sa, ra)
                if re is not None:
                    np.testing.assert_array_equal(
                        np.asarray(se)[:, :base.padded], np.asarray(re))
            else:
                np.testing.assert_allclose(sg, rg, atol=1e-6)
                np.testing.assert_allclose(sa, ra, atol=1e-6)
                if re is not None:
                    np.testing.assert_allclose(
                        np.asarray(se)[:, :base.padded], np.asarray(re),
                        atol=1e-6)
            print(f"OK d={density} q={quant} mesh={shape}")
"""


def test_sharded_step_meshes_match_legacy_subprocess():
    out = _run_subprocess(BATTERY)
    assert out.count("OK") == 9, out


TAIL_ACCOUNTING = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.vgg import VGG5
    from repro.models.split_program import get_split_program
    from repro.fl.flatbuf import ShardedFlatLayout, get_server_step
    from repro.parallel.sharding import make_flat_mesh

    # vgg5 has 580 blocks: 580 % 8 == 4, so (1, 8) is a natural
    # non-divisible case -> 4 padding blocks on the final shard.
    prog = get_split_program(VGG5)
    params = prog.init(jax.random.PRNGKey(0))
    base = prog.flat_layout(params)
    mesh = make_flat_mesh((1, 8))
    lay = prog.flat_layout(params, mesh=mesh)
    assert lay.tail == 4 * lay.block, lay.tail
    assert lay.padded == base.padded + lay.tail
    assert lay.padded % (lay.block * 8) == 0
    # the tail is masked out of the compression metadata, not replicated
    meta = lay.block_meta(0.05)
    assert meta.shape[0] == lay.padded // lay.block
    np.testing.assert_array_equal(meta[-4:], [[0, 1]] * 4)
    np.testing.assert_array_equal(meta[:-4], base.block_meta(0.05))
    # per-shard byte accounting: every device owns a distinct shard of
    # exactly padded/8 elements -- nothing is replicated
    g = lay.flatten(params)
    shards = sorted(g.addressable_shards, key=lambda s: s.index[0].start)
    assert len(shards) == 8
    starts = [s.index[0].start for s in shards]
    assert starts == [i * lay.shard_elems for i in range(8)]
    assert sum(s.data.size for s in shards) == lay.padded
    assert all(s.data.nbytes == lay.shard_elems * 4 for s in shards)
    # flatten puts zeros in the tail, and a topk step keeps them zero
    np.testing.assert_array_equal(np.asarray(g)[base.padded:], 0.0)
    step = get_server_step(lay, 0.05, False)
    K = 3
    d = jnp.pad(jnp.stack([0.01 * jax.random.normal(k, (base.padded,))
                           for k in jax.random.split(
                               jax.random.PRNGKey(1), K)]),
                ((0, 0), (0, lay.tail)))
    e = jnp.zeros((K, lay.padded), jnp.float32)
    sg, se = step(g, d, [1.0] * K, e)
    np.testing.assert_array_equal(np.asarray(sg)[base.padded:], 0.0)
    np.testing.assert_array_equal(np.asarray(se)[:, base.padded:], 0.0)
    print("TAIL-OK")
"""


def test_nondivisible_tail_masked_not_replicated_subprocess():
    out = _run_subprocess(TAIL_ACCOUNTING)
    assert "TAIL-OK" in out


RESUME = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import tempfile
    import jax, numpy as np
    from repro.configs.vgg import VGG5
    from repro.data.synthetic import make_cifar_like, split_clients
    from repro.fl.loop import FLConfig, run_federated

    clients = split_clients(make_cifar_like(90, seed=0), 3)
    test = make_cifar_like(30, seed=9)
    tmp = tempfile.mkdtemp()

    def cfg(sub):
        return FLConfig(rounds=4, local_iters=1, batch_size=20, mode="sfl",
                        static_op=2, seed=0, delta_density=0.5,
                        mesh_shape=(1, 2),
                        checkpoint_dir=os.path.join(tmp, sub),
                        checkpoint_every=2)

    full = run_federated(VGG5, clients, test, cfg("full"))
    interrupted = cfg("resume")
    interrupted.rounds = 2
    run_federated(VGG5, clients, test, interrupted)
    resumed = run_federated(VGG5, clients, test, cfg("resume"), resume=True)
    np.testing.assert_array_equal(resumed["accuracy"][-2:],
                                  full["accuracy"][-2:])
    for a, b in zip(jax.tree_util.tree_leaves(resumed["params"]),
                    jax.tree_util.tree_leaves(full["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("RESUME-OK")
"""


def test_sharded_sync_resume_bitwise_subprocess():
    out = _run_subprocess(RESUME)
    assert "RESUME-OK" in out
