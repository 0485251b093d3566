"""Smoke run of the main path on a TPU: federated training and serving.

    python chip_smoke.py               # one chip: the phases below
    python chip_smoke.py --chips 4     # four chips: the mesh path only

One chip, one process, every phase must pass:

1. ``run_federated`` on VGG5 (the paper's model at its Table IV widths,
   CIFAR-shaped synthetic data): K=4 clients, batch 100, batched engine,
   top-k (density 0.01) + int8 deltas through the fused server step, and
   int8 smashed data for the clients the planner cuts at OP1 and OP2.
   Then one round with the sequential engine.  Checks: finite losses, a
   test loss that falls, the fused server step against
   ``reference_server_step`` on a trained round's deltas and EF rows, the
   top-k kernel against ``_topk_blocks_ref``, and ``tpu_custom_call`` in
   the compiled server and fleet steps.
2. ``run_federated`` on LM100M at its full widths: K=4, batched engine,
   top-k + int8 server step.  Check: finite losses.
3. ``ServeEngine`` on the phase-2 params: requests of different lengths
   that free and reclaim slots mid-decode, one ``ParamStore`` hot swap and
   one more request, at fp32 matmul precision.  Checks: token-for-token
   equality with ``reference_decode`` and one compile per engine program.
   Then the same requests at the default precision: they complete with one
   compile per program, and the agreement with ``reference_decode`` is
   printed.

``--chips 4`` runs only the device-mesh path and its single-device
comparison: ``run_federated`` on VGG5, K=8, batched, top-k + int8, at
``mesh_shape=(4, 1)`` and at ``mesh_shape=None``; then, on the same inputs,
the sharded fleet step against the single device (``phase_mesh`` says
how), and the server step at ``(4, 1)`` and ``(1, 4)`` against the
single-device step within ``allclose(atol=1e-6)``.

Times are host wall-clock seconds around work that ends in
``block_until_ready``; compile seconds are JAX's own lowering and
backend-compile events inside the same window (tracing counts as rest).
This is a smoke run, not a benchmark.  The last line of standard output is
the JSON result; without a TPU the script exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from contextlib import contextmanager

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# lowering and XLA compile; tracing is left out because a jit traced inside
# another's trace reports its own duration too, and would count twice
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_event(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration


class Failed(Exception):
    """A smoke check that did not hold."""


def check(ok: bool, what: str, failures=None) -> None:
    """Print the verdict; a failure raises, or joins ``failures`` where the
    caller wants every check of a phase run before it fails."""
    print(f"  check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        if failures is None:
            raise Failed(what)
        failures.append(what)


@contextmanager
def timed(label: str):
    """Wall-clock seconds of the block, and the compile seconds in it.  The
    block must end its own device work with ``block_until_ready``."""
    c0, t0 = _compile_s[0], time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    comp = _compile_s[0] - c0
    print(f"  wall-clock {label}: {wall:.3f} s, of which compile "
          f"{comp:.3f} s, rest {wall - comp:.3f} s", flush=True)


def _max_abs_diff(a, b) -> float:
    import jax
    import numpy as np
    worst = 0.0
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        worst = max(worst, float(np.max(np.abs(np.asarray(x, np.float64)
                                               - np.asarray(y, np.float64)))))
    return worst


def _allclose(a, b) -> bool:
    """The mesh-vs-single-device contract the mesh tests pin: every leaf
    ``np.allclose(atol=1e-6)``."""
    import jax
    import numpy as np
    return all(np.allclose(np.asarray(x), np.asarray(y), atol=1e-6)
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _test_loss(program, params, test) -> float:
    import jax
    import jax.numpy as jnp
    batch = {k: jnp.asarray(v) for k, v in test.items()}
    return float(jax.jit(lambda p, b: program.loss_through_cut(
        p, b, program.native_op))(params, batch))


def fixed_planner(ops):
    """A planner that gives each client its own fixed OP, every round."""
    from repro.fl.planner import Planner

    class FixedPlanner(Planner):
        def plan(self, round_idx, last_times, bandwidths):
            return [int(o) for o in ops]

    return FixedPlanner()


# =============================================================================
# phase 1: VGG5 federated training
# =============================================================================
def phase_vgg(seed: int, rounds: int = 4, local_iters: int = 5,
              per_client: int = 500):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.vgg import VGG5
    from repro.data.loader import FleetLoader
    from repro.data.synthetic import make_cifar_like, split_clients
    from repro.fl.flatbuf import get_server_step, reference_server_step
    from repro.fl.fleet import get_engine
    from repro.fl.loop import FLConfig, run_federated
    from repro.kernels.topk_compress.ops import _topk_blocks_ref
    from repro.kernels.topk_compress.topk_compress import topk_compress_pallas
    from repro.models.split_program import get_split_program

    print("phase 1: run_federated VGG5, K=4, batch 100", flush=True)
    K, batch = 4, 100
    program = get_split_program(VGG5)
    op1, op2 = VGG5.ops[0], VGG5.ops[1]
    ops = [op1, op2, program.native_op, program.native_op]
    clients = split_clients(make_cifar_like(K * per_client, seed=seed), K)
    test = make_cifar_like(500, seed=seed + 1)
    fl = FLConfig(rounds=rounds, local_iters=local_iters, batch_size=batch,
                  engine="batched", delta_density=0.01, quantize_deltas=True,
                  quantize_transfer=True, seed=seed)
    init = program.init(jax.random.PRNGKey(seed))
    loss0 = _test_loss(program, init, test)
    with timed(f"{rounds} rounds, batched engine, OPs {ops}"):
        hist = run_federated(VGG5, clients, test, fl,
                             planner=fixed_planner(ops))
        params = jax.block_until_ready(hist["params"])
    loss1 = _test_loss(program, params, test)
    acc = [round(float(a), 4) for a in hist["accuracy"]]
    print(f"  test accuracy per round {acc}; test loss {loss0:.4f} -> "
          f"{loss1:.4f}", flush=True)
    check(bool(np.isfinite(hist["accuracy"]).all()) and np.isfinite(loss1),
          "VGG5 batched: accuracy and test loss finite")
    check(loss1 < loss0, "VGG5 batched: test loss falls over training")

    seq_fl = FLConfig(rounds=1, local_iters=local_iters, batch_size=batch,
                      engine="sequential", delta_density=0.01,
                      quantize_deltas=True, quantize_transfer=True, seed=seed)
    with timed("1 round, sequential engine"):
        shist = run_federated(VGG5, clients, test, seq_fl,
                              planner=fixed_planner(ops))
        sparams = jax.block_until_ready(shist["params"])
    sloss = _test_loss(program, sparams, test)
    check(bool(np.isfinite(shist["accuracy"]).all()) and np.isfinite(sloss),
          f"VGG5 sequential: accuracy {float(shist['accuracy'][0]):.4f} and "
          f"test loss {sloss:.4f} finite")

    # -- the fused server step against the per-leaf reference, on one
    # round's inputs: deltas from a batched-engine round and the EF rows
    # the previous round's server step left ------------------------------
    eng = get_engine("batched", program, local_iters, seed, augment=True,
                     quantize=True)
    loader = FleetLoader.for_clients(clients, batch, seed=seed)
    layout = program.flat_layout(params)
    step = get_server_step(layout, 0.01, True)
    sizes = [1.0, 2.0, 3.0, 4.0]
    g = layout.flatten(params)
    idxs, rows = eng.run_round(params, loader, ops, list(range(K)), 0, fl.lr)
    g, errs = step(g, layout.rows_to_deltas(rows, g),
                   [sizes[k] for k in idxs], jnp.zeros((K, layout.padded)))
    base = layout.unflatten(g)
    idxs, rows = eng.run_round(base, loader, ops, list(range(K)), 1, fl.lr)
    deltas = layout.rows_to_deltas(rows, g)
    weights = [sizes[k] for k in idxs]
    w = jnp.asarray(np.asarray(weights) / np.sum(weights), jnp.float32)
    text = step._step.lower(g, deltas, w, errs, None).compile().as_text()
    check("tpu_custom_call" in text,
          "compiled server step holds tpu_custom_call (top-k, int8)")
    ref_params, ref_err = reference_server_step(
        layout, base, [layout.unflatten(deltas[i]) for i in range(K)],
        weights, errs, density=0.01, quantize=True)
    with timed("fused server step, one call"):
        new_g, new_err = step(g, deltas, weights, jnp.copy(errs))
        new_g = jax.block_until_ready(new_g)
    dp = _max_abs_diff(layout.unflatten(new_g), ref_params)
    de = _max_abs_diff(new_err, ref_err)
    check(dp <= 1e-6 and de <= 1e-7,
          f"fused server step == reference_server_step on a trained round's "
          f"deltas (max |delta| {float(jnp.abs(deltas).max()):.3g}) and EF "
          f"rows (max |err| {float(jnp.abs(errs).max()):.3g}): max |dparams| "
          f"{dp:.3g} <= 1e-6, max |derr| {de:.3g} <= 1e-7")

    # -- the top-k kernel against the vectorized reference ------------------
    meta = layout.block_meta(0.01)
    kmax = int(meta[:, 1].max())
    nb = layout.padded // layout.block
    x = jax.random.normal(jax.random.PRNGKey(seed + 3), (layout.padded,))
    # ties across the threshold: a block of repeated magnitudes
    x = x.at[:layout.block].set(jnp.tile(jnp.asarray([3.0, -3.0, 1.0, 0.5]),
                                         layout.block // 4))
    metaj = jnp.asarray(meta)
    kern = jax.jit(lambda v, m: topk_compress_pallas(
        v, m, kmax, layout.block, interpret=False))(x, metaj)
    ref = jax.jit(lambda v, m: _topk_blocks_ref(
        v.reshape(nb, layout.block), m, kmax).reshape(-1))(x, metaj)
    same = bool(np.array_equal(np.asarray(kern), np.asarray(ref)))
    check(same, f"topk_compress_pallas == _topk_blocks_ref on {nb} blocks "
                f"(kept {int(np.count_nonzero(np.asarray(kern)))} entries)")

    # -- the fleet step (vmap of scan, int8 cut) compiles the kernels -------
    sds = {"images": jax.ShapeDtypeStruct((1, local_iters, batch, 32, 32, 3),
                                          jnp.float32),
           "labels": jax.ShapeDtypeStruct((1, local_iters, batch), jnp.int32)}
    ftext = eng._step.lower(params, sds, jnp.float32(0.01),
                            op=op1).compile().as_text()
    check("tpu_custom_call" in ftext,
          "compiled batched fleet step at OP1 holds tpu_custom_call "
          "(int8 cut)")
    return params


# =============================================================================
# phase 2: LM100M federated training
# =============================================================================
def phase_lm(seed: int, rounds: int = 3, local_iters: int = 2,
             batch: int = 8, seq: int = 128):
    import jax
    import numpy as np
    from repro.configs.lm_small import LM100M
    from repro.data.synthetic import split_clients, token_dataset
    from repro.fl.loop import FLConfig, run_federated

    cfg, K = LM100M, 4
    print(f"phase 2: run_federated {cfg.name} ({cfg.param_count() / 1e6:.1f}M"
          f" params), K={K}, batch {batch} x seq {seq}", flush=True)
    clients = split_clients(
        token_dataset(K * batch * local_iters * 2, seq, cfg.vocab_size,
                      seed=seed), K)
    test = token_dataset(16, seq, cfg.vocab_size, seed=seed + 1)
    fl = FLConfig(rounds=rounds, local_iters=local_iters, batch_size=batch,
                  lr=0.05, engine="batched", delta_density=0.01,
                  quantize_deltas=True, augment=False, seed=seed)
    with timed(f"{rounds} rounds, batched engine, topk_int8 server step"):
        hist = run_federated(cfg, clients, test, fl)
        params = jax.block_until_ready(hist["params"])
    losses = [round(-float(a), 4) for a in hist["accuracy"]]
    print(f"  test CE loss per round {losses}", flush=True)
    check(bool(np.isfinite(hist["accuracy"]).all()),
          f"{cfg.name}: test losses finite")
    return cfg, params


# =============================================================================
# phase 3: serving across a hot swap
# =============================================================================
def _drive(engine, reqs, label: str):
    """Serve ``reqs`` to completion: the first alone (its wall clock holds
    the engine's compiles), then one admission per decode step, so later
    requests claim slots freed mid-decode.  Returns ``{rid: tokens}``."""
    import jax
    out, pending, steps = {}, list(reqs), 0
    with timed(f"{label}: first request (prefill + claim + decode compile)"):
        rid, prompt, gen = pending.pop(0)
        engine.submit(rid, prompt, gen)
        for fin in engine.step():
            out[fin.rid] = fin.tokens
        jax.block_until_ready(engine.cache)
    t0 = time.perf_counter()
    while pending or engine.num_active:
        if pending and engine.free_slots > 0:
            rid, prompt, gen = pending.pop(0)
            fin = engine.submit(rid, prompt, gen)
            if fin is not None:
                out[fin.rid] = fin.tokens
        for fin in engine.step():
            out[fin.rid] = fin.tokens
        steps += 1
    jax.block_until_ready(engine.cache)
    print(f"  wall-clock {label}: {len(reqs) - 1} more requests, {steps} "
          f"decode steps in {time.perf_counter() - t0:.3f} s "
          f"({sum(g for _, _, g in reqs)} tokens generated in all)",
          flush=True)
    check(sorted(out) == [r[0] for r in reqs] and
          all(len(out[r]) == g for r, _, g in reqs),
          f"{label}: all {len(reqs)} requests completed with their lengths")
    return out


def phase_serve(seed: int, cfg, params, slots: int = 4, max_prompt: int = 32,
                max_seq: int = 64):
    """Strict at fp32 ("highest") matmul precision, where engine and oracle
    agree token for token.  Then the same requests at the default precision
    that ``launch/serve.py`` runs: the run must complete with one compile
    per program; agreement with the oracle at that precision is reported,
    and a flipped token is shown against the fp32 logits of its position."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import api
    from repro.models.split_program import get_split_program
    from repro.serving import ParamStore, ServeEngine, reference_decode

    print(f"phase 3: ServeEngine {cfg.name}, {slots} slots", flush=True)
    rng = np.random.RandomState(seed)
    lens = [(5, 4), (12, 12), (32, 8), (7, 16), (20, 6), (3, 10)]
    reqs = [(rid, rng.randint(0, cfg.vocab_size, pl).astype(np.int32), gen)
            for rid, (pl, gen) in enumerate(lens)]
    ones = {"prefill": 1, "claim": 1, "decode": 1}
    with jax.default_matmul_precision("highest"):
        engine = ServeEngine(cfg, params, slots=slots, max_prompt=max_prompt,
                             max_seq=max_seq)
        out = _drive(engine, reqs, "fp32")
        for rid, prompt, gen in reqs[:3]:
            ref = reference_decode(cfg, params, prompt, gen)
            check(out[rid] == ref, f"fp32: request {rid} (prompt "
                                   f"{len(prompt)}, gen {gen}) == "
                                   f"reference_decode")

        program = get_split_program(cfg)
        layout = program.flat_layout(params)
        store = ParamStore(layout)
        swapped = api.init(cfg, jax.random.PRNGKey(seed + 7), jnp.float32)
        store.publish(swapped)
        with timed("hot swap (publish + adopt)"):
            check(engine.maybe_swap(store), "engine adopted ParamStore v1")
            jax.block_until_ready(engine.params)
        prompt = rng.randint(0, cfg.vocab_size, 9).astype(np.int32)
        engine.submit(100, prompt, 8)
        got = None
        while engine.num_active:
            for fin in engine.step():
                got = fin.tokens
        ref = reference_decode(cfg, layout.unflatten(layout.flatten(swapped)),
                               prompt, 8)
        check(got == ref, "fp32: post-swap request == reference_decode on "
                          "the swapped params")
        counts = engine.compile_counts()
        check(counts == ones, f"fp32: one compile per engine program across "
                              f"requests and swap {counts}")

    engine = ServeEngine(cfg, params, slots=slots, max_prompt=max_prompt,
                         max_seq=max_seq)
    out = _drive(engine, reqs, "default precision")
    counts = engine.compile_counts()
    check(counts == ones,
          f"default precision: one compile per engine program {counts}")
    same, agree, flip = 0, 0, None
    for rid, prompt, gen in reqs:
        ref = reference_decode(cfg, params, prompt, gen)
        n = next((i for i, (a, b) in enumerate(zip(out[rid], ref))
                  if a != b), gen)
        same += n == gen
        agree += n
        if n < gen and flip is None:
            flip = (rid, prompt, n, out[rid][n], ref[n], ref[:n])
    print(f"  default precision vs reference_decode at default precision: "
          f"{same} of {len(reqs)} requests equal token for token; "
          f"{agree} of {sum(g for _, _, g in reqs)} tokens before the "
          f"first difference", flush=True)
    if flip is not None:
        rid, prompt, n, a, b, head = flip
        toks = jnp.asarray(np.concatenate([prompt, head]).astype(np.int32))
        batch = {"tokens": toks[None]}
        lo = np.asarray(api.prefill(cfg, params, batch,
                                    target_seq=len(toks) + 1)[0][0])
        with jax.default_matmul_precision("highest"):
            hi = np.asarray(api.prefill(cfg, params, batch,
                                        target_seq=len(toks) + 1)[0][0])
        top = np.sort(hi)[::-1]
        print(f"  first flip: request {rid}, token {n}: engine {a}, "
              f"reference {b}; fp32 logits {hi[a]:.6f} and {hi[b]:.6f} "
              f"(gap {abs(hi[a] - hi[b]):.3g}; fp32 top-2 gap "
              f"{top[0] - top[1]:.3g}, logit std {hi.std():.3g}); default-"
              f"precision logits there differ from fp32 by up to "
              f"{np.abs(lo - hi).max():.3g}", flush=True)


# =============================================================================
# --chips 4: the device-mesh path against the single device
# =============================================================================
def phase_mesh(seed: int, devices: int = 4, rounds: int = 2,
               local_iters: int = 2, per_client: int = 200):
    """The mesh path end to end, then each of its two programs against the
    single device on the same inputs.  Top-k selection and int8 rounding
    are discontinuous, so a last-ulp difference in a client update may move
    an entry across a threshold and the runs drift apart over rounds: the
    end-to-end difference is printed, and the programs are checked stage by
    stage, where both sides see the same inputs.

    Local training is checked twice.  The mesh against the single device
    running the same per-device program (one dispatch per slice of the
    client axis that a device trains) must agree within the 1e-6 contract
    for every client, the int8-cut clients included.  Against
    ``mesh_shape=None``, which trains four clients per dispatch, the
    programs differ on a single chip too.  XLA:TPU compiles the per-client
    convolutions and matmuls of a four-client dispatch as base-dilated
    convolutions over the client axis, and of a one-client dispatch as
    plain ones.  At the default one-bf16-pass precision the two round
    differently.  At fp32 ("highest") they differ in the last ulps, which
    move some int8 codes of the cut activations by one step.  So that
    comparison is checked at fp32: clients without a cut within 1e-6, cut
    clients within one int8 step of the cut times lr times the local
    iterations.  Every check runs before the phase fails."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.vgg import VGG5
    from repro.data.loader import FleetLoader
    from repro.data.synthetic import make_cifar_like, split_clients
    from repro.fl.flatbuf import get_server_step
    from repro.fl.fleet import (BatchedEngine, StackedRows, get_engine,
                                take_rows)
    from repro.fl.loop import FLConfig, run_federated
    from repro.kernels.quant_transfer.ops import quantize
    from repro.models.split_program import get_split_program
    from repro.parallel.sharding import make_flat_mesh

    K, batch = 8, 100
    failed = []
    program = get_split_program(VGG5)
    cut, native = VGG5.ops[0], program.native_op
    ops = [cut] * (K // 2) + [native] * (K // 2)
    print(f"mesh phase: VGG5, K={K}, batch {batch}, batched engine, "
          f"topk_int8, OPs {ops}", flush=True)
    clients = split_clients(make_cifar_like(K * per_client, seed=seed), K)
    test = make_cifar_like(200, seed=seed + 1)

    def fl(mesh_shape):
        return FLConfig(rounds=rounds, local_iters=local_iters,
                        batch_size=batch, engine="batched",
                        delta_density=0.01, quantize_deltas=True,
                        quantize_transfer=True, seed=seed,
                        mesh_shape=mesh_shape)

    hist = {}
    for shape in (None, (devices, 1)):
        with timed(f"{rounds} rounds run_federated, mesh {shape}"):
            h = run_federated(VGG5, clients, test, fl(shape),
                              planner=fixed_planner(ops))
            jax.block_until_ready(h["params"])
        check(bool(np.isfinite(h["accuracy"]).all()),
              f"mesh {shape}: accuracy per round "
              f"{[round(float(a), 4) for a in h['accuracy']]} finite", failed)
        hist[shape] = h
    drift = _max_abs_diff(hist[None]["params"], hist[(devices, 1)]["params"])
    print(f"  end to end, mesh ({devices}, 1) vs none: max |dparams| "
          f"{drift:.3g} after {rounds} rounds (not a check: see above)",
          flush=True)

    # -- local training: one round from the same params on the same data ----
    params = program.init(jax.random.PRNGKey(seed))
    lr = fl(None).lr
    # each OP group holds K/2 clients: a device of the mesh trains
    # K/2/devices of them per dispatch
    width = K // 2 // devices
    engines = {
        "mesh": get_engine("batched", program, local_iters, seed,
                           augment=True, quantize=True,
                           mesh=make_flat_mesh((devices, 1))),
        "none": get_engine("batched", program, local_iters, seed,
                           augment=True, quantize=True),
        "width": BatchedEngine(program, local_iters, seed, augment=True,
                               quantize=True, max_group=width)}

    def one_round(name):
        loader = FleetLoader.for_clients(clients, batch, seed=seed)
        with timed(f"one round of local training, {name}"):
            idxs, r = engines[name].run_round(params, loader, ops,
                                              list(range(K)), 0, lr)
            jax.block_until_ready(r.tree)
        check(idxs == list(range(K)), f"{name}: clients in order", failed)
        return {op: take_rows(r, [j for j, k in enumerate(idxs)
                                  if ops[k] == op]).tree for op in (cut, native)}

    mesh, one = one_round("mesh"), one_round("width")
    trained = none = one_round("none")
    for op in (cut, native):
        check(_allclose(mesh[op], one[op]),
              f"fleet step mesh ({devices}, 1) == single device at "
              f"{width} client(s) per dispatch, OP {op} clients: allclose("
              f"atol=1e-6), max |d| {_max_abs_diff(mesh[op], one[op]):.3g}",
              failed)
        print(f"  default precision, mesh ({devices}, 1) vs none, OP {op} "
              f"clients: max |d| {_max_abs_diff(mesh[op], none[op]):.3g} "
              f"(the width-{width} vs width-{K // 2} gap of one device; "
              f"checked at fp32 below)", flush=True)

    # one int8 step of the cut activations (largest row scale over the cut
    # clients' data at the round's starting params)
    acts = jax.jit(lambda p, x: program.client_forward(p, {"images": x},
                                                       cut))
    step8 = max(float(jnp.max(quantize(acts(params, jnp.asarray(
        clients[k]["images"])))[1])) for k in range(K) if ops[k] == cut)
    tol = step8 * lr * local_iters
    with jax.default_matmul_precision("highest"):
        mesh, none = one_round("mesh"), one_round("none")
    d_native = _max_abs_diff(mesh[native], none[native])
    d_cut = _max_abs_diff(mesh[cut], none[cut])
    check(_allclose(mesh[native], none[native]),
          f"fp32 fleet step mesh ({devices}, 1) == none, OP {native} "
          f"clients: allclose(atol=1e-6), max |d| {d_native:.3g}", failed)
    check(d_cut <= tol,
          f"fp32 fleet step mesh ({devices}, 1) vs none, OP {cut} (int8 "
          f"cut) clients: max |d| {d_cut:.3g} <= int8 step {step8:.4g} x lr "
          f"{lr} x {local_iters} iterations = {tol:.3g}", failed)

    # -- the server step on the default-precision deltas of that round and
    # the EF rows that a first step on them left -----------------------------
    base = program.flat_layout(params)
    g = base.flatten(params)
    deltas = base.rows_to_deltas(StackedRows(jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b]), trained[cut],
        trained[native])), g)
    weights = list(np.arange(1, K + 1, dtype=np.float64))
    step = get_server_step(base, 0.01, True)
    _, errs = step(g, deltas, weights, jnp.zeros(deltas.shape))
    rg, re = step(g, deltas, weights, jnp.copy(errs))
    rg, re = np.asarray(rg), np.asarray(re)
    for shape in ((devices, 1), (1, devices)):
        lay = program.flat_layout(params, mesh=make_flat_mesh(shape))
        step = get_server_step(lay, 0.01, True)
        with timed(f"server step on mesh {shape}"):
            sg, se = step(lay.flatten(params),
                          jnp.pad(deltas, ((0, 0), (0, lay.tail))), weights,
                          jnp.pad(errs, ((0, 0), (0, lay.tail))))
            sg = jax.block_until_ready(sg)
        sg = np.asarray(sg)[:base.padded]
        se = np.asarray(se)[:, :base.padded]
        check(_allclose(sg, rg) and _allclose(se, re),
              f"server step mesh {shape} == single device: allclose(atol="
              f"1e-6), max |dg| {_max_abs_diff(sg, rg):.3g}, max |derr| "
              f"{_max_abs_diff(se, re):.3g}", failed)
    if failed:
        raise Failed("; ".join(failed))


# =============================================================================
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip phases; 4: the mesh path only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devs[0].platform}); this run "
              f"needs the chip and does not fall back", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's sources are missing ({e})",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    print(f"device {devs[0].device_kind} x{len(devs)}; compile cache {cache}",
          flush=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_mesh(args.seed, devices=4)
        else:
            phase_vgg(args.seed)
            cfg, params = phase_lm(args.seed)
            phase_serve(args.seed, cfg, params)
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"wall-clock total {time.perf_counter() - t0:.3f} s, of which "
          f"compile {_compile_s[0]:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
