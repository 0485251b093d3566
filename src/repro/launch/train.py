"""End-to-end FedAdapt LM training driver (CPU-runnable).

Trains a real LM with the full FedAdapt stack: K heterogeneous client
slices, PPO controller choosing per-group Offloading Points each round,
split execution through the ``SplitProgram`` API (optionally int8
smashed-data), FedAvg aggregation, straggler deadlines, failure injection
and checkpoint/resume.  Model-agnostic: any arch with a registered
``SplitProgram`` trains through the same driver (``--arch mamba2-780m-smoke``
runs the attention-free SSM family).

    PYTHONPATH=src python -m repro.launch.train --arch lm100m --rounds 40 \\
        --local-steps 5 --batch 2 --seq 64 --ckpt-dir /tmp/fedadapt_lm

Round *times* come from the Eq. 1 cost model with heterogeneous slice
profiles (this container has no testbed); the model updates are real.
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs.lm_small import SMALL_CONFIGS
from repro.core import costmodel as cm
from repro.core.agent import PPOAgent, PPOConfig
from repro.core.controller import FedAdaptController
from repro.core.env import SimulatedCluster
from repro.data.synthetic import batch_tokens, make_token_stream
from repro.fl.fedavg import fedavg_delta
from repro.models.split_program import get_split_program
from repro.optim import adamw, cosine
from repro.runtime.failures import FailureInjector
from repro.runtime.straggler import deadline_mask, reweight


def make_client_profiles(k: int):
    """Heterogeneous slices: one fast 'server-class' group, mid group, one
    straggler (mirrors the paper's Jetson / Pi4+Pi3s / throttled-Pi4)."""
    profs = []
    for i in range(k):
        if i == 0:
            profs.append(cm.slice_profile(f"client{i}", chips=8, mfu=0.5))
        elif i == k - 1:
            profs.append(cm.slice_profile(f"client{i}", chips=1, mfu=0.15))
        else:
            profs.append(cm.slice_profile(f"client{i}", chips=2, mfu=0.3))
    return profs


def resolve_arch(name: str):
    if name in SMALL_CONFIGS:
        return SMALL_CONFIGS[name]
    # "<registry-arch>-smoke" trains the family's smoke config — the driver
    # is generic over every registered SplitProgram family
    from repro.configs import get_smoke_config
    return get_smoke_config(name[: -len("-smoke")])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm16m",
                    choices=list(SMALL_CONFIGS) + ["mamba2-780m-smoke",
                                                   "llama3-8b-smoke"])
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mode", default="fedadapt", choices=["fedadapt", "fl"])
    ap.add_argument("--quantize-transfer", action="store_true",
                    help="int8 smashed data across the cut")
    ap.add_argument("--deadline", type=float, default=0.0)
    ap.add_argument("--fail-prob", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = resolve_arch(args.arch)
    program = get_split_program(cfg)
    K = args.clients
    print(f"# FedAdapt LM training: {cfg.name} "
          f"({cfg.param_count()/1e6:.0f}M params), K={K} clients, "
          f"mode={args.mode}", flush=True)

    params = program.init(jax.random.PRNGKey(args.seed))
    opt = adamw(schedule=cosine(args.lr, args.rounds * args.local_steps,
                                warmup_steps=20))
    opt_state = opt.init(params)

    streams = [make_token_stream(400_000, cfg.vocab_size, seed=args.seed + i)
               for i in range(K)]

    @partial(jax.jit, static_argnames=("op", "quant"))
    def local_step(p, o, tokens, labels, op, quant):
        loss, grads = jax.value_and_grad(
            lambda q: program.loss_through_cut(
                q, {"tokens": tokens, "labels": labels}, op,
                quantize=quant))(p)
        p, o = opt.update(p, grads, o)
        return p, o, loss

    # --- FedAdapt controller over the cost model -------------------------
    # bf16-on-the-wire cut bytes, matching the previous lm_workload model
    workload = cm.program_workload(program, args.batch, args.seq,
                                   bytes_per_el=2)
    native = program.native_op
    op_candidates = sorted(set(list(range(0, native + 1, 2)) + [native]))
    devices = make_client_profiles(K)
    server_flops = cm.slice_profile("server", chips=64, mfu=0.5).flops_per_s
    sim = SimulatedCluster(workload, devices, server_flops, op_candidates,
                           iterations=args.local_steps, jitter=0.03,
                           seed=args.seed)
    agent = PPOAgent(PPOConfig(num_groups=3, factored=True), seed=args.seed)
    controller = FedAdaptController(workload, op_candidates, num_groups=3,
                                    low_bw_threshold=None, agent=agent,
                                    seed=args.seed)
    injector = FailureInjector(args.fail_prob, seed=args.seed)

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_round = 0
    if mgr is not None and args.resume:
        restored, step = mgr.restore_latest(
            {"params": params, "opt": opt_state})
        if restored is not None:
            params, opt_state = restored["params"], restored["opt"]
            start_round = int(step)
            print(f"# resumed from round {start_round}", flush=True)

    baseline = sim.round_times(sim.native_ops(), 0)
    controller.begin(baseline)
    times = baseline
    print("round,loss,round_time_s,ops,dropped,wall_s", flush=True)
    for r in range(start_round, args.rounds):
        t0 = time.time()
        if args.mode == "fedadapt":
            plan = controller.plan(times, sim.bandwidths(r), explore=True)
            ops = plan.ops
        else:
            ops = sim.native_ops()
        alive = injector.round_mask(K, round_idx=r)
        client_params, losses = [], []
        for k in range(K):
            if not alive[k]:
                continue
            p_k, o_k = params, opt_state
            for step in range(args.local_steps):
                toks, labs = batch_tokens(streams[k], args.batch, args.seq,
                                          r * args.local_steps + step)
                p_k, o_k, loss = local_step(
                    p_k, o_k, jnp.asarray(toks), jnp.asarray(labs),
                    ops[k], args.quantize_transfer)
            client_params.append(p_k)
            losses.append(float(loss))
        times = sim.round_times(ops, r)
        keep = np.ones(K, bool)
        if args.deadline > 0:
            keep = deadline_mask(times, args.deadline)
        keep &= alive
        w = reweight(np.ones(K), keep)
        survivors = [cp for k, cp in zip(np.flatnonzero(alive), client_params)
                     if keep[k]]
        sw = [w[k] for k in np.flatnonzero(alive) if keep[k]]
        if survivors:
            params = fedavg_delta(params, survivors, sw)
            # optimizer state follows the fastest surviving client (local
            # opt states are client-private in FedAvg)
            opt_state = opt.update(params, jax.tree_util.tree_map(
                jnp.zeros_like, params), opt_state)[1]
        if args.mode == "fedadapt":
            controller.feedback(times)
        print(f"{r},{np.mean(losses):.4f},{times.max():.3f},"
              f"\"{ops}\",{int(K - keep.sum())},{time.time()-t0:.1f}",
              flush=True)
        if mgr is not None and (r + 1) % args.ckpt_every == 0:
            mgr.save({"params": params, "opt": opt_state}, r + 1)
    print("# done", flush=True)
    return params


if __name__ == "__main__":
    main()
