"""End-to-end training driver.

Wires the whole stack: config -> mesh -> sharded init -> jit train_step
-> deterministic data stream -> checkpoint manager (async, atomic,
retained) -> fault-tolerance hooks (heartbeats + straggler EWMA; on this
single-host container the heartbeat source is simulated, the decision
logic is the production state machine).

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
      --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ck

``--kill-at N`` injects a failure at step N and demonstrates
restart-from-checkpoint continuing to the target step with identical
data order (the FT guarantee).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, latest_step
from repro.configs import get_config, reduced
from repro.data.tokens import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.launch.steps import (batch_shardings, init_state, make_train_step,
                                train_shardings)
from repro.runtime.elastic import plan_remesh
from repro.runtime.ft import FaultToleranceManager, StragglerDetector


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--kill-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--async-dispatch", action="store_true",
                    help="split-phase MoE dispatch: issue the exchange "
                         "wire, overlap the always-on paths, then finish "
                         "(DESIGN.md section 1.9)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.async_dispatch:
        cfg = dataclasses.replace(cfg, moe_async_dispatch=True)

    n_dev = len(jax.devices())
    model_par = 1
    data_par = n_dev // model_par
    mesh = make_test_mesh(data=data_par, model=model_par)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    rng = jax.random.PRNGKey(args.seed)
    params, opt, psh, osh = init_state(cfg, mesh, rng)
    step_fn = jax.jit(make_train_step(cfg, mesh),
                      donate_argnums=(0, 1))

    stream = TokenStream(vocab=cfg.vocab, seq_len=args.seq,
                         global_batch=args.batch, seed=args.seed)
    ckpt = CheckpointManager(args.ckpt_dir, save_interval=args.ckpt_every) \
        if args.ckpt_dir else None
    ft = FaultToleranceManager(n_nodes=max(n_dev, 1))
    strag = StragglerDetector(n_nodes=max(n_dev, 1))

    start_step = 0
    if ckpt and latest_step(args.ckpt_dir) is not None:
        (params, opt, stream_state), start_step = ckpt.restore_latest(
            (params, opt, stream.state_dict()))
        stream.load_state_dict(jax.tree_util.tree_map(int, stream_state))
        print(f"restored checkpoint at step {start_step}")

    stream.step = start_step
    losses = []
    for step in range(start_step, args.steps):
        if args.kill_at is not None and step == args.kill_at:
            if ckpt:
                ckpt.wait()   # drain in-flight async save, like a real
                #               preemption handler would before exiting
            # drive the production recovery state machine with the kill:
            # node 0 goes silent, every survivor keeps heartbeating, and
            # the detector's decision selects the restart step + remesh
            killed = 0
            now = time.time()
            for node in range(max(n_dev, 1)):
                if node != killed:
                    ft.heartbeat(node, now)
            ckpt_step = (latest_step(args.ckpt_dir) or 0) if ckpt else 0
            dec = ft.tick(now + ft.interval * ft.timeout_beats,
                          last_ckpt_step=ckpt_step)
            print(f"[ft] injected failure at step {step}: "
                  f"node {killed} silent -> decision {dec}")
            if dec.failed_nodes and not dec.promoted_spares:
                survivors = max(n_dev, 1) - len(dec.failed_nodes)
                try:
                    plan = plan_remesh(tuple(mesh.axis_names),
                                       tuple(mesh.devices.shape), survivors)
                    print(f"[ft] remesh plan: {plan.old_shape} -> "
                          f"{plan.new_shape} (dropped "
                          f"{plan.dropped_devices}, batch/shard x"
                          f"{plan.batch_per_shard_scale:.2f})")
                except ValueError as e:
                    print(f"[ft] remesh impossible: {e}")
            print(f"[ft] restart this command to resume from step "
                  f"{dec.restart_step}; the survivors re-inject the dead "
                  "rank's checkpointed container shards on restore")
            return 17
        hb = time.time()
        batch_np = stream.next_batch()
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        t0 = time.time()
        params, opt, metrics = step_fn(params, opt, batch)
        loss = float(metrics["loss"])
        dt = time.time() - t0
        losses.append(loss)
        for node in range(max(n_dev, 1)):
            ft.heartbeat(node, hb)
            strag.observe(node, dt)
        dec = ft.tick(time.time(), last_ckpt_step=step)
        if dec.action != "none":
            print(f"[ft] decision: {dec}")
        if ckpt:
            ckpt.maybe_save(step + 1, (params, opt, stream.state_dict()))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['grad_norm']):8.3f} "
                  f"{dt*1000:7.1f} ms "
                  f"stragglers={strag.stragglers()}")
        if not np.isfinite(loss):
            print("NON-FINITE LOSS — aborting")
            return 1
    if ckpt:
        ckpt.wait()
    first = np.mean(losses[:5]) if len(losses) >= 5 else losses[0]
    last = np.mean(losses[-5:])
    print(f"loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
