"""Fault-tolerant training driver (end-to-end: CIAO ingest → train loop).

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen3-1.7b --reduced --dataset ycsb --budget-us 1.0 \
        --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/run1

Flow:
  1. Build the CIAO plan for the dataset's recipe workload under the client
     budget; spin up client shards; ingest with the work-stealing
     coordinator; construct the recipe batcher + prefetcher.
  2. Build model/optimizer with mesh shardings; auto-resume from the latest
     valid checkpoint in --ckpt-dir (crash-safe: partial writes are ignored).
  3. Train with async checkpointing every --ckpt-every steps.
     ``--fail-at-step N`` injects a crash (SystemExit) for the restart test.

Elastic restarts: the checkpoint stores logical arrays; restore device_puts
onto whatever mesh this run constructed, so the same run directory can be
resumed with a different --mesh-shape.
"""
from __future__ import annotations

import argparse
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.client import NumpyEngine
from repro.core.planner import build_plan
from repro.core.predicates import Query
from repro.core.server import CiaoStore
from repro.core.workload import generate_workload
from repro.data.datasets import generate_records, predicate_pool
from repro.data.pipeline import ClientShard, IngestCoordinator, Prefetcher, RecipeBatcher
from repro.data.tokenizer import ByteTokenizer
from repro.dist import sharding as shd
from repro.models.layers import split
from repro.models.model import build_model
from repro.train import checkpoint as ckpt
from repro.train.train_step import init_opt_state, make_train_step, opt_config_for


def build_data(args, vocab_size: int):
    pool = predicate_pool(args.dataset)
    rng = np.random.default_rng(args.seed)
    wl = generate_workload(
        pool, n_queries=args.n_queries, distribution="zipf", zipf_a=1.5,
        rng=rng, name="train-recipes",
    )
    sample = generate_records(args.dataset, 500, seed=args.seed + 1)
    report = build_plan(wl, sample, budget_us=args.budget_us)
    store = CiaoStore(report.plan)
    engine = NumpyEngine()
    clients = [
        ClientShard(args.dataset, i, engine, report.plan,
                    chunk_records=args.chunk_records,
                    speed=(0.25 if (args.straggler and i == 0) else 1.0))
        for i in range(args.n_clients)
    ]
    coord = IngestCoordinator(clients, store, steal=True)
    coord.run(chunks_per_client=args.chunks_per_client)
    # recipe: the highest-value pushed clause (or full data if none pushed)
    recipe = (
        Query((report.plan.clauses[0],))
        if report.plan.clauses else Query(tuple())
    )
    tok = ByteTokenizer(vocab_size=vocab_size)
    batcher = RecipeBatcher(store, tok, seq_len=args.seq, batch_size=args.batch)
    return report, store, coord, recipe, batcher


def make_mesh(shape_str: str) -> Mesh:
    dims = tuple(int(x) for x in shape_str.split(",") if x)
    names = ("data", "model")[: len(dims)] if len(dims) <= 2 else ("pod", "data", "model")
    devs = jax.devices()
    need = math.prod(dims)
    if len(devs) < need:
        raise RuntimeError(f"mesh {dims} needs {need} devices, have {len(devs)}")
    return Mesh(np.array(devs[:need]).reshape(dims), names)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--dataset", default="ycsb")
    ap.add_argument("--budget-us", type=float, default=1.0)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mesh-shape", default="1,1")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--fail-at-step", type=int, default=None)
    ap.add_argument("--n-clients", type=int, default=4)
    ap.add_argument("--n-queries", type=int, default=20)
    ap.add_argument("--chunk-records", type=int, default=256)
    ap.add_argument("--chunks-per-client", type=int, default=4)
    ap.add_argument("--straggler", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    import dataclasses

    cfg = dataclasses.replace(cfg, microbatches=1)
    model = build_model(cfg)
    mesh = make_mesh(args.mesh_shape)

    report, store, coord, recipe, batcher = build_data(args, cfg.vocab_size)
    print(f"[data] plan: {report.selection.describe()}")
    print(f"[data] loaded {store.stats.n_loaded}/{store.stats.n_records} "
          f"(ratio {store.stats.loading_ratio:.3f}), stolen chunks: {coord.stolen}")

    values, axes = split(model.init(jax.random.PRNGKey(args.seed)))
    params_sh = shd.param_shardings(values, axes, mesh)
    values = jax.tree.map(jax.device_put, values, params_sh)
    opt_cfg = opt_config_for(cfg)
    opt_state = init_opt_state(model, values, opt_cfg)

    start_step = 0
    if args.ckpt_dir:
        latest = ckpt.latest_step(args.ckpt_dir)
        if latest is not None:
            opt_sh = {
                "m": params_sh,
                "v": params_sh,
                "step": NamedSharding(mesh, P()),
            }
            (values, opt_state), manifest = ckpt.restore(
                args.ckpt_dir, latest, (values, opt_state),
                shardings=(params_sh, opt_sh),
            )
            start_step = manifest["step"]
            print(f"[ckpt] resumed from step {start_step}")

    step_fn = jax.jit(
        make_train_step(model, opt_cfg, n_micro=1), donate_argnums=(0, 1)
    )
    writer = ckpt.AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None

    data_it = Prefetcher(batcher.batches(recipe, repeat=True), depth=2)
    losses = []
    t0 = time.time()
    with jax.set_mesh(mesh):
        for step in range(start_step, args.steps):
            tokens, mask = next(data_it)
            batch = {"tokens": jnp.asarray(tokens), "loss_mask": jnp.asarray(mask)}
            values, opt_state, metrics = step_fn(values, opt_state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step {step} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"({(time.time()-t0):.1f}s)")
            if writer and (step + 1) % args.ckpt_every == 0:
                writer.save((values, opt_state), step=step + 1)
            if args.fail_at_step is not None and step + 1 == args.fail_at_step:
                print(f"[fault-injection] crashing at step {step + 1}")
                raise SystemExit(42)
    if writer:
        writer.save((values, opt_state), step=args.steps)
        writer.wait()
    result = {
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "steps_run": len(losses),
        "loading_ratio": store.stats.loading_ratio,
    }
    print(f"[done] {json.dumps(result)}")
    return result


if __name__ == "__main__":
    main()
