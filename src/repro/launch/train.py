"""End-to-end training driver: ``python -m repro.launch.train --arch <id>``.

Wires the full stack: config -> model -> data pipeline -> optimizer ->
fault-tolerant runtime (checkpoint/restart, straggler watchdog) -> metrics.
On this CPU container it runs the *reduced* config by default (the full
configs are exercised by the dry-run); pass --full on real hardware.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import base as cfgs
from repro.configs import get_config, reduced
from repro.data import synth
from repro.launch import compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models import dlrm as dlrm_mod
from repro.models import gnn as gnn_mod
from repro.models import params as prm
from repro.models import recsys as rec_mod
from repro.models import transformer as tfm
from repro.optim.optimizers import adafactor, adam, rowwise_adagrad
from repro.runtime.fault_tolerance import (FailureInjector, StragglerWatchdog,
                                           run_resilient)


def train_lm(cfg, mesh, steps: int, batch: int, seq: int, ckpt_dir=None,
             log_every: int = 10) -> Dict[str, Any]:
    params = prm.initialize(tfm.model_specs(cfg, mesh), jax.random.PRNGKey(0))
    opt = adafactor(lr=3e-3)
    opt_state = opt.init(params)
    step_fn = jax.jit(tfm.make_train_step(cfg, mesh, opt))
    batches = list(synth.lm_batches(cfg, batch, seq, steps))
    losses = []
    state = {"params": params, "opt": opt_state}

    def one(state, b):
        p, o, m = step_fn(state["params"], state["opt"],
                          {k: jnp.asarray(v) for k, v in b.items()})
        return {"params": p, "opt": o}, m

    with mesh:
        if ckpt_dir:
            ck = Checkpointer(ckpt_dir)
            rep = run_resilient(one, state, lambda i: batches[i], steps, ck,
                                ckpt_every=max(steps // 4, 1),
                                watchdog=StragglerWatchdog())
            return {"steps": rep.steps_done,
                    "final_loss": float(rep.final_metrics["loss"])}
        for i, b in enumerate(batches):
            state, m = one(state, b)
            losses.append(float(m["loss"]))
            if i % log_every == 0:
                print(f"step {i:4d} loss {losses[-1]:.4f}")
    return {"first_loss": losses[0], "final_loss": losses[-1],
            "losses": losses}


def train_dlrm(cfg, mesh, steps: int, batch: int, mode: str = "pifs",
               replan_every: int = 0, log_every: int = 10) -> Dict[str, Any]:
    engine, offs = dlrm_mod.build_engine(cfg, mesh)
    params = prm.initialize(dlrm_mod.model_specs(cfg, mesh),
                            jax.random.PRNGKey(0))
    state = engine.init_state(jax.random.PRNGKey(1))
    opt, eopt = adam(1e-3), rowwise_adagrad(5e-2)
    ostate = opt.init(params)
    eostate = eopt.init({"cold": state.cold, "hot": state.hot})
    step_fn = jax.jit(dlrm_mod.make_train_step(cfg, engine, mesh, opt, eopt,
                                               mode=mode))
    losses = []
    with mesh:
        for i, b in enumerate(synth.dlrm_batches(cfg, batch, steps)):
            jb = {k: jnp.asarray(v) for k, v in b.items()}
            params, state, ostate, eostate, m = step_fn(
                params, state, ostate, eostate, jb)
            losses.append(float(m["loss"]))
            state = engine.observe(state, jb["indices"])
            if replan_every and (i + 1) % replan_every == 0:
                state, stats = engine.plan_and_migrate(state)
            if i % log_every == 0:
                print(f"step {i:4d} loss {losses[-1]:.4f}")
    return {"first_loss": losses[0], "final_loss": losses[-1],
            "losses": losses}


def train_rec(cfg, mesh, steps: int, batch: int, mode: str = "pifs",
              log_every: int = 10) -> Dict[str, Any]:
    engine, offs = rec_mod.build_engine(cfg, mesh)
    params = prm.initialize(rec_mod.model_specs(cfg, mesh),
                            jax.random.PRNGKey(0))
    state = engine.init_state(jax.random.PRNGKey(1))
    opt, eopt = adam(1e-3), rowwise_adagrad(5e-2)
    ostate = opt.init(params)
    eostate = eopt.init({"cold": state.cold, "hot": state.hot})
    step_fn = jax.jit(rec_mod.make_train_step(cfg, engine, offs, mesh, opt,
                                              eopt, mode=mode))
    losses = []
    with mesh:
        for i, b in enumerate(synth.rec_batches(cfg, batch, steps)):
            jb = {k: jnp.asarray(v) for k, v in b.items()}
            params, state, ostate, eostate, m = step_fn(
                params, state, ostate, eostate, jb)
            losses.append(float(m["loss"]))
            if i % log_every == 0:
                print(f"step {i:4d} loss {losses[-1]:.4f}")
    return {"first_loss": losses[0], "final_loss": losses[-1],
            "losses": losses}


def train_gnn(cfg, mesh, steps: int, log_every: int = 10) -> Dict[str, Any]:
    g = synth.make_graph(256, 2048, d_feat=32, n_classes=cfg.n_classes)
    params = prm.initialize(gnn_mod.model_specs(cfg, 32),
                            jax.random.PRNGKey(0))
    opt = adam(1e-2)
    ostate = opt.init(params)
    step_fn = jax.jit(gnn_mod.make_train_step(cfg, mesh, opt, "full"))
    batch = {k: jnp.asarray(v) for k, v in g.items()}
    losses = []
    with mesh:
        for i in range(steps):
            params, ostate, m = step_fn(params, ostate, batch)
            losses.append(float(m["loss"]))
            if i % log_every == 0:
                print(f"step {i:4d} loss {losses[-1]:.4f}")
    return {"first_loss": losses[0], "final_loss": losses[-1],
            "losses": losses}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mode", default="pifs",
                    choices=["pifs", "pond", "beacon"])
    ap.add_argument("--full", action="store_true",
                    help="full config (real hardware)")
    ap.add_argument("--ckpt-dir")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    n_dev = len(jax.devices())
    tp = min(4, n_dev)
    mesh = make_test_mesh(n_dev, tp)
    t0 = time.time()
    if isinstance(cfg, cfgs.LMConfig):
        out = train_lm(cfg, mesh, args.steps, args.batch, args.seq,
                       ckpt_dir=args.ckpt_dir)
    elif isinstance(cfg, cfgs.DLRMConfig):
        out = train_dlrm(cfg, mesh, args.steps, args.batch, mode=args.mode,
                         replan_every=max(args.steps // 4, 1))
    elif isinstance(cfg, cfgs.RecConfig):
        out = train_rec(cfg, mesh, args.steps, args.batch, mode=args.mode)
    else:
        out = train_gnn(cfg, mesh, args.steps)
    out.pop("losses", None)
    print(f"done in {time.time() - t0:.1f}s: {out}")


if __name__ == "__main__":
    main()
