"""The system under test for a DLRM configuration, bound through the
program's own serving pieces: ``serving.bind_model`` (engine, seeded weights
and tables made on the device, the jitted serve step), the program's
``DynamicBatcher`` and padder, and ``ServingRuntime.warmup`` for exactly the
mix's buckets.  Datapath knobs (``impl``, ``front_end``, ``dedup``) are left
at the program's defaults, so a change of default is what gets measured.
"""
from __future__ import annotations

import re

import numpy as np


class Program:
    def __init__(self, model: dict, mix: dict, seed: int, devices):
        import jax
        from repro.configs.base import DLRMConfig
        from repro.distributed.sharding import make_mesh
        from repro.serving import (BatcherConfig, BindingExecutor,
                                   DynamicBatcher, RuntimeConfig,
                                   ServingRuntime, bind_model,
                                   dummy_request_factory, make_padder)

        dep = model["deployment"]
        self.cfg = DLRMConfig(
            name=model["name"], emb_num=model["emb_num"],
            emb_dim=model["emb_dim"], bottom_mlp=tuple(model["bottom_mlp"]),
            top_mlp=tuple(model["top_mlp"]), n_tables=model["n_tables"],
            pooling=int(mix["pooling"]), n_dense=model["n_dense"])
        self.mesh = make_mesh(tuple(dep["mesh"]), tuple(dep["axes"]),
                              devices=devices)
        self.binding = bind_model(self.cfg, self.mesh, seed=seed,
                                  mode=dep["mode"],
                                  hot_fraction=dep["hot_fraction"])
        rt_cfg = RuntimeConfig(observe_every=dep["observe_every"],
                               replan_every=dep["replan_every"])
        self.batcher = DynamicBatcher(BatcherConfig(
            batch_sizes=tuple(sorted(mix["buckets"])),
            poolings=(self.cfg.pooling,),
            max_wait_ms=float(mix["slo_ms"]) / 2))
        self.runtime = ServingRuntime(BindingExecutor(self.binding),
                                      self.batcher, make_padder(self.cfg),
                                      rt_cfg)
        self.pad = self.runtime.padder
        self.service = self.runtime.service_model
        self.queue_capacity = rt_cfg.queue_capacity
        self.observe_every = rt_cfg.observe_every
        self.replan_every = rt_cfg.replan_every
        with self.mesh:
            self.runtime.warmup(dummy_request_factory(self.cfg))
        page = self.binding.engine.cfg.page_size
        rows = -(-self.cfg.emb_num // page) * page
        self.offsets = (np.arange(self.cfg.n_tables, dtype=np.int64)
                        * rows)[:, None].astype(np.int32)
        self._jax = jax

    def features(self, reqs):
        """(dense, global ids) as the program's requests carry them."""
        return reqs.dense, reqs.ids + self.offsets[None]

    def execute(self, batch) -> np.ndarray:
        return np.asarray(self.binding.execute(batch))

    def observe(self, batch) -> None:
        self.binding.observe(batch)

    def replan(self) -> None:
        self.binding.replan()

    def step_modules(self) -> set:
        """Names of the compiled serve-step modules, read from the lowered
        step, so the trace reduction finds their events on the device."""
        jnp = self._jax.numpy
        names = set()
        for bucket in self.batcher.buckets():
            reqs = [self.runtime.warmup_factory(i, bucket.pooling)
                    for i in range(bucket.batch)]
            jb = {k: jnp.asarray(v)
                  for k, v in self.pad(reqs, bucket).items()}
            text = self.binding.steps[self.binding.active].lower(
                self.binding.params, self.binding.state, jb).as_text()
            m = re.search(r"module @([\w.\-]+)", text)
            if m:
                names.add(m.group(1))
        return names

    def plan_traces(self) -> int:
        return int(self.binding.plan_stats()["traces"])

    def reset_plan_stats(self) -> None:
        self.binding.reset_plan_stats()

    def close(self) -> None:
        """Drop every device buffer the program holds."""
        self.binding.state = None
        self.binding.params = None
        self.binding = self.runtime = None
