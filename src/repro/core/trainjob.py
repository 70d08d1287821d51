"""TFJob analog: a distributed training job over a device mesh.

Two flavours:
  * SupervisedTrainJob -- classifier (LeNet/MNIST, the paper's workload);
  * LMTrainJob         -- any of the 10 assigned architectures, pjit'd over
                          the active mesh with the launch-layer shardings.
Both log metrics through the Experiment tracker, checkpoint into the
ArtifactStore (PVC analog), and time their stages for the Tables 4/5 repro.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.store import ArtifactStore
from ..configs.base import ArchConfig
from ..data import tokens as token_data
from ..launch import shardings as launch_shardings
from ..models import lenet, lm, sharding as msh, steps
from ..optim import adamw
from ..optim.schedules import warmup_cosine
from ..telemetry.events import EventLog


class SupervisedTrainJob:
    """Train a classifier given (init_fn, loss_fn) pure functions."""

    def __init__(self, *, lr: float = 1e-3, batch_size: int = 64,
                 n_steps: int = 200, width: int = 16, seed: int = 0,
                 store: Optional[ArtifactStore] = None,
                 log: Optional[EventLog] = None):
        self.lr = lr
        self.batch_size = batch_size
        self.n_steps = n_steps
        self.width = width
        self.seed = seed
        self.store = store
        self.log = log or EventLog()

    def run(self, data: Iterable[dict], *, report: Optional[Callable] = None,
            checkpoint_name: str = "lenet") -> dict:
        opt_cfg = adamw.AdamWConfig(lr=self.lr, weight_decay=1e-4)
        params = lenet.init_params(jax.random.PRNGKey(self.seed), width=self.width)
        opt = adamw.init_opt_state(params)

        @jax.jit
        def step(params, opt, batch):
            (loss, metrics), grads = jax.value_and_grad(
                lenet.loss_fn, has_aux=True)(params, batch)
            params, opt, om = adamw.adamw_update(params, grads, opt, opt_cfg)
            return params, opt, {**metrics, **om}

        it = iter(data)
        metrics = {}
        t0 = time.perf_counter()
        with self.log.stage("tfjob:train"):
            for i in range(self.n_steps):
                try:
                    batch = next(it)
                except StopIteration:
                    it = iter(data)
                    batch = next(it)
                params, opt, metrics = step(params, opt, batch)
                if report and (i + 1) % max(self.n_steps // 5, 1) == 0:
                    report(i + 1, float(metrics["loss"]))
        wall = time.perf_counter() - t0
        out = {k: float(v) for k, v in metrics.items()}
        out["wall_s"] = wall
        if self.store:
            with self.log.stage("tfjob:checkpoint"):
                out["checkpoint"] = self.store.save_tree(checkpoint_name, params,
                                                         meta=out)
        out["params"] = params
        return out


class LMTrainJob:
    """Distributed LM training over the active mesh (pjit + shardings)."""

    def __init__(self, cfg: ArchConfig, *, batch_size: int, seq_len: int,
                 n_steps: int, lr: float = 3e-4, seed: int = 0,
                 mesh=None, store: Optional[ArtifactStore] = None,
                 log: Optional[EventLog] = None):
        self.cfg = cfg
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.n_steps = n_steps
        self.lr = lr
        self.seed = seed
        self.mesh = mesh
        self.store = store
        self.log = log or EventLog()

    def run(self, *, report: Optional[Callable] = None,
            checkpoint_name: Optional[str] = None,
            resume_from: Optional[str] = None) -> dict:
        """resume_from: checkpoint name in the store -- restores params AND
        optimizer state, continuing the step counter (preemption recovery,
        the Kubernetes-rescheduling analog)."""
        cfg = self.cfg
        opt_cfg = adamw.AdamWConfig(lr=self.lr)
        schedule = functools.partial(warmup_cosine, warmup=max(self.n_steps // 10, 1),
                                     total=self.n_steps)

        def train_step(params, opt_state, batch, step_i):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: steps.loss_fn(p, cfg, batch), has_aux=True)(params)
            params, opt_state, om = adamw.adamw_update(
                params, grads, opt_state, opt_cfg, lr_scale=schedule(step_i))
            return params, opt_state, {"loss": loss, **metrics, **om}

        def init(key):
            params = lm.init_params(key, cfg)
            return params, adamw.init_opt_state(params)

        state_sh = None
        if self.mesh is not None:
            # params AND optimizer state are made in place on their shards,
            # never whole on one device first
            p_spec = steps.params_spec(cfg)
            state_sh = (msh.param_shardings(p_spec, self.mesh),
                        launch_shardings.opt_shardings(
                            steps.opt_state_spec(p_spec), p_spec, self.mesh,
                            zero1=cfg.zero1))
        with msh.use_mesh(self.mesh):
            with self.log.stage("tfjob:init"):
                params, opt = jax.jit(init, out_shardings=state_sh)(
                    jax.random.PRNGKey(self.seed))
                if resume_from and self.store:
                    params = self.store.load_tree(resume_from, params)
                    if self.store.exists(f"{resume_from}_opt"):
                        opt = self.store.load_tree(f"{resume_from}_opt", opt)
                    if state_sh is not None:
                        params, opt = jax.device_put((params, opt), state_sh)
                jstep = jax.jit(train_step, donate_argnums=(0, 1))
            data = token_data.lm_batches(cfg, self.batch_size, self.seq_len,
                                         seed=self.seed)
            history = []
            with self.log.stage("tfjob:train"):
                for i, batch in enumerate(data):
                    if i >= self.n_steps:
                        break
                    params, opt, metrics = jstep(params, opt, batch, i)
                    loss = float(metrics["loss"])
                    history.append(loss)
                    if report:
                        report(i + 1, loss)
            out = {"loss": history[-1] if history else float("nan"),
                   "history": history}
            if self.store and checkpoint_name:
                with self.log.stage("tfjob:checkpoint"):
                    out["checkpoint"] = self.store.save_tree(checkpoint_name, params,
                                                             meta={"loss": out["loss"]})
                    self.store.save_tree(f"{checkpoint_name}_opt", opt)
            out["params"] = params
        return out
