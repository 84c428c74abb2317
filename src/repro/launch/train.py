"""End-to-end training driver: data -> sharded train_step -> checkpoints.

Fault-tolerance contract (exercised by tests/test_fault_tolerance.py):
  * auto-resume: on start, restore the latest valid checkpoint if present
    (atomic tmp+rename writes mean a crash mid-save can't corrupt it);
  * elastic restart: the checkpoint stores plain host arrays; restoring onto
    a different mesh (e.g. 2 pods -> 1) just device_puts with the new specs;
  * exact replay: the data stream is a pure function of (seed, step), so a
    restarted run recomputes the same batches — continuation is bit-identical
    on CPU (test-asserted) and numerically equivalent on TPU;
  * straggler / dead-node handling at this layer: SPMD steps are bulk-
    synchronous, so the launcher watches a heartbeat (wall-time per step);
    on breach it aborts and the wrapper restarts from the last checkpoint —
    simulated in tests by killing the loop mid-run.

Usage (small-scale, real compute on host devices):
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --steps 50 \
      --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.checkpoint import ckpt as ckptlib
from repro.data import synthetic
from repro.launch import mesh as meshlib
from repro.launch import steps as steplib
from repro.models import registry as R
from repro.optim import adamw, grad_compress


@dataclasses.dataclass
class TrainRun:
    cfg: object
    opt_cfg: adamw.AdamWConfig
    mesh: object
    global_batch: int
    seq: int
    seed: int = 0
    ckpt_dir: str | None = None
    ckpt_every: int = 25
    compress_grads: bool = False
    heartbeat_s: float = 0.0  # 0 = disabled; else max seconds per step
    total_steps: int = 10_000  # schedule horizon (warmup = total/10, cap 500)

    def __post_init__(self):
        self.step_fn = steplib.build_train_step(
            self.cfg, self.opt_cfg, compress=self.compress_grads,
            total_steps=self.total_steps)
        self.jit_step = jax.jit(self.step_fn, donate_argnums=(0, 1))
        self.ckptr = (ckptlib.AsyncCheckpointer(self.ckpt_dir)
                      if self.ckpt_dir else None)

    def init_state(self):
        params = R.init_params(self.cfg, jax.random.PRNGKey(self.seed))
        opt_state = adamw.init(params, self.opt_cfg)
        ebuf = grad_compress.init_error_buf(params) if self.compress_grads else None
        return params, opt_state, ebuf, 0

    def restore_or_init(self):
        if self.ckpt_dir:
            last = ckptlib.latest_step(self.ckpt_dir)
            if last is not None:
                params, opt_state, ebuf, _ = self.init_state()
                tree = {"params": params, "opt": opt_state}
                if self.compress_grads:
                    tree["ebuf"] = ebuf
                restored, manifest = ckptlib.restore(self.ckpt_dir, last, tree)
                # host arrays -> device (donation requires jax.Array)
                restored = jax.tree.map(jax.numpy.asarray, restored)
                print(f"[train] resumed from step {last}")
                return (restored["params"], restored["opt"],
                        restored.get("ebuf"), last)
        return self.init_state()

    def batch_at(self, step: int):
        b = synthetic.batch_for(self.cfg, step, global_batch=self.global_batch,
                                seq=self.seq, seed=self.seed)
        return jax.tree.map(jax.numpy.asarray, b)

    def run(self, steps: int, log_every: int = 10, abort_at: int | None = None):
        """Train `steps` more steps. `abort_at` simulates a node failure."""
        params, opt_state, ebuf, start = self.restore_or_init()
        history = []
        try:
            return self._loop(params, opt_state, ebuf, start, steps,
                              log_every, abort_at, history)
        finally:
            # Drain the async writer even on (simulated) failure: the atomic
            # rename contract plus this drain is what restart relies on.
            if self.ckptr:
                self.ckptr.wait()

    def _loop(self, params, opt_state, ebuf, start, steps, log_every,
              abort_at, history):
        with jax.set_mesh(self.mesh):
            for step in range(start, start + steps):
                if abort_at is not None and step >= abort_at:
                    raise RuntimeError(f"simulated node failure at step {step}")
                t0 = time.time()
                batch = self.batch_at(step)
                if self.compress_grads:
                    params, opt_state, ebuf, metrics = self.jit_step(
                        params, opt_state, batch, ebuf)
                else:
                    params, opt_state, metrics = self.jit_step(
                        params, opt_state, batch)
                dt = time.time() - t0
                if self.heartbeat_s and dt > self.heartbeat_s and step > start:
                    raise RuntimeError(
                        f"straggler heartbeat breach: step took {dt:.1f}s")
                loss = float(metrics["loss"])
                history.append(loss)
                if log_every and (step + 1) % log_every == 0:
                    print(f"[train] step {step+1} loss {loss:.4f} ({dt:.2f}s)",
                          flush=True)
                if self.ckptr and (step + 1) % self.ckpt_every == 0:
                    tree = {"params": params, "opt": opt_state}
                    if self.compress_grads:
                        tree["ebuf"] = ebuf
                    self.ckptr.save(step + 1, tree)
        if self.ckptr:
            self.ckptr.wait()
        return params, opt_state, history


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()

    spec = R.get(args.arch)
    cfg = dataclasses.replace(spec.smoke, microbatches=1)
    run = TrainRun(
        cfg=cfg,
        opt_cfg=adamw.AdamWConfig(lr=args.lr),
        mesh=meshlib.make_host_mesh(),
        global_batch=args.batch,
        seq=args.seq,
        ckpt_dir=args.ckpt_dir,
        compress_grads=args.compress_grads,
    )
    _, _, hist = run.run(args.steps)
    print(f"[train] loss {hist[0]:.4f} -> {hist[-1]:.4f}")


if __name__ == "__main__":
    main()
