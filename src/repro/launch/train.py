"""End-to-end trainer: data pipeline -> sharded train step -> checkpoints.

Production behaviors wired in:
  * jit'd train step with full sharding trees (launch/steps.py),
  * async atomic checkpointing + keep-k GC + resume (checkpoint/),
  * deterministic resumable data stream (data/),
  * watchdog + retry-restore fault tolerance (distributed/fault_tolerance),
  * optional KLARAPTOR kernel tuning pass before the first step (builds
    drivers for the model's kernel shapes against the target device model).

CPU-scale usage (the end-to-end example trains ~100M params for a few
hundred steps):

    python -m repro.launch.train --arch llama3.2-1b --smoke \
        --steps 300 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.configs.base import ShapePreset
from repro.data import Prefetcher, SyntheticConfig, SyntheticStream
from repro.distributed import Watchdog, shardings_for_specs
from repro.launch.compile_cache import use_compile_cache
from repro.launch.steps import build_step
from repro.models import init_params
from repro.optim import AdamWConfig, adamw_init, warmup_cosine

__all__ = ["TrainLoop", "main"]


class TrainLoop:
    def __init__(self, cfg, preset: ShapePreset, mesh=None,
                 opt_cfg: AdamWConfig | None = None,
                 ckpt_dir: str | None = None, ckpt_every: int = 50,
                 keep: int = 3, seed: int = 0,
                 watchdog_timeout: float = 0.0):
        self.cfg = cfg
        self.preset = preset
        opt_cfg = opt_cfg or AdamWConfig(
            lr=warmup_cosine(5e-3, 10, 10_000), weight_decay=0.01)
        self.bundle = build_step(cfg, preset, mesh, opt_cfg=opt_cfg)
        self.opt_cfg = opt_cfg
        self.mesh = mesh
        self.step_fn = jax.jit(
            self.bundle.fn,
            in_shardings=self.bundle.in_shardings if mesh else None,
            out_shardings=self.bundle.out_shardings if mesh else None)
        self.manager = (CheckpointManager(ckpt_dir, keep=keep)
                        if ckpt_dir else None)
        self.ckpt_every = ckpt_every
        self.seed = seed
        self.watchdog = (Watchdog(watchdog_timeout).start()
                         if watchdog_timeout > 0 else None)
        self.stream = SyntheticStream(SyntheticConfig(
            vocab_size=cfg.vocab_size, seq_len=preset.seq_len,
            global_batch=preset.global_batch, seed=seed))
        self.prefetch = Prefetcher(self.stream)
        self.params = None
        self.opt_state = None
        self.step = 0

    # -- state ----------------------------------------------------------------
    def init_state(self) -> None:
        model = self.bundle.model
        self.params = init_params(model.specs(), jax.random.PRNGKey(self.seed))
        self.opt_state = adamw_init(self.opt_cfg, self.params)
        self.step = 0

    def restore_or_init(self) -> int:
        if self.manager is not None and self.manager.latest_step() is not None:
            model = self.bundle.model
            template = {
                "params": model.abstract_params(),
                "mu": jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(
                        s.shape, self.opt_cfg.state_dtype),
                    model.abstract_params()),
                "nu": jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(
                        s.shape, self.opt_cfg.state_dtype),
                    model.abstract_params()),
            }
            tree, aux, step = self.manager.restore(template)
            self.params = tree["params"]
            self.opt_state = {"mu": tree["mu"], "nu": tree["nu"],
                              "step": jnp.asarray(step, jnp.int32)}
            self.step = step
            self.stream.load_state_dict(aux["stream"])
            self.prefetch.load_state_dict(aux["prefetch"])
        else:
            self.init_state()
        return self.step

    def save(self, block: bool = False) -> None:
        if self.manager is None:
            return
        tree = {"params": self.params, "mu": self.opt_state["mu"],
                "nu": self.opt_state["nu"]}
        aux = {"stream": self.stream.state_dict(),
               "prefetch": self.prefetch.state_dict()}
        self.manager.save(self.step, tree, aux=aux, block=block)

    # -- loop -----------------------------------------------------------------
    def run(self, n_steps: int, log_every: int = 10,
            fail_at: int | None = None) -> list[dict]:
        """Run n_steps; ``fail_at`` injects a crash (fault-tolerance tests)."""
        history = []
        while self.step < n_steps:
            batch = self.prefetch.next_batch()
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            if self.cfg.arch_kind == "vlm":
                batch["patches"] = jnp.zeros(
                    (batch["tokens"].shape[0], self.cfg.num_patches,
                     self.cfg.d_model), self.cfg.dtype)
            elif self.cfg.arch_kind == "encdec":
                batch["frames"] = jnp.zeros(
                    (batch["tokens"].shape[0], self.cfg.encoder_seq,
                     self.cfg.d_model), self.cfg.dtype)
            t0 = time.perf_counter()
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
            self.step += 1
            if fail_at is not None and self.step == fail_at:
                raise RuntimeError("injected failure")
            if self.watchdog is not None:
                self.watchdog.beat()
            if self.step % log_every == 0 or self.step == n_steps:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = self.step
                m["step_time_s"] = time.perf_counter() - t0
                history.append(m)
            if self.manager is not None and self.step % self.ckpt_every == 0:
                self.save()
        if self.manager is not None:
            self.save(block=True)
            self.manager.wait()
        return history


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    preset = ShapePreset("cli", "train", args.seq, args.batch)
    loop = TrainLoop(cfg, preset, mesh=None, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every)
    loop.restore_or_init()
    hist = loop.run(args.steps, log_every=args.log_every)
    for m in hist:
        print(f"step {m['step']:5d}  loss {m['loss']:.4f}  "
              f"grad_norm {m.get('grad_norm', 0.0):.3f}  "
              f"{m['step_time_s'] * 1e3:.0f} ms")


if __name__ == "__main__":
    main()
