"""End-to-end training driver.

Wires every substrate together: Lance-backed token loader (full-scan path),
model zoo, optimizer, sharded train_step, async checkpointing, heartbeat /
straggler monitoring and crash-restart with exact data-cursor resume.

On this CPU container it trains reduced configs on the host mesh; on a pod
it takes ``--mesh production``.  Example (the ~100M-scale run used by
examples/train_lm.py):

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
      --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, reduced_config
from ..data.loader import TokenLoader, write_token_file
from ..dist.checkpoint import Checkpointer
from ..dist.fault import DataCursor, HeartbeatMonitor, RestartPolicy, run_with_restarts
from ..dist.sharding import ShardingPolicy
from ..models.registry import build_model
from ..train.optimizer import make_optimizer
from ..train.train_loop import make_train_step
from .mesh import make_host_mesh, make_production_mesh


def train(arch: str, *, reduced: bool = True, steps: int = 50, batch: int = 8,
          seq: int = 128, ckpt_dir=None, ckpt_every: int = 25,
          mesh_kind: str = "host", microbatches: int = 1, lr: float = 3e-4,
          log_every: int = 10, inject_failure_at=None):
    cfg = reduced_config(arch) if reduced else get_config(arch)
    mesh = make_host_mesh() if mesh_kind == "host" else make_production_mesh()
    policy = ShardingPolicy(mesh, fsdp=False)
    model = build_model(cfg, mesh=mesh, batch_axes=policy.batch_axes(),
                        data_size=mesh.shape["data"], use_sharded_moe=False)

    with jax.set_mesh(mesh):
        params, specs = model.init(jax.random.PRNGKey(0))
        p_sh = policy.param_shardings(specs)
        params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, p_sh)
        opt = make_optimizer(cfg.optimizer, lr=lr)
        opt_state = opt.init(params)
        step_fn = jax.jit(make_train_step(model, opt, microbatches=microbatches),
                          donate_argnums=(0, 1))

        # data: a Lance-encoded token file (full-scan consumer)
        fbytes = write_token_file(n_rows=max(64, batch * 4), seq_len=seq,
                                  vocab=cfg.vocab, seed=0)
        loader = TokenLoader(fbytes, batch=batch, seq_len=seq, seed=0)

        ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
        start = 0
        if ckpt:
            restored, s = ckpt.restore_latest({"params": params, "opt": opt_state},
                                              {"params": p_sh, "opt": None})
            if restored is not None:
                params, opt_state = restored["params"], restored["opt"]
                start = s + 1
                print(f"[train] resumed from step {s}")

        hb = HeartbeatMonitor(on_straggler=lambda s, dt, med: print(
            f"[fault] step {s} straggled: {dt:.3f}s vs median {med:.3f}s"))
        state = {"params": params, "opt": opt_state, "loss": None,
                 "injected": False}

        def do_step(step: int):
            hb.start_step()
            batch_np = loader.batch_for_step(step)
            if (inject_failure_at is not None and step == inject_failure_at
                    and not state["injected"]):
                state["injected"] = True
                raise RuntimeError("injected failure (fault-tolerance test)")
            b = {k: jnp.asarray(v) for k, v in batch_np.items()}
            state["params"], state["opt"], metrics = step_fn(
                state["params"], state["opt"], b, jnp.int32(step))
            state["loss"] = float(metrics["loss"])
            dt = hb.end_step(step)
            if step % log_every == 0:
                print(f"[train] step {step} loss={state['loss']:.4f} {dt*1e3:.0f}ms",
                      flush=True)
            if ckpt and step and step % ckpt_every == 0:
                ckpt.save(step, {"params": state["params"], "opt": state["opt"]})

        def on_failure(e: Exception) -> int:
            print(f"[fault] step failed ({e}); restoring latest checkpoint")
            if ckpt:
                restored, s = ckpt.restore_latest({"params": state["params"],
                                                   "opt": state["opt"]},
                                                  {"params": p_sh, "opt": None})
                if restored is not None:
                    state["params"], state["opt"] = restored["params"], restored["opt"]
                    return s + 1
            return 0

        last = run_with_restarts(do_step, start_step=start, n_steps=steps - start,
                                 policy=RestartPolicy(), on_failure=on_failure)
        if ckpt:
            ckpt.save(last - 1, {"params": state["params"], "opt": state["opt"]},
                      blocking=True)
        loader.close()
        return state["loss"], last


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh", choices=["host", "production"], default="host")
    args = ap.parse_args()
    loss, last = train(args.arch, reduced=args.reduced, steps=args.steps,
                       batch=args.batch, seq=args.seq, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every, mesh_kind=args.mesh,
                       microbatches=args.microbatches, lr=args.lr)
    print(f"[train] done at step {last - 1}, final loss {loss:.4f}")


if __name__ == "__main__":
    main()
