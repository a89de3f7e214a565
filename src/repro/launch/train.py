"""End-to-end training driver (runs on the local devices; the serverless
path sizes the mesh via MARP).

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --smoke \
        --steps 20 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig
from repro.configs.registry import get_arch, smoke_config
from repro.core import memory_model as mm
from repro.core import memtrace
from repro.data import SyntheticTokens
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_plan_mesh
from repro.obs import device as obs_device
from repro.train import build_train_step, make_train_state, state_specs
from repro import ckpt as ckpt_mod
from jax.sharding import NamedSharding, PartitionSpec as P


def record_compile_telemetry(step_jit, state, batch, cfg, tc, d: int,
                             t: int) -> None:
    """AOT-compile the jitted step and feed its XLA memory accounting into
    the memory feedback plane (``core.memtrace``) — the live-compile
    telemetry source.  The jitted step reuses this executable on its first
    call (one compile, not two).  A failed compile fails the run; a backend
    without ``memory_analysis`` is reported."""
    compiled = step_jit.lower(state, batch).compile()
    ma = compiled.memory_analysis()
    if ma is None:
        print("memtrace: this backend reports no memory_analysis",
              flush=True)
        return
    observed = mm.xla_peak_bytes(ma)
    pred = mm.exact_peak_bytes(cfg, tc.global_batch, tc.seq_len, d, t,
                               zero=tc.zero, microbatch=tc.microbatch)
    dev_type = memtrace.device_type_for(jax.devices()[0].device_kind)
    memtrace.record(cfg.family, tc.zero, dev_type, pred, observed,
                    source="xla")
    print(f"memtrace: observed peak {observed / 2**30:.2f} GiB vs"
          f" predicted {pred / 2**30:.2f} GiB"
          f" ({dev_type}, zero={tc.zero})", flush=True)


def run(cfg, tc: TrainConfig, mesh, *, log_every: int = 10):
    """Train ``tc.steps`` steps of ``cfg`` on ``mesh`` from the seed's
    weights and data.  Returns (losses, final state)."""
    d, t = mesh.shape["data"], mesh.shape["model"]
    batch, seq = tc.global_batch, tc.seq_len
    state = make_train_state(cfg, tc, jax.random.PRNGKey(tc.seed))
    sspec = state_specs(cfg, tc, mesh, state)
    s_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), sspec,
                        is_leaf=lambda x: isinstance(x, P))
    state = jax.device_put(state, s_sh)
    step_jit, _ = build_train_step(cfg, tc, mesh, batch, seq, jit=True)

    it = iter(SyntheticTokens(cfg, batch, seq, seed=tc.seed))

    def prep():
        with obs_device.span(obs_device.DATA):
            return {k: jnp.asarray(v) for k, v in next(it).items()
                    if k in ("tokens", "labels", "modal_embeds")}

    # one AOT compile: the jitted step runs it in the loop below, and its
    # observed peak memory feeds the feedback plane (batch shapes are
    # static, so one executable serves every step)
    first = prep()
    record_compile_telemetry(step_jit, state, first, cfg, tc, d, t)
    losses = []
    t0 = time.time()
    for i in range(tc.steps):
        state, metrics = step_jit(state, first if i == 0 else prep())
        losses.append(float(metrics["loss"]))
        if i % log_every == 0 or i == tc.steps - 1:
            dt = time.time() - t0
            print(f"step {i:5d} loss {losses[-1]:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"({dt:.1f}s)", flush=True)
    return losses, state


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config of the same family")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--zero", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    tc = TrainConfig(global_batch=args.batch, seq_len=args.seq,
                     microbatch=args.microbatch, learning_rate=args.lr,
                     steps=args.steps, warmup_steps=max(args.steps // 10, 1),
                     zero=args.zero)

    # serverless mesh sizing: all local devices, data-parallel by default
    n_dev = jax.device_count()
    d = min(n_dev, args.batch)
    t = max(n_dev // d, 1)
    mesh = make_plan_mesh(d, t)
    print(f"arch={cfg.name} params on mesh d={d} t={t} "
          f"(devices={n_dev})", flush=True)
    losses, state = run(cfg, tc, mesh, log_every=args.log_every)
    if args.ckpt_dir:
        ckpt_mod.save(args.ckpt_dir, args.steps, state["params"])
        print(f"checkpoint saved to {args.ckpt_dir}")
    first10, last10 = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"first-10-mean {first10:.4f} last-10-mean {last10:.4f}")
    if not last10 < first10:
        raise RuntimeError(f"loss did not fall: {first10} -> {last10}")
    return losses


if __name__ == "__main__":
    main()
