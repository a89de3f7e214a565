import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
# a CPU emulation of the mesh: never take an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"

"""Fig 6 reproduction: MARP peak-memory prediction vs XLA's own accounting.

Lowers the real train step for GPT2-350M / GPT2-7B (the paper's models)
under several (d, t) parallelisations and batch sizes on a (d, t) mesh of
placeholder devices, and compares ``compiled.memory_analysis()`` (ground
truth — the Megatron-measurement stand-in, DESIGN.md §3) against MARP's
exact-mode prediction and the paper's closed formula.
"""
import argparse
import json

import jax

from repro.configs.base import TrainConfig
from repro.configs.registry import get_arch
from repro.core import memory_model as mm
from repro.core import memtrace
from repro.launch.inputs import train_inputs
from repro.launch.mesh import make_plan_mesh
from repro.train import build_train_step
from repro.configs.base import ShapeConfig

DEFAULT_OUT = os.path.join(os.path.dirname(__file__),
                           "../../../experiments/memcheck")

# (arch, global_batch, seq, d, t) — the paper sweeps batch sizes and (d, t)
COMBOS = [
    ("gpt2-350m", 8, 1024, 1, 1),
    ("gpt2-350m", 8, 1024, 2, 1),
    ("gpt2-350m", 8, 1024, 4, 1),
    ("gpt2-350m", 16, 1024, 4, 2),
    ("gpt2-350m", 16, 1024, 2, 4),
    ("gpt2-7b", 2, 1024, 1, 4),
    ("gpt2-7b", 2, 1024, 2, 4),
    ("gpt2-7b", 2, 1024, 2, 8),
    ("gpt2-7b", 4, 1024, 4, 4),
    ("gpt2-7b", 8, 1024, 8, 2),
]


def run_one(arch, batch, seq, d, t, zero=0):
    cfg = get_arch(arch)
    mesh = make_plan_mesh(d, t)
    shape = ShapeConfig(f"mem_{batch}x{seq}", seq, batch, "train")
    tc = TrainConfig(global_batch=batch, seq_len=seq, microbatch=1,
                     zero=zero)
    (state_sds, batch_sds), (s_sh, b_sh) = train_inputs(cfg, shape, mesh, tc)
    step, n_micro = build_train_step(cfg, tc, mesh, batch, seq)
    compiled = jax.jit(step, in_shardings=(s_sh, b_sh),
                       donate_argnums=(0,)).lower(state_sds,
                                                  batch_sds).compile()
    actual = mm.xla_peak_bytes(compiled.memory_analysis())
    pred_exact = mm.exact_peak_bytes(cfg, batch, seq, d, t, zero=zero,
                                     microbatch=1)
    pred_paper = mm.paper_peak_bytes(cfg, batch, seq, d, t)
    # offline measured source for the memory feedback plane (the committed
    # JSONs seed it at import; in-process runs feed it directly)
    memtrace.record(cfg.family, zero, memtrace.ANY_DEVICE, pred_exact,
                    actual, source="memcheck")
    return {"arch": arch, "batch": batch, "seq": seq, "d": d, "t": t,
            "zero": zero, "actual_bytes": int(actual),
            "pred_exact": pred_exact, "pred_paper": pred_paper,
            "acc_exact": round(1 - abs(pred_exact - actual) / actual, 4),
            "acc_paper": round(1 - abs(pred_paper - actual) / actual, 4)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--zero", type=int, default=0)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"memcheck_zero{args.zero}.json")
    if os.path.exists(path) and not args.force:
        print(f"cached: {path}")
        return
    rows = []
    for arch, batch, seq, d, t in COMBOS:
        r = run_one(arch, batch, seq, d, t, args.zero)
        rows.append(r)
        print(f"{arch} b={batch} d={d} t={t}: actual"
              f" {r['actual_bytes'] / 2**30:.2f} GiB, exact-pred"
              f" {r['pred_exact'] / 2**30:.2f} ({r['acc_exact']:.1%}),"
              f" paper-pred {r['pred_paper'] / 2**30:.2f}"
              f" ({r['acc_paper']:.1%})", flush=True)
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
