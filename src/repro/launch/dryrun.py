import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# a CPU emulation of the mesh: never take an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape x mesh) combination: build the real
step function (train_step / prefill / serve_step), lower it against
ShapeDtypeStruct inputs with production shardings, ``.compile()`` it, and
record ``memory_analysis()`` + ``cost_analysis()`` + the HLO-derived
roofline terms (repro.launch.hlo_analysis) to a JSON cache.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import INPUT_SHAPES, TrainConfig
from repro.configs.registry import (ARCHS, ASSIGNED, get_arch, get_shape,
                                    shape_applicable)
from repro.core import memory_model as mm
from repro.core import memtrace
from repro.launch import hlo_analysis
from repro.launch.inputs import (batch_struct, decode_inputs,
                                 default_train_config, prefill_inputs,
                                 train_inputs)
from repro.launch.mesh import make_production_mesh
from repro.models import forward, decode_step
from repro.serve.engine import serve_step
from repro.train import build_train_step

DEFAULT_OUT = os.path.join(os.path.dirname(__file__),
                           "../../../experiments/dryrun")


def lower_combo(arch: str, shape_name: str, multi_pod: bool,
                tc: TrainConfig = None):
    """Build and lower the step for one combination.  Returns lowered."""
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.parallel import sharding as sh

    daxes = sh.data_axes(mesh)
    dax = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    nd = 1
    for a in daxes:
        nd *= mesh.shape[a]
    b_ok = shape.global_batch % max(nd, 1) == 0
    tp = mesh.shape.get("model", 1)
    v_ax = "model" if cfg.vocab_size % tp == 0 else None

    def logits_sharding(ndim):
        spec = [dax if b_ok else None] + [None] * (ndim - 2) + [v_ax]
        return NamedSharding(mesh, P(*spec))

    if shape.kind == "train":
        tc = tc or default_train_config(cfg, shape)
        (state_sds, batch_sds), (s_sh, b_sh) = train_inputs(
            cfg, shape, mesh, tc)
        step, n_micro = build_train_step(cfg, tc, mesh, shape.global_batch,
                                         shape.seq_len)
        metrics_sh = {"loss": NamedSharding(mesh, P()),
                      "grad_norm": NamedSharding(mesh, P())}
        lowered = jax.jit(step, in_shardings=(s_sh, b_sh),
                          out_shardings=(s_sh, metrics_sh),
                          donate_argnums=(0,)).lower(state_sds, batch_sds)
        meta = {"kind": "train", "zero": tc.zero, "n_micro": n_micro}
    elif shape.kind == "prefill":
        (p_sds, batch_sds), (p_sh, b_sh) = prefill_inputs(cfg, shape, mesh)

        from repro.parallel.act import activation_sharding

        def prefill_fn(params, batch):
            with activation_sharding(mesh, cfg):
                logits, _, caches = forward(cfg, params, batch,
                                            want_cache=True)
            return logits[:, -1, :], caches

        out_sds = jax.eval_shape(prefill_fn, p_sds, batch_sds)
        c_spec = sh.prefill_cache_specs(cfg, shape, mesh)
        cache_sh = {
            jname: {k: NamedSharding(mesh, sh.enforce_divisibility(
                c_spec[jname][k], tuple(leaf.shape), mesh))
                for k, leaf in sub.items()}
            for jname, sub in out_sds[1].items()}
        lowered = jax.jit(prefill_fn, in_shardings=(p_sh, b_sh),
                          out_shardings=(logits_sharding(2), cache_sh)
                          ).lower(p_sds, batch_sds)
        meta = {"kind": "prefill"}
    else:  # decode
        (p_sds, tok_sds, cache_sds, pos_sds), shardings = decode_inputs(
            cfg, shape, mesh)

        from repro.parallel.act import activation_sharding

        def decode_fn(params, tokens, cache, pos):
            with activation_sharding(mesh, cfg):
                return serve_step(cfg, params, tokens, cache, pos)

        cache_sh = shardings[2]
        lowered = jax.jit(decode_fn, in_shardings=shardings,
                          out_shardings=(logits_sharding(3), cache_sh),
                          donate_argnums=(2,)).lower(
            p_sds, tok_sds, cache_sds, pos_sds)
        meta = {"kind": "decode", "cache_len": shape.cache_len}
    return lowered, meta, mesh


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            force: bool = False, tag: str = "", tc: TrainConfig = None) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    key = f"{arch}__{shape_name}__{mesh_name}{tag}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, key + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "n_devices": 512 if multi_pod else 256, "ok": False}
    t0 = time.time()
    try:
        lowered, meta, mesh = lower_combo(arch, shape_name, multi_pod, tc)
        rec.update(meta)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        rec["bytes_per_device"] = mm.xla_peak_bytes(ma)
        if meta["kind"] == "train":
            # live-compile telemetry for the memory feedback plane: the
            # XLA accounting vs MARP's prediction for this (d, t)
            cfg = get_arch(arch)
            shape = get_shape(shape_name)
            t_deg = mesh.shape.get("model", 1)
            d_deg = max(mesh.devices.size // t_deg, 1)
            pred = mm.exact_peak_bytes(cfg, shape.global_batch,
                                       shape.seq_len, d_deg, t_deg,
                                       zero=meta["zero"])
            memtrace.record(cfg.family, meta["zero"], memtrace.ANY_DEVICE,
                            pred, rec["bytes_per_device"], source="xla")
            rec["pred_exact"] = pred
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        rec["xla_cost"] = {k: float(v) for k, v in ca.items()
                           if k in ("flops", "bytes accessed")}
        stats = hlo_analysis.analyze(compiled.as_text())
        rec["hlo"] = stats.to_json()
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — record failures, they are bugs
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 1)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = "OK " if rec["ok"] else "FAIL"
    print(f"[{status}] {key}: {rec.get('bytes_per_device', 0) / 2**30:.2f}"
          f" GiB/dev, {rec['total_s']}s"
          + ("" if rec["ok"] else f"  {rec.get('error', '')[:200]}"),
          flush=True)
    return rec


def all_combos():
    for arch in ASSIGNED:
        for shape_name in INPUT_SHAPES:
            if shape_applicable(arch, shape_name):
                yield arch, shape_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args()

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]
    n_fail = 0
    if args.all:
        for arch, shape_name in all_combos():
            for mp in meshes:
                rec = run_one(arch, shape_name, mp, args.out, args.force)
                n_fail += 0 if rec["ok"] else 1
    else:
        if not shape_applicable(args.arch, args.shape):
            print(f"[SKIP] {args.arch} x {args.shape}: not applicable"
                  " (DESIGN.md §5)")
            raise SystemExit(0)
        for mp in meshes:
            rec = run_one(args.arch, args.shape, mp, args.out, args.force)
            n_fail += 0 if rec["ok"] else 1
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
