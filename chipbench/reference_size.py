"""The plain reference's train step at a registry model's own shapes,
before the benchmark has a cell of that model.

    python chipbench/reference_size.py --arch starcoder2-3b --layers 15 \
        --seq 8192 --rows 4 --topology v5e:2x2
    python chipbench/reference_size.py --arch starcoder2-3b --layers 15 \
        --seq 8192 --rows 4 --chips 4 --seed 11

With ``--topology`` it compiles the step for a described TPU topology (no
chip needed; run it with ``JAX_PLATFORMS=cpu``) over its first
``--chips`` chips (all of them by default) and prints
``memory_analysis()``'s bytes a device.  Without, it runs the reference
on ``--chips`` attached chips as the check does, for one step (which
compiles), for one and for three, and prints the seconds a step from the
difference of the last two.
One JSON line goes to standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import harness  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import weights  # noqa: E402
from traffic import TokenStream  # noqa: E402

from repro.configs.registry import get_arch  # noqa: E402


def model_of(arch: str, layers: int):
    cfg = get_arch(arch).scaled(num_layers=layers)
    return cfg, {k: getattr(cfg, k) for k in cfg.__dataclass_fields__}


def traffic_of(seq: int, rows: int):
    t = dict(spec._load_json(os.path.join(spec.HERE, "traffic",
                                          "train.s1024.json")))
    t.update(seq_len=seq, global_batch=rows, reference_rows=rows)
    return t


def compiled_memory(cfg, m, t, devices) -> dict:
    """``memory_analysis()`` of the reference step over ``devices``."""
    family = spec.reference_family(m["family"])
    make = harness.reference_weights(cfg, family, devices)
    shapes = jax.eval_shape(make, jax.ShapeDtypeStruct((2,), jnp.uint32))
    shard = reference.placement(devices, shapes)
    p = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, jnp.float32, sharding=sh), shapes, shard)
    rows, seq = t["reference_rows"], t["seq_len"]
    step = reference.make_step(m, t, "f32", rows, family.loss, shard)
    toks = jax.ShapeDtypeStruct((t["global_batch"] // rows, rows, seq),
                                jnp.int32)
    with jax.default_matmul_precision("highest"):
        mem = step.lower(p, p, p, toks, 0.0).compile().memory_analysis()
    names = ("argument_size_in_bytes", "output_size_in_bytes",
             "alias_size_in_bytes", "temp_size_in_bytes",
             "generated_code_size_in_bytes")
    out = {k: int(getattr(mem, k)) for k in names}
    out["total_bytes"] = (out["argument_size_in_bytes"]
                          + out["output_size_in_bytes"]
                          - out["alias_size_in_bytes"]
                          + out["temp_size_in_bytes"])
    out["n_params"] = int(sum(np.prod(s.shape)
                              for s in jax.tree.leaves(shapes)))
    return out


def seconds_a_step(cfg, m, t, devices, seed: int) -> dict:
    """The reference run as the check runs it, for one step (compiling),
    then for one and for three; the seconds a step from the difference of
    the last two."""
    family = spec.reference_family(m["family"])
    key = weights.seed_key(seed)
    make = harness.reference_weights(cfg, family, devices)
    stream = TokenStream(t, cfg.vocab_size, seed)
    batches = [next(stream)["tokens"] for _ in range(3)]
    out = {}
    for tag, n in (("first", 1), ("one", 1), ("three", 3)):
        t0 = time.perf_counter()
        r = reference.readings(m, t, make, key, batches[:n],
                               family=family)
        out[f"seconds_{tag}"] = time.perf_counter() - t0
        out[f"loss_{tag}"] = r["loss"]
    out["seconds_a_step"] = (out["seconds_three"] - out["seconds_one"]) / 2
    stats = [d.memory_stats() or {} for d in devices]
    out["peak_bytes_in_use"] = max(s.get("peak_bytes_in_use", 0)
                                   for s in stats)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--layers", type=int, required=True)
    ap.add_argument("--seq", type=int, required=True)
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--topology")
    ap.add_argument("--chips", type=int)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    if not args.topology and not args.chips:
        ap.error("--chips is needed to run on attached chips")
    cfg, m = model_of(args.arch, args.layers)
    t = traffic_of(args.seq, args.rows)
    out = {"arch": args.arch, "layers": args.layers, "seq": args.seq,
           "rows": args.rows}
    if args.topology:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=args.topology)
        devices = list(topo.devices)[:args.chips]
        out["topology"] = args.topology
        out["chips"] = len(devices)
        out.update(compiled_memory(cfg, m, t, devices))
    else:
        harness.use_compile_cache()
        harness.check_device(args.chips)
        devices = jax.devices()[:args.chips]
        out["device"] = devices[0].device_kind
        out["chips"] = len(devices)
        out.update(seconds_a_step(cfg, m, t, devices, args.seed))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
