"""One run of one training cell: set-up, the measured window, the check.

In order, in this one process:

1. the device: a TPU whose ``device_kind`` is in ``peaks.json``, with as
   many chips as the cell asks for (no fallback to the CPU);
2. the state: weights made on the device from the seed in one jitted
   call, already in the program's shardings (``train.state_specs``);
3. the step: ``train.build_train_step(jit=True)``, the donated jitted step
   ``launch.train`` drives, loaded from the persistent compile cache;
4. its first ``check_steps`` steps on the seed's first batches, which
   warm up the one shape the window uses and are kept for the check;
5. the window: whole steps on fresh batches made on the host while it
   runs, until ``--seconds`` have passed;
6. the peak device memory, then the program's state is freed and the plain
   reference, its state split over the cell's chips
   (``reference.placement``), runs the checked steps again; ``compare``
   decides ``correct``.

With ``--trace 1`` the window runs under the JAX profiler and the cell's
per-layer metrics are read from its trace instead of the end-to-end ones.
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import compare
import devtrace
import reference
import spec
import weights
from traffic import TokenStream

sys.path.insert(0, os.path.join(spec.ROOT, "src"))

from repro.configs.base import ModelConfig, TrainConfig  # noqa: E402
from repro.launch.mesh import make_plan_mesh  # noqa: E402
from repro.train import build_train_step, state_specs  # noqa: E402
from repro.train.optimizer import init_opt_state  # noqa: E402

CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")
TRACE_DIR = os.path.join(spec.ROOT, ".chipbench_trace")
GIB = 2 ** 30


class NoChip(RuntimeError):
    """No TPU, an unknown one, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """What a per-layer metric's reader sees."""
    model: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    peaks: Dict[str, Any]
    n_params: int
    steps: int
    trace: devtrace.Trace
    base: str


def use_compile_cache() -> str:
    """``$JAX_COMPILATION_CACHE_DIR``, which JAX reads itself, else a fixed
    directory in the checkout; every program is cached."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_device(chips: int) -> Dict[str, Any]:
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {d0.platform!r}")
    try:
        peaks = spec.peaks_for(d0.device_kind)
    except spec.SpecError as e:
        raise NoChip(str(e)) from None
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return peaks


def train_config(t: Dict[str, Any]) -> TrainConfig:
    return TrainConfig(
        global_batch=t["global_batch"], seq_len=t["seq_len"],
        microbatch=t["microbatch"], learning_rate=t["learning_rate"],
        weight_decay=t["weight_decay"], beta1=t["beta1"], beta2=t["beta2"],
        eps=t["eps"], warmup_steps=t["warmup_steps"],
        steps=t["total_steps"], zero=t["zero"], remat=t["remat"])


def reference_weights(cfg: ModelConfig, family, devices: List):
    """``key -> weights`` jitted: the benchmark's weights as the program
    holds them, made directly in the reference's placement over
    ``devices``."""
    make = partial(weights.make_params, cfg,
                   init=getattr(family, "INIT", None))
    shape = jax.eval_shape(make, jax.ShapeDtypeStruct((2,), jnp.uint32))
    return jax.jit(make, out_shardings=reference.placement(devices, shape))


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
        t_start: float, devices: Optional[List] = None,
        peaks: Optional[Dict] = None, log=print) -> Dict[str, Any]:
    """One run; returns the result line as a dict.  ``devices`` and
    ``peaks`` default to the checked TPU's."""
    phases = {"imports": time.time() - t_start}
    mark = time.perf_counter()

    def phase(name, *arrays):
        nonlocal mark
        jax.block_until_ready(arrays)
        now = time.perf_counter()
        phases[name] = now - mark
        mark = now

    use_compile_cache()
    if peaks is None:
        peaks = check_device(cell.chips)
    phase("device")
    devices = (devices or jax.devices())[:cell.chips]
    m, t = dict(cell.config["model"]), cell.traffic
    cfg, tc = ModelConfig(**m), train_config(t)
    d, tp = t["mesh"]
    mesh = make_plan_mesh(d, tp, devices)
    B, S = t["global_batch"], t["seq_len"]
    key = weights.seed_key(seed)
    rules = getattr(cell.family, "INIT", None)

    # -- set-up: state, step, the checked steps --------------------------
    def make_state(k):
        p = weights.make_params(cfg, k, rules)
        return {"params": p, "opt": init_opt_state(p),
                "step": jnp.zeros((), jnp.int32)}

    shape = jax.eval_shape(make_state, key)
    shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                         state_specs(cfg, tc, mesh, shape),
                         is_leaf=lambda x: isinstance(x, P))
    state = jax.jit(make_state, out_shardings=shard)(key)
    phase("weights", state)
    n_params = sum(math.prod(x.shape)
                   for x in jax.tree.leaves(shape["params"]))
    step, _ = build_train_step(cfg, tc, mesh, B, S, jit=True)
    batch_shard = NamedSharding(mesh, P("data", None))
    stream = TokenStream(t, cfg.vocab_size, seed)

    def put(raw):
        return {k: jax.device_put(v, batch_shard) for k, v in raw.items()}

    b1 = t["beta1"]
    grad_norms = jax.jit(lambda mom: reference.layer_norms(
        jax.tree.map(lambda x: x / (1.0 - b1), mom)))
    change_norms = jax.jit(lambda master, k: reference.layer_norms(
        jax.tree.map(lambda a, b: a - b.astype(jnp.float32), master,
                     weights.make_params(cfg, k, rules))))
    checked, prog = [], {"loss": []}
    for i in range(t["check_steps"]):
        raw = next(stream)
        if len(np.unique(raw["tokens"], axis=0)) != B:
            raise RuntimeError("the feed repeated a row in a checked batch")
        checked.append(raw["tokens"])
        state, met = step(state, put(raw))
        prog["loss"].append(float(met["loss"]))
        if i == 0:
            # the step loaded from the cache, and its first run
            phase("first_step")
            prog["grad_norms"] = reference.to_host(
                grad_norms(state["opt"]["m"]))
    prog["change_norms"] = reference.to_host(
        change_norms(state["opt"]["master"], key))
    phase("checked_steps")
    log(f"checked steps: losses {prog['loss']}")

    # -- the window --------------------------------------------------------
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    setup_s = time.time() - t_start
    log("set-up phases (s): " + ", ".join(f"{k} {v:.3f}"
                                          for k, v in phases.items()))
    losses, done = [], []
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench/traced"):
        # one step in flight behind the one waited on; stop dispatching
        # once the step in flight will end past ``seconds``, so the window
        # ends on the first step boundary after it
        while True:
            with jax.profiler.TraceAnnotation("chipbench/make_batch"):
                b = put(next(stream))
            with jax.profiler.TraceAnnotation("chipbench/dispatch"):
                state, met = step(state, b)
            losses.append(met["loss"])
            if len(losses) > 1:
                with jax.profiler.TraceAnnotation("chipbench/wait"):
                    losses[-2].block_until_ready()
                done.append(time.perf_counter() - t0)
                last = done[-1] - (done[-2] if len(done) > 1 else 0.0)
                if done[-1] + last >= seconds:
                    break
        with jax.profiler.TraceAnnotation("chipbench/wait"):
            jax.block_until_ready(state)
    window_s = time.perf_counter() - t0
    if traced:
        jax.profiler.stop_trace()
    n = len(losses)
    losses = [float(x) for x in losses]
    # the runtime reserves each program's temporaries apart from the
    # arrays it holds: a chip's peak is the peak of both.  Both are still
    # held here, with the state live and the step loaded, so the sum of
    # what is held now shows how close the two peaks come to one moment.
    stats = [dv.memory_stats() or {} for dv in devices]
    peak = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)
    held = max(s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0)
               for s in stats)
    log(f"memory_stats: {stats}")
    log(f"memory: peak in use + peak reserved {peak}, held now {held}")
    log(f"window: {n} steps in {window_s:.3f} s, last loss {losses[-1]}")

    # -- the check, with the program's state freed -------------------------
    del state, met, b
    gc.collect()
    init = reference_weights(cfg, cell.family, devices)
    t_ref = time.perf_counter()
    ref = reference.readings(m, t, init, key, checked, family=cell.family)
    log(f"reference: losses {ref['loss']}"
        f" ({time.perf_counter() - t_ref:.1f} s)")
    ok, checks = compare.verdict(compare.numbers(prog, ref), cell.limits)
    failed = sum(not np.isfinite(x) for x in losses + prog["loss"])
    checks["nonfinite_losses"] = {"value": failed, "limit": 0}
    correct = ok and failed == 0

    out = {"correct": bool(correct), "attempted": n + len(checked),
           "failed": int(failed)}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak)}
    if traced:
        tr = devtrace.load(TRACE_DIR)
        ctx = Run(model=m, traffic=t, chips=cell.chips, peaks=peaks,
                  n_params=n_params, steps=n, trace=tr, base=cell.base)
        metrics = {}
        for pm in cell.per_layer:
            v = spec.metric_reader(pm["name"], base=cell.base)(ctx)
            if v is not None:
                metrics[pm["name"]] = {"value": v, "unit": pm["unit"]}
        dev["busy_s"] = devtrace.busy_s(tr)
        dev["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": devtrace.top_ops(tr),
                            "idle_gaps": devtrace.idle_gaps(tr)}
    else:
        e2e = {"train_tokens_per_s": n * B * S / window_s,
               "peak_hbm_gib": peak / GIB, "setup_s": setup_s}
        metrics = {e["name"]: {"value": e2e[e["name"]], "unit": e["unit"]}
                   for e in cell.end_to_end}
    out["metrics"] = metrics
    out["device"] = dev
    out["checks"] = checks
    return out
