"""The program's loss and gradients on a 2 x 2 (data x model) mesh of four
CPU devices against the chip check's plain reference
(``chipbench/references/dense.py``), on the same seeded weights
(``chipbench/weights.py``): the dense block at tiny widths of
starcoder2-3b-15l's, GQA with 4 query heads to 2 KV heads (one a model
shard), a window shorter than the sequence, the embedding tied to the
head, StarCoder2's rotary base.  The attention runs through the Pallas
path in interpret mode, per shard.  Both sides run in float32 from the
seeded bf16 weights, so they part only by the order of their sums: the
loss within 1e-5 of itself, each leaf's gradient within 1e-4 of its
norm.  Runs in a subprocess: the
XLA device count is fixed at first JAX init."""
import json
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
import reference, weights
from references import dense
from repro.configs.base import ModelConfig, TrainConfig
from repro.kernels import dispatch
from repro.launch.mesh import make_plan_mesh
from repro.models import cross_entropy, forward
from repro.parallel import sharding as sh
from repro.parallel.act import activation_sharding

m = {"name": "tiny-starcoder2", "family": "dense", "num_layers": 2,
     "d_model": 128, "vocab_size": 512, "attention": "gqa", "num_heads": 4,
     "num_kv_heads": 2, "head_dim": 32, "rope_theta": 999999.4420358813,
     "sliding_window": 48, "d_ff": 512, "mlp_variant": "gelu",
     "norm_eps": 1e-05, "tie_embeddings": True, "dtype": "bfloat16"}
cfg = ModelConfig(**m)
mesh = make_plan_mesh(2, 2)
key = weights.seed_key(3000000017)
# the seeded bf16 weights, taken to float32 for both sides
params = jax.tree.map(lambda x: x.astype(jnp.float32),
                      weights.make_params(cfg, key))
tokens = jax.random.randint(jax.random.PRNGKey(5), (4, 128), 0, 512)


def program(params, tokens):
    with activation_sharding(mesh, cfg):
        logits, _, _ = forward(cfg, params, {"tokens": tokens}, remat=True)
        return cross_entropy(logits[:, :-1], tokens[:, 1:])


spec = sh.param_specs(cfg, params, mesh, zero_data=False)
put = lambda t, s: jax.device_put(t, jax.tree.map(
    lambda s: NamedSharding(mesh, s), s, is_leaf=lambda x: isinstance(x, P)))
with dispatch.force("pallas"):
    loss, grads = jax.jit(jax.value_and_grad(program))(
        put(params, spec), put(tokens, P("data", None)))
ein = reference.make_ein("f32")
with jax.default_matmul_precision("highest"):
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p, t: dense.loss(m, ein, p, t)))(params, tokens)
f64 = lambda x: np.asarray(x, np.float64)
print(json.dumps({
    "loss": float(loss), "want": float(want),
    "devices": len(jax.tree.leaves(grads)[0].sharding.device_set),
    "grads": {reference._names(grads)[i]: float(
        np.linalg.norm(f64(a) - f64(b)) / np.linalg.norm(f64(b)))
        for i, (a, b) in enumerate(zip(jax.tree.leaves(grads),
                                       jax.tree.leaves(want_g)))}}))
"""


def test_loss_and_grads_on_a_2x2_mesh_match_the_reference(tmp_path):
    script = tmp_path / "dense_reference_mesh.py"
    script.write_text(SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script),
                          os.path.join(ROOT, "chipbench")],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4
    assert abs(got["loss"] - got["want"]) <= 1e-5 * abs(got["want"]), got
    assert "embed" in got["grads"] and "lm_head" not in got["grads"]
    assert all(v <= 1e-4 for v in got["grads"].values()), got["grads"]
