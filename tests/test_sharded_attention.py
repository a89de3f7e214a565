"""The attention op on a 2 x 2 (data x model) mesh of four CPU devices,
through the Pallas path (interpret mode), at a length that takes the
chunked reference's scan branch (8,192 tokens): GQA with one KV head a
model shard and a sliding window.  Each shard's forward kernel and its
backward kernels run inside ``per_shard``'s map, and nothing traced there
may constrain the global mesh.  The value and the q, k, v gradients must
equal the unsharded op's, and lie within bf16 rounding of the chunked
reference's.  Runs in a subprocess: the XLA device count is fixed at
first JAX init."""
import json
import os
import subprocess
import sys

import numpy as np

SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ModelConfig
from repro.kernels import dispatch
from repro.launch.mesh import make_plan_mesh
from repro.models.attention import chunked_attention
from repro.parallel.act import activation_sharding

S, H, K, D, W = 8192, 4, 2, 32, 1024
cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=H * D,
                  vocab_size=64, num_heads=H, num_kv_heads=K, head_dim=D,
                  sliding_window=W)
mesh = make_plan_mesh(2, 2)
ks = jax.random.split(jax.random.PRNGKey(0), 4)
q = jax.random.normal(ks[0], (2, S, H, D), jnp.bfloat16)
k = jax.random.normal(ks[1], (2, S, K, D), jnp.bfloat16)
v = jax.random.normal(ks[2], (2, S, K, D), jnp.bfloat16)
w = jax.random.normal(ks[3], (2, S, H, D), jnp.float32)


def of(attend):
    def f(q, k, v):
        o = attend(q, k, v)
        return (o.astype(jnp.float32) * w).sum(), o
    return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)


def sharded(q, k, v):
    with activation_sharding(mesh, cfg):
        return of(lambda *a: dispatch.attention(*a, window=W))(q, k, v)


with dispatch.force("pallas"):
    (_, o_sh), g_sh = jax.jit(sharded)(q, k, v)
    (_, o_one), g_one = jax.jit(of(lambda *a: dispatch.attention(
        *a, window=W)))(q, k, v)
(_, o_ref), g_ref = jax.jit(of(lambda *a: chunked_attention(
    *a, window=W)))(q, k, v)

f32 = lambda x: np.asarray(x, np.float32)
rel = lambda a, b: float(np.max(np.abs(f32(a) - f32(b)))
                         / np.max(np.abs(f32(b))))
print(json.dumps({
    "devices": len(o_sh.sharding.device_set),
    "kernel_sharded_vs_one": rel(o_sh, o_one),
    "grads_sharded_vs_one": [rel(a, b) for a, b in zip(g_sh, g_one)],
    "value_vs_ref": rel(o_sh, o_ref),
    "grads_vs_ref": [rel(a, b) for a, b in zip(g_sh, g_ref)],
}))
"""


def test_attention_on_a_2x2_mesh_equals_the_unsharded_op(tmp_path):
    script = tmp_path / "sharded_attention.py"
    script.write_text(SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4
    # one kernel per (batch, head) shard computes what it computes whole
    assert got["kernel_sharded_vs_one"] == 0.0, got
    # the kernel and the chunked reference both round a float32 result to
    # bf16 once, from sums taken in another order: one bf16 step apart at
    # most, 2^-7 of the largest value's binade
    assert got["value_vs_ref"] <= 2.0 ** -7, got
    # the backward kernels per shard compute what they compute whole, and
    # within the bf16 tolerance of the kernel tests of the reference's VJP
    # (P and dS enter the MXU in bf16 in both, in another order)
    np.testing.assert_array_equal(got["grads_sharded_vs_one"],
                                  [0.0, 0.0, 0.0])
    assert max(got["grads_vs_ref"]) <= 2e-2, got
