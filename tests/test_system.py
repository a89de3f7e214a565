"""End-to-end behaviour tests: serverless submit -> train -> loss falls;
data pipeline; checkpointing; hlo analyzer; train/serve drivers."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.configs.registry import smoke_config
from repro.data import SyntheticTokens
from repro import ckpt as ckpt_mod


def test_end_to_end_training_loss_falls(tmp_path):
    from repro.launch.train import main as train_main
    losses = train_main(["--arch", "mamba2-130m", "--smoke", "--steps", "12",
                         "--batch", "4", "--seq", "128",
                         "--ckpt-dir", str(tmp_path), "--log-every", "100"])
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    assert ckpt_mod.latest_step(str(tmp_path)) == 12


def test_serve_driver():
    from repro.launch.serve import main as serve_main
    toks = serve_main(["--arch", "llama3.2-3b", "--smoke", "--batch", "2",
                       "--prompt-len", "16", "--gen", "4"])
    assert toks.shape == (2, 4)


def test_submit_driver():
    from repro.launch.submit import main as submit_main
    results = submit_main(["--arch", "gpt2-350m", "--arch", "gpt2-7b",
                           "--batch", "16", "--seq", "1024",
                           "--cluster", "paper-sim"])
    assert all(r.started for r in results)


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The entry points' compile cache: a fixed directory in the checkout,
    unless JAX_COMPILATION_CACHE_DIR places it (JAX then reads it itself
    and the helper sets nothing)."""
    from repro.launch import compile_cache as cc
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
        assert cc.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(cc.ENV_VAR)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cc.use_compile_cache() == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_data_pipeline_shapes_and_determinism():
    cfg = smoke_config("llava-next-34b")
    d1 = iter(SyntheticTokens(cfg, 4, 32 + cfg.num_modal_tokens, seed=7))
    d2 = iter(SyntheticTokens(cfg, 4, 32 + cfg.num_modal_tokens, seed=7))
    b1, b2 = next(d1), next(d2)
    assert b1["tokens"].shape == (4, 32)
    assert b1["labels"].shape == (4, 32 + cfg.num_modal_tokens)
    assert b1["modal_embeds"].shape == (4, cfg.num_modal_tokens, cfg.d_model)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert (b1["tokens"] < cfg.vocab_size).all()


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    ckpt_mod.save(str(tmp_path), 3, tree)
    assert ckpt_mod.latest_step(str(tmp_path)) == 3
    like = jax.tree.map(jnp.zeros_like, tree)
    back = ckpt_mod.restore(str(tmp_path), 3, like)
    np.testing.assert_array_equal(np.asarray(back["a"]), np.asarray(tree["a"]))
    assert back["b"]["c"].dtype == jnp.bfloat16


def test_hlo_analyzer_counts_loops_and_collectives():
    """The analyzer must multiply while-body costs by the trip count."""
    from repro.launch import hlo_analysis

    def step(w, x):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    n_iter, m = 48, 128
    w = jnp.zeros((n_iter, m, m), jnp.float32)
    x = jnp.zeros((8, m), jnp.float32)
    txt = jax.jit(step).lower(w, x).compile().as_text()
    stats = hlo_analysis.analyze(txt)
    want_flops = 2 * 8 * m * m * n_iter
    assert 0.8 * want_flops < stats.flops < 1.3 * want_flops
    # loop state must be re-read every iteration
    assert stats.hbm_bytes > n_iter * m * m * 4


def test_hlo_analyzer_dot_shapes():
    from repro.launch import hlo_analysis
    a = jnp.zeros((64, 128), jnp.float32)
    b = jnp.zeros((128, 32), jnp.float32)
    txt = jax.jit(lambda a, b: a @ b).lower(a, b).compile().as_text()
    stats = hlo_analysis.analyze(txt)
    assert stats.flops == 2 * 64 * 128 * 32


def test_lr_schedule():
    from repro.train import lr_at
    tc = TrainConfig(learning_rate=1e-3, warmup_steps=10, steps=100)
    assert float(lr_at(tc, jnp.int32(0))) == pytest.approx(1e-4, rel=1e-3)
    assert float(lr_at(tc, jnp.int32(10))) == pytest.approx(1e-3, rel=1e-3)
    assert float(lr_at(tc, jnp.int32(100))) == pytest.approx(1e-4, rel=1e-2)
