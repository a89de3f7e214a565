"""The reference step of the next four-chip configuration fits a v5e chip:
StarCoder2-3B (the registry's, untied head) at 15 of its 30 layers, rows
of 8,192 tokens in blocks of 4, compiled for a described ``v5e:2x2``
with its state split over the four chips.  Nothing runs; no chip is
needed.  About a minute and a half.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library.
"""
from __future__ import annotations

import os

import jax
import pytest

import tiny  # noqa: F401  (puts the benchmark's directory on the path)

import reference_size

GIB = 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


def test_starcoder2_3b_15_layers_at_8192_fits_four_v5e_chips(topo):
    cfg, m = reference_size.model_of("starcoder2-3b", 15)
    t = reference_size.traffic_of(8192, 4)
    mem = reference_size.compiled_memory(cfg, m, t, list(topo.devices))
    assert mem["n_params"] == 1_741_255_680
    assert mem["total_bytes"] <= 14 * GIB, mem
