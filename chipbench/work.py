"""Operations and bytes a training step needs, from the problem's shapes.

These count the work the model's mathematics requires, never what an
implementation happens to do: attention over the causal (or sliding
window) band only, no recomputation.  So every implementation is read
against the same work.
"""
from __future__ import annotations

from typing import Dict


def band_pairs(seq: int, window: int = 0) -> int:
    """(query, key) pairs a causal mask, within ``window`` if set, keeps."""
    w = window if window and window < seq else seq
    return w * (w + 1) // 2 + (seq - w) * w


def attention_fwd(b: int, s: int, heads: int, kv_heads: int, hd: int,
                  window: int = 0) -> Dict[str, float]:
    """Scores and value products over the band; reads q, k, v and writes
    the output in bf16."""
    flops = 4.0 * hd * band_pairs(s, window) * b * heads
    nbytes = 2.0 * b * s * hd * (2 * heads + 2 * kv_heads)
    return {"flops": flops, "bytes": nbytes}


def adam(params: int) -> Dict[str, float]:
    """Reads g, m, v, master in f32 and writes m, v, master in f32 and the
    parameter in bf16: 30 bytes a parameter, about 10 operations."""
    return {"flops": 10.0 * params, "bytes": 30.0 * params}


def roofline_s(w: Dict[str, float], peaks: Dict) -> float:
    """Least time the chip could take for ``w``."""
    return max(w["flops"] / peaks["bf16_flops"], w["bytes"] / peaks["hbm_bw"])


def heads_per_shard(model: Dict, traffic: Dict):
    """(query heads, KV heads, head size) one chip's kernel call sees."""
    tp = traffic["mesh"][1]
    H, K = model["num_heads"], model["num_kv_heads"]
    hd = model["head_dim"] or model["d_model"] // H
    return H // tp if H % tp == 0 else H, max(K // tp, 1), hd


def model_flops_per_step(model: Dict, traffic: Dict, n_params: int) -> float:
    """6 x matmul parameters x tokens, plus three times the forward's
    attention band work (forward and backward), over the whole global
    batch.  The embedding counts once: as the head's matmul when
    tied, and not for the lookup when a separate head exists."""
    b, s = traffic["global_batch"], traffic["seq_len"]
    n = n_params - (0 if model["tie_embeddings"]
                    else model["vocab_size"] * model["d_model"])
    flops = 6.0 * n * b * s
    H = model["num_heads"]
    hd = model["head_dim"] or model["d_model"] // H
    return flops + 3 * model["num_layers"] * attention_fwd(
        b, s, H, model["num_kv_heads"], hd, model["sliding_window"])["flops"]
