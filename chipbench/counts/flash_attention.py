"""Work of the flash attention forward kernel: every call is one
micro-batch of one layer on one chip, over the causal or window band."""
import work


def work_of(run, n_events: int):
    H, K, hd = work.heads_per_shard(run.model, run.traffic)
    w = work.attention_fwd(run.traffic["microbatch"], run.traffic["seq_len"],
                           H, K, hd, run.model["sliding_window"])
    return {k: v * n_events for k, v in w.items()}
