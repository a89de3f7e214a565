"""Work of the fused Adam kernel: every parameter once a step, however the
leaves and their shards split into calls."""
import work


def work_of(run, n_events: int):
    return work.adam(run.n_params * run.steps)
