"""Model FLOPs of the traced steps over traced time x chips x bf16 peak."""
import work


def read(run):
    flops = work.model_flops_per_step(run.model, run.traffic, run.n_params)
    return 100.0 * flops * run.steps / (
        run.trace.window_s * run.chips * run.peaks["bf16_flops"])
