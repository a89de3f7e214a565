"""Device self time a step of the forward run again by remat inside the
backward (ops under ``rematted_computation``), in ms."""
import scopes


def read(run):
    return scopes.phase_ms(scopes.of(run), run.trace.window, run.steps,
                           "recompute")
