"""Device self time a step of the update: gradient mean and norm, Adam
and the cast back to bf16 (ops under ``optimizer``), in ms."""
import scopes


def read(run):
    return scopes.phase_ms(scopes.of(run), run.trace.window, run.steps,
                           "optimizer")
