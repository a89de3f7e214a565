"""Device self time a step of the forward and loss, run once (ops
under ``jvp(model)``), in ms."""
import scopes


def read(run):
    return scopes.phase_ms(scopes.of(run), run.trace.window, run.steps,
                           "forward")
