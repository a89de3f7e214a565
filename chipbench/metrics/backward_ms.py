"""Device self time a step of the backward and the f32 gradient sum
(ops under ``transpose(jvp(model))`` outside remat, and under
``grad_accum``), in ms."""
import scopes


def read(run):
    return scopes.phase_ms(scopes.of(run), run.trace.window, run.steps,
                           "backward")
