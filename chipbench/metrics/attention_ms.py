"""Device self time a step of the ops under the ``attention`` scope, in
ms: the forward kernel, its recomputation and the backward of the chunked
reference."""
import scopes


def read(run):
    return scopes.scope_ms(scopes.of(run), run.trace.window, run.steps,
                           "attention")
