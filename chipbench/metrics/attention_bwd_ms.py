"""Device self time a step of the ops under the ``attention_bwd`` scope,
in ms: the backward of the attention kernel's ``custom_vjp``, the VJP of
the chunked reference recomputed from q, k and v."""
import scopes


def read(run):
    return scopes.scope_ms(scopes.of(run), run.trace.window, run.steps,
                           "attention_bwd")
