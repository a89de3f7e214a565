"""Calls of the train step inside the traced window that compiled or
loaded a program (``repro/train_step`` spans with ``compiled=1``)."""
import scopes


def read(run):
    return scopes.compiles_in_window(scopes.of(run), run.trace.window)
