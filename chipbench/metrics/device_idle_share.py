"""One minus the union of device operation intervals over the traced
window, averaged over the chips."""
import devtrace


def read(run):
    share = devtrace.idle_share(run.trace)
    return None if share is None else 100.0 * share
