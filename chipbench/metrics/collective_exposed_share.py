"""Share of the traced window in which a chip runs a collective and no
other operation, averaged over the chips: an async pair covers the time
from its start's begin to its done's end (``collectives.py``)."""
import collectives


def read(run):
    return collectives.exposed_share(run.trace)
