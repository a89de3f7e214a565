"""Device self time a step of the exchanges between chips (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all, by the HLO
opcode in the event's text), in ms, averaged over the chips."""
import collectives


def read(run):
    return collectives.self_ms(run.trace, run.steps)
