"""Share of the adam_update kernel's roofline: the least time its work needs at
the chip's peaks over the time its trace events took."""
import roofline


def read(run):
    return roofline.share(run, "adam_update")
