"""Share of the flash_attention kernel's roofline: the least time its work needs at
the chip's peaks over the time its trace events took."""
import roofline


def read(run):
    return roofline.share(run, "flash_attention")
