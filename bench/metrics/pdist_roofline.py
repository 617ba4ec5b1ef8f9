"""Share of its roofline that the ``pdist`` kernel reached in the
window: the least time its calls could take on this chip (bytes and
operations from their shapes, ``limsbench.roofline``) over the device
time of its events in the profiler trace.  The byte roof bounds it."""
from limsbench import roofline


def read(ctx):
    if ctx["peak"] is None:
        return None
    return roofline.share("pdist", ctx["calls"]["pdist"],
                          ctx["trace"]["kernel_s"]["pdist"], ctx["peak"])
