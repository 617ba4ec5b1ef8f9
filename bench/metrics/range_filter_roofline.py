"""Share of its roofline that the fused ``range_filter`` kernel reached
in the window: the least time its calls could take on this chip (bytes
and operations from their shapes, ``limsbench.roofline``) over the
device time of its events in the profiler trace."""
from limsbench import roofline


def read(ctx):
    if ctx["peak"] is None:
        return None
    return roofline.share("range_filter", ctx["calls"]["range_filter"],
                          ctx["trace"]["kernel_s"]["range_filter"],
                          ctx["peak"])
