"""Mean milliseconds per batch the host blocked waiting for a device
value it was about to copy (``QueryProfile.stages["device_wait"]``,
summed over the batch's copies), over the window's batches."""


def read(ctx):
    ps = [p.stages["device_wait"] for p in ctx["profiles"]
          if "device_wait" in p.stages]
    return 1e3 * sum(ps) / len(ps) if ps else None
