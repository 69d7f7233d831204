"""Host time per microbatch of the window: the time of the service's
microbatch outside its waits for the device (the service's ``host_s`` and
``microbatches`` counters, taken where the ``svc.microbatch`` and
``svc.wait`` spans open and close)."""


def read(run):
    c = run["window"]["counters"]
    if not c.get("microbatches"):
        return None
    return 1e3 * c["host_s"] / c["microbatches"]
