"""Share of the window the host spent drawing the next batch and putting
it on the device (harness span around next(batch) + device_put)."""


def read(ctx):
    v = ctx.get("data_s")
    return None if v is None else v / ctx["window_s"]
