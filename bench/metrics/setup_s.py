"""Process start to the start of the window, compile included."""


def read(ctx):
    return ctx.get("setup_s")
