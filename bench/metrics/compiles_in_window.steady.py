"""Backend compiles during the window (jax.monitoring)."""


def read(ctx):
    return ctx.get("compiles_in_window") if "ttft_ms" in ctx else None
