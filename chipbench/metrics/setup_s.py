"""Process start to the window's opening: imports, event generation,
warm-up compiles and cache loads, and the store's allocation."""


def read(ctx):
    return ctx.setup_s
