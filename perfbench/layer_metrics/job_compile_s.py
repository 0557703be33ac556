"""Seconds `train.py` spent compiling its step (its own `compile_s`
metric in the run store): cold on a checkout's first run, a cache read
after."""


def read(ctx):
    return ctx.collected.get("compile_s")
