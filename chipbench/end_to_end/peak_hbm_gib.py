"""Largest `peak_bytes_in_use` over the cell's chips, read from the device
runtime after the window and before the reference runs. GiB."""


def read(ctx):
    return ctx.peak_bytes / 2**30 if ctx.peak_bytes else None
