"""Seconds of the host FMBI bulk load (``core/fmbi.py``) in set-up."""


def read(ctx):
    return ctx.build["bulk_load_s"]
