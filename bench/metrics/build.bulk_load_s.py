"""Seconds of the boot's index construction in set-up: the host FMBI bulk
load (``core/fmbi.py``) for the FMBI boot, the AMBI construction of one
unrefined root (``core/ambi.py``) for the AMBI boot."""


def read(ctx):
    return ctx.build["bulk_load_s"]
