"""Seconds from the boot's server construction until its table is on the
device: the ``NodeTable`` export and the upload (``from_index`` for the
FMBI boot, the partial export of ``from_ambi`` for the AMBI boot)."""


def read(ctx):
    return ctx.build["upload_s"]
