"""Seconds from ``DeviceQueryServer.from_index`` until its table is on the
device: the ``NodeTable`` export and the upload."""


def read(ctx):
    return ctx.build["upload_s"]
