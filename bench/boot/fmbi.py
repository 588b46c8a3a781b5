"""The FMBI boot: the host FMBI bulk load of the points, then
``DeviceQueryServer.from_index`` until its table is on the device.  The
served index is static: serving changes nothing in it, so set-up warms up
the server that the window then serves."""
from __future__ import annotations

import time

import numpy as np

CHANGES_AS_IT_SERVES = False


def boot(config: dict, points: np.ndarray, batch_max: int):
    """Returns the server and the seconds of the bulk load
    (``bulk_load_s``) and of the boot until the table is on the device
    (``upload_s``)."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench.harness import buffer_pages
    from repro.core import PageStore, bulk_load
    from repro.serve.engine import DeviceQueryServer

    m = buffer_pages(config, points)
    t0 = time.perf_counter()
    with TraceAnnotation("build.bulk_load"):
        idx = bulk_load(points, m, PageStore(m))
    t1 = time.perf_counter()
    with TraceAnnotation("build.boot"):
        srv = DeviceQueryServer.from_index(idx, microbatch=batch_max)
        jax.block_until_ready(srv.dev)
    t2 = time.perf_counter()
    return srv, {"bulk_load_s": t1 - t0, "upload_s": t2 - t1}
