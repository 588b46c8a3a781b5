"""The AMBI boot: a query-driven cold start (arXiv:2409.09447, section 4).
The index starts as one unrefined root over the points;
``DeviceQueryServer.from_ambi`` exports it partially, and serving refines
on the host where the queries land, grafts the result into the table and
pushes the new leaves to the device.  So the served index changes as it
serves: set-up warms up a second server booted alike, and the window
starts from this one as booted."""
from __future__ import annotations

import time

import numpy as np

CHANGES_AS_IT_SERVES = True


def boot(config: dict, points: np.ndarray, batch_max: int):
    """Returns the server and the seconds of the AMBI construction
    (``bulk_load_s``) and of the boot until the partial table is on the
    device (``upload_s``)."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench.harness import buffer_pages
    from repro.core import AMBI, PageStore
    from repro.serve.engine import DeviceQueryServer

    m = buffer_pages(config, points)
    t0 = time.perf_counter()
    with TraceAnnotation("build.bulk_load"):
        ambi = AMBI(points, m, PageStore(m))
    t1 = time.perf_counter()
    with TraceAnnotation("build.boot"):
        srv = DeviceQueryServer.from_ambi(ambi, microbatch=batch_max)
        jax.block_until_ready(srv.dev)
    t2 = time.perf_counter()
    return srv, {"bulk_load_s": t1 - t0, "upload_s": t2 - t1}
