"""Host-side input pipeline: background prefetch + sharded device_put.

Deliberately simple (the synthetic stream is cheap), but shaped like the
real thing: a producer thread keeps ``depth`` batches in flight, each
device_put against the step's NamedShardings so host->device transfer
overlaps the previous step's compute.

Under ``jax.profiler`` the pipeline records three host spans:
``repro.input.produce`` (``batch_fn``) and ``repro.input.put`` (the
sharded ``device_put``) in the producer thread, and ``repro.input.wait``
(the consumer blocked on the queue).  With no profiler active each costs
about a microsecond on the host.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterator

import jax
from jax.profiler import TraceAnnotation

__all__ = ["prefetch_to_device"]


def prefetch_to_device(
    batch_fn: Callable[[int], Any],
    shardings: Any,
    n_steps: int,
    *,
    depth: int = 2,
) -> Iterator[Any]:
    """Yields device-placed batches for steps [0, n_steps).

    ``batch_fn`` returns host (numpy) arrays; the only transfer is the
    sharded ``device_put``.  An exception in the producer thread is
    re-raised here, in the consumer, so a failing input pipeline fails
    the run instead of ending it early.
    """
    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = object()
    failure: list[BaseException] = []

    def produce():
        try:
            for s in range(n_steps):
                with TraceAnnotation("repro.input.produce"):
                    host = batch_fn(s)
                with TraceAnnotation("repro.input.put"):
                    dev = jax.tree.map(
                        lambda x, sh: jax.device_put(x, sh), host, shardings
                    )
                q.put(dev)
        except BaseException as e:  # noqa: BLE001 — handed to the consumer
            failure.append(e)
        finally:
            q.put(stop)

    t = threading.Thread(target=produce, daemon=True)
    t.start()
    while True:
        with TraceAnnotation("repro.input.wait"):
            item = q.get()
        if item is stop:
            t.join()
            if failure:
                raise failure[0]
            return
        yield item
