"""Host input pipeline: sharded prefetch, and producer failures surface."""

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.data.pipeline import prefetch_to_device


def _shardings():
    return {"tokens": SingleDeviceSharding(jax.devices()[0])}


def test_prefetch_yields_every_batch_in_order_on_the_sharding():
    def batch_fn(k):
        return {"tokens": np.full((2, 3), k, np.int32)}

    got = list(prefetch_to_device(batch_fn, _shardings(), 5))
    assert [int(b["tokens"][0, 0]) for b in got] == [0, 1, 2, 3, 4]
    for b in got:
        assert isinstance(b["tokens"], jax.Array)
        assert b["tokens"].sharding == _shardings()["tokens"]


def test_prefetch_reraises_a_producer_failure_in_the_consumer():
    def batch_fn(k):
        if k == 2:
            raise ValueError("bad shard")
        return {"tokens": np.zeros((2, 3), np.int32)}

    seen = []
    with pytest.raises(ValueError, match="bad shard"):
        for b in prefetch_to_device(batch_fn, _shardings(), 5):
            seen.append(b)
    assert len(seen) == 2
