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


def test_train_profile_dir_records_the_input_spans(tmp_path, monkeypatch):
    """``launch.train --profile-dir`` traces the chosen steps, each in a
    ``train`` step annotation, with the input pipeline's spans beside it.
    The producer runs two batches ahead: the batch it makes while step 2
    runs falls inside the trace of steps 1 to 3."""
    from repro.launch import train

    # JAX reads the variable itself: the run sets no cache in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    out = tmp_path / "profile"
    train.run([
        "--arch", "qwen3-0.6b", "--smoke", "--steps", "6", "--seq-len", "16",
        "--per-node-batch", "2", "--log-every", "1",
        "--profile-dir", str(out), "--profile-steps", "1:4",
    ])
    paths = list(out.glob("**/*.xplane.pb"))
    assert len(paths) == 1
    names = {ev.name for plane in jax.profiler.ProfileData.from_file(str(paths[0])).planes
             for line in plane.lines for ev in line.events}
    assert {"repro.input.produce", "repro.input.put", "repro.input.wait",
            "train"} <= names


@pytest.mark.parametrize("text,want", [("0:3", (0, 3)), ("2:5", (2, 5))])
def test_profile_steps_parse(text, want):
    from repro.launch import train

    assert train.parse_args(["--profile-steps", text]).profile_steps == want


@pytest.mark.parametrize("text", ["3", "3:3", "4:2", "a:b"])
def test_profile_steps_refuse_a_bad_range(text):
    from repro.launch import train

    with pytest.raises(SystemExit):
        train.parse_args(["--profile-steps", text])
