"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout that holds ``BENCHMARK.json``, ``bench/`` and
the program under ``src/``.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, ``breakdown`` (traced runs), and ``checks``,
each compared number beside its limit, which are also the last lines of
standard error.  With no TPU, too few chips, a device kind that
``bench/peaks.json`` does not list, or no program beside it, the run exits
with a code other than 0 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]


def fail(msg: str, code: int = 2) -> int:
    print(f"bench: FAIL: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0:
        return fail("--seed must be a non-negative whole number")

    import spec

    try:
        cell = spec.load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot load the cell: {e!r}")

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        return fail(f"the cell needs {cell.chips} chips; JAX sees {len(devices)}")
    try:
        peaks = spec.peaks_for(devices[0].device_kind)
    except KeyError as e:
        return fail(str(e))
    try:
        from repro.launch.compile_cache import configure_compile_cache
    except ImportError as e:
        return fail(f"the program is not beside the benchmark ({e})")
    configure_compile_cache()
    # small programs (weights, norms, the reference) are cached too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    import harness

    result, rows = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace),
        devices=devices[: cell.chips], peaks=peaks, t_start=T_START)
    for r in rows:
        print(f"check {r['name']}: {r['value']:.6g} (limit {r['limit']:.6g}; "
              f"worst at {r['where']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip the runtime's teardown, whose messages would follow the checks
    os._exit(code)
