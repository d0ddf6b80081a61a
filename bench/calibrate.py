"""Readings that the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control] [--faults half_batch,no_exchange] [--out <file.jsonl>]

For each seed, in one process: the program's first steps as a run takes
them, then the reference; with ``--control`` the reference in float8 put in
the program's place; with ``--faults`` the reference with each fault
planted, in the program's place.  Each line of output is one seed's
numbers (``check.numbers``) for every side.  The benchmark's runs never
call this.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", action="store_true")
    p.add_argument("--faults", default="")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    import jax

    import check
    import harness
    import reference
    import spec
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload)
    devices = jax.devices()[: cell.chips]
    program = harness.Program(cell, devices)
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        state, feed = program.start(seed)
        state, got = program.first_steps(state, feed)
        feed.close()
        harness.free(state)
        batches = program.check_batches()
        kw = dict(devices=devices)
        ref = reference.run(cell.config, cell.traffic, seed, batches, **kw)
        row = {"workload": cell.name, "seed": seed,
               "program": check.numbers(got, ref), "loss": got["loss"],
               "ref_loss": ref["loss"]}
        if args.control:
            ctl = reference.run(cell.config, cell.traffic, seed, batches,
                                precision="fp8", **kw)
            row["control"] = check.numbers(ctl, ref)
        for fault in filter(None, args.faults.split(",")):
            bad = reference.run(cell.config, cell.traffic, seed, batches,
                                fault=fault, **kw)
            row[fault] = check.numbers(bad, ref)
        row["seconds"] = time.perf_counter() - t
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
