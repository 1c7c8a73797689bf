"""Readings that the limits of ``correct`` are set from (not part of a
benchmark run):

    python3 bench/calibrate.py --workload <name> --seeds 11,12,... \
        --controls 11,12,13 --faults half [--witness norm_eps=1e-06] \
        --out <file.json>

In one process on the chip, at the cell's own size: the program's first
window from each seed against the plain reference (the lower readings),
then the reference computed with fp8 products in the program's place
(the control) and the reference with a fault planted (``half``: half of
every batch left out) on the ``--controls`` seeds (the upper readings).
Each number is the one ``bench.check`` compares; the limits go into
``bench/limits/<workload>.json`` by the rules in PERF.md. ``--witness``
also holds each program seed against the reference with one of its
settings changed (``key=value`` of the configuration's ``reference``
group), and that reference against the plain one: where the program
departs from its configuration in that setting, the witness shows how
much of a reading the departure makes.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from bench import check, harness, reference  # noqa: E402
from bench.spec import ROOT, Spec  # noqa: E402


def main(argv=None, root: str = ROOT, require_chip: bool = True) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", default="")
    p.add_argument("--faults", default="")
    p.add_argument("--witness", default="")
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = [int(s) for s in args.controls.split(",") if s]
    faults = [f for f in args.faults.split(",") if f]

    spec = Spec(root)
    work = spec.workload(args.workload)
    config = spec.config(work["config"])
    traffic = spec.traffic(work["traffic"])
    try:
        if require_chip:
            harness.require_devices(work["chips"])
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    harness.configure_cache(root)
    from repro.parallel.collectives import compat_set_mesh

    t0 = time.perf_counter()
    job = harness.Job(config, traffic)
    progs = {}
    with compat_set_mesh(job.mesh):
        for seed in seeds:
            t = time.perf_counter()
            state, loop, pipe, prog = job.first_window(seed)
            pipe.stop()
            del state, loop, pipe
            progs[seed] = prog
            print(f"program seed {seed}: {time.perf_counter() - t:.1f} s",
                  file=sys.stderr, flush=True)
    setup = time.perf_counter() - t0
    del job
    gc.collect()

    ref_f32 = reference.Reference(config, traffic)
    out = {"workload": work["name"], "setup_s": setup, "program": {},
           "control": {}, "faults": {f: {} for f in faults}}
    witness = None
    if args.witness:
        key, value = args.witness.split("=")
        out["witness"] = {"setting": {key: float(value)}}
        witness = reference.Reference(
            dict(config, reference={**config["reference"],
                                    key: float(value)}), traffic)
    K = traffic["window_steps"]
    refs = {}
    for seed in seeds:
        t = time.perf_counter()
        refs[seed] = ref_f32.run(seed, K)
        out["program"][seed] = check.numbers(progs[seed], refs[seed])
        out["program"][seed]["losses"] = progs[seed]["losses"]
        print(f"reference seed {seed}: {time.perf_counter() - t:.1f} s; "
              f"{out['program'][seed]}", file=sys.stderr, flush=True)
        if witness is not None:
            w = witness.run(seed, K)
            out["witness"][seed] = {"program": check.numbers(progs[seed], w),
                                    "reference": check.numbers(w,
                                                               refs[seed])}
            print(f"witness seed {seed}: {out['witness'][seed]}",
                  file=sys.stderr, flush=True)
    del witness  # its optimizer state, before the control's
    gc.collect()
    if controls:
        ctl = reference.Reference(config, traffic, precision="fp8")
        for seed in controls:
            out["control"][seed] = check.numbers(ctl.run(seed, K),
                                                 refs[seed])
            print(f"control seed {seed}: {out['control'][seed]}",
                  file=sys.stderr, flush=True)
    for fault in faults:
        for seed in controls:
            out["faults"][fault][seed] = check.numbers(
                ref_f32.run(seed, K, fault=fault), refs[seed])
            print(f"fault {fault} seed {seed}: "
                  f"{out['faults'][fault][seed]}", file=sys.stderr,
                  flush=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("program", "control", "faults",
                                          "witness") if k in out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
