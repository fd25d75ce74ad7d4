#!/usr/bin/env python3
"""``python3 benchmark/control.py --workload <cell> --seeds a,b,c``: the
control of "How correct is decided", at the cell's own size on the chip.

For each seed it makes the cell's tables as a run does, answers every plan
of the mix with the reference and with the plan's ``control`` (the
reference in the precision below the one the configuration states), and
prints each number compared beside its limit. The control has to come out
as NOT correct on every seed; the exit code is non-zero if it ever passes.
The benchmark's own runs never run this."""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def control_numbers(workload: str, seed: int, *, platform: str = "tpu",
                    sizes: dict | None = None) -> dict:
    """{plan: {number: value}} of the control against the reference."""
    from benchmark import harness, resolve

    bench = resolve.spec()
    cell, config, mix = resolve.cell(workload, bench)
    harness.find_device(platform, int(cell["chips"]))
    hosts = {name: maker.host_copy(arrays) for name, (maker, _, arrays)
             in harness.make_tables(config, seed, sizes or {}).items()}
    out = {}
    for p in mix["plans"]:
        mod = resolve.module("plans", p["plan"])
        out[p["plan"]] = mod.compare(mod.control(hosts[mod.TABLE]),
                                     mod.oracle(hosts[mod.TABLE]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    from benchmark import resolve

    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        for plan, numbers in control_numbers(args.workload, seed).items():
            limits = resolve.module("plans", plan).LIMITS
            over = [n for n, v in numbers.items() if not v <= limits[n]]
            print(f"control {args.workload} seed {seed} plan {plan}: " + ", ".join(
                f"{n} {v!r} (limit {limits[n]!r})" for n, v in numbers.items())
                + (" -> not correct" if over else " -> PASSED, it must not"),
                flush=True)
            passed += not over
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
