"""Run a cell with a control in the program's place: the plain reference,
weakened, where the timed path would run.  `correct` must come out false.

  python3 bench/control.py --workload <cell> --seed <n> --seconds <s> \
      --weaken half_words|snapshot

  half_words  words held in 16 bits, below the configuration's 32;
  snapshot    every op of a batch sees the state from before the batch
              (the serialising slow path skipped).

Prints the run's result line, as `run.py` does, with `weaken` added.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, os.path.dirname(HERE))

from bench import reference, run  # noqa: E402

WEAKEN = {"half_words": {"mask": reference.HALF},
          "snapshot": {"snapshot": True}}


def run_control(plan: dict, seed: int, seconds: float, weaken: str,
                devices) -> dict:
    mod = run.system(plan["config"]["system"])
    out = run.run_cell(
        plan, seed, seconds, False, devices,
        make_cell=lambda c, t, s, d: mod.Control(c, t, s, d, WEAKEN[weaken]))
    return {"weaken": weaken, **out}


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--weaken", choices=sorted(WEAKEN), required=True)
    args, rest = ap.parse_known_args(argv)
    cli = run.parse(rest)
    run.setup_paths()
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        plan = run.plan_for(json.load(f), cli.workload)
    devices = run.start_jax(plan["chips"])
    run.print_result(run_control(plan, cli.seed, cli.seconds, args.weaken,
                                 devices))


if __name__ == "__main__":
    main()
