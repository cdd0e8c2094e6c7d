"""Run one cell of the benchmark once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

It needs a TPU and never falls back to the CPU: without one, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device (and breakdown with --trace 1), and last the
numbers compared, each beside its limit (also the last lines of
standard error).
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, allow_cpu=False, overrides=None,
         t_process=None) -> int:
    args = parse(argv)
    # the compile cache lives at a fixed path inside the checkout (the
    # program takes the directory it is given)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import tpu_ir  # noqa: F401  (the system under test must be there)

    from benchmark import harness

    run = harness.Run(args, T_PROCESS if t_process is None else t_process,
                      allow_cpu=allow_cpu, overrides=overrides)
    try:
        harness.driver(run.traffic["kind"]).run(run)
        result = run.result()
    finally:
        run.close()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
