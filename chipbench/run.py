"""Run one cell of BENCHMARK.json once, in this process, on this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model from the seed, checks it against its float32
reference, compiles and warms the one step program the cell uses, then
measures for ``--seconds`` (``--trace 0``: the end-to-end metrics) or
profiles a short steady window and reduces the device trace
(``--trace 1``: the per-layer metrics). The last line of stdout is one
JSON object; everything else a reader may want is on the lines before it.

Without a TPU, or with another number of chips than the cell asks for,
it exits 2 and prints no result. ``--rehearse`` is the one CPU path: the
same code at a toy size, printing counts and never a metric (see
README.md).
"""
import time

T0 = time.perf_counter()    # set-up is counted from here

import argparse     # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), required=True)
    ap.add_argument('--rehearse', action='store_true',
                    help='toy size on the CPU backend; counts only')
    ap.add_argument('--out', default=None,
                    help='where a traced run leaves its files (default: '
                         'chiprun_out/<workload>/ in the checkout)')
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, 'mxnet_tpu')):
        print(f"chipbench: no mxnet_tpu in {ROOT}: there is no program "
              f"here to measure", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from chipbench import harness
    return harness.run(args, T0)


if __name__ == '__main__':
    sys.exit(main())
