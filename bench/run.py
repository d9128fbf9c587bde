"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  The last
line of standard output is one JSON object: ``correct``, ``attempted``
and ``failed`` (window rounds, and those that disagree with the plain
reference), ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, last, ``check``:
each number compared with its limit.  It exits 1 and prints no result
where JAX finds no TPU, or fewer chips than the cell asks for.
"""
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import harness
    sys.exit(harness.main(t_start=T_START))
