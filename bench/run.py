"""Chip benchmark of the RMQ service: one run of one cell.

    python3 bench/run.py --workload hybrid_n26.batch_small --seed 7 --seconds 30 --trace 0

Runs from the root of a checkout, on the machine that holds the cell's chips.
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics read from a profiler trace of the window. The last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last the
``checks`` that decided ``correct``). Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.

JAX's compilation cache is kept in ``.jax_cache/`` at the root of the
checkout, so only a cell's first run in a checkout compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"rmqbench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    # Caches and logs stay inside the checkout or the run's own TMPDIR.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "rmqbench-tpu-logs"))
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT / "bench"))

    from repro.launch.cache import enable_compile_cache
    from rmqbench import harness

    enable_compile_cache()
    return harness.run(ROOT, args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
