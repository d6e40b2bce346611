"""The control of the comparison that decides ``correct``, at a cell's size.

    python3 bench/control.py --workload hybrid_n26.batch_small --seeds 11 12 13

For each seed it makes the cell's array and request pool exactly as a run
does, puts the reference computed over bfloat16-rounded values in the
program's place, and prints how many of the pool's answers the comparison
finds wrong (``control_wrong_answers``; ``control_wrong_idx`` counts the
answers whose index alone differs). The benchmark's own runs never run
this; it sets the upper reading of the ``wrong_answers`` limit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "rmqbench-tpu-logs"))
    sys.path.insert(0, str(ROOT / "bench"))

    import jax
    from rmqbench import harness, reference, traffic

    cell = harness.load_cell(ROOT, args.workload, trace=False)
    for seed in args.seeds:
        x = np.asarray(jax.device_get(harness.make_data(jax, cell.config, seed)))
        pool = traffic.request_pool(seed, cell.config["n"], cell.mix)
        ref = reference.Reference(x)
        ctl = reference.Reference(reference.control_values(x))
        wrong = wrong_idx = queries = 0
        for per_client in pool:
            for l, r in per_client:
                gi, gv = ref.query(l, r)
                ci, cv = ctl.query(l, r)
                wrong += int(np.count_nonzero((ci != gi) | (cv != gv)))
                wrong_idx += int(np.count_nonzero(ci != gi))
                queries += l.size
        print(json.dumps({
            "workload": args.workload, "seed": seed, "queries": queries,
            "control_wrong_answers": wrong, "control_wrong_idx": wrong_idx,
            "device": jax.devices()[0].device_kind,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
