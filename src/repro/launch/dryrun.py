import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run of the suffix-array pipeline: lower + compile
``make_pipeline`` on a flat 256- or 512-device mesh of host devices against
ShapeDtypeStruct inputs; record cost_analysis, memory_analysis and HLO
collective bytes.

The XLA_FLAGS lines above MUST stay the first statements — jax locks the
device count at first init.  Never set this in conftest/pyproject.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh single --out dryrun.json
"""
import argparse
import json
import time

import jax
import numpy as np


def run_sa_dryrun(multi_pod: bool):
    """Lower+compile the SA pipeline itself on the production mesh."""
    from repro.analysis import hlo as hlo_lib
    from repro.config import SAConfig
    from repro.core.pipeline import make_pipeline, plan
    from repro.launch.mesh import make_sa_mesh

    d = 512 if multi_pod else 256
    mesh = make_sa_mesh(d)
    # grouper-genome-scale shard sizing, shrunk rows so CPU lowering stays sane
    # (per-device record count matches ~64 GB input / 512 shards at L=200)
    reads_per_shard = 2048
    l = 200
    cfg = SAConfig(vocab_size=4, packing="base", samples_per_shard=1024,
                   adaptive=False)
    corpus_shape = (reads_per_shard * d, l)
    jitted, info = make_pipeline(corpus_shape, cfg, mesh)
    rows = info["rows_per_shard"]
    data = jax.ShapeDtypeStruct((d * rows, l), np.int32)
    lens = jax.ShapeDtypeStruct((d * rows,), np.int32)
    halo = jax.ShapeDtypeStruct((d,), np.int32)
    t0 = time.time()
    lowered = jitted.lower(data, lens, halo)
    compiled = lowered.compile()
    coll = hlo_lib.collective_bytes(compiled.as_text())
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return {
        "arch": "suffix-array-pipeline",
        "shape": f"reads{reads_per_shard * d}x{l}",
        "mesh": "512flat" if multi_pod else "256flat",
        "status": "ok",
        "seconds": round(time.time() - t0, 1),
        "hlo_flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "collective": coll,
        "memory_analysis": str(compiled.memory_analysis()),
    }


def main():
    ap = argparse.ArgumentParser(
        description="Lower and compile the suffix-array pipeline on a flat "
        "256- (single) or 512-device (multi) mesh of host devices.")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--out", default="dryrun_results.json")
    args = ap.parse_args()

    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for mp in meshes:
        r = run_sa_dryrun(mp)
        results.append(r)
        print(json.dumps({k: r[k] for k in ("arch", "mesh", "status", "seconds")}))
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
