"""push_pull microbenchmark — the byteps_tpu rendering of the reference's
``example/pytorch/microbenchmark-byteps.py``: per-size latency (and
effective bandwidth) of the eager scheduled push_pull path, plus the
wire-compression variants.  Run::

    python examples/microbenchmark_byteps.py
    python examples/microbenchmark_byteps.py --sizes 1024 1048576

Note what this measures: the EAGER path is host-mediated (host tensor →
device → collective → host), so host↔device transfer dominates — the
same is true of the reference's eager op (its GPU D2H/H2D stages).  The
training hot path (``make_data_parallel_step``) keeps tensors on-device
and does not pay this; use ``benchmark/run.py`` for end-to-end step numbers.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

import byteps_tpu as bps
from byteps_tpu.ops.compression import Compression


def benchmark(x, name, iters, compression=Compression.none):
    # warm the path (declaration, partitioning, first collective compile)
    out = bps.push_pull(x, average=True, name=name, compression=compression)
    np.asarray(out)
    lat = []
    for i in range(iters):
        t0 = time.perf_counter()
        out = bps.push_pull(x, average=True, name=name,
                            compression=compression)
        np.asarray(out)  # value readback = true completion barrier
        lat.append(time.perf_counter() - t0)
    return np.array(lat)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--num-iters", type=int, default=50)
    p.add_argument("--sizes", type=int, nargs="*",
                   default=[2 ** k for k in range(10, 25, 2)],
                   help="tensor sizes in elements (fp32)")
    args = p.parse_args()

    bps.init()
    if bps.rank() == 0:
        print(f"workers: {bps.size()}  devices: {len(jax.devices())}")
        print(f"{'bytes':>12} {'p50 ms':>9} {'p99 ms':>9} {'GB/s':>8}  variant")

    import jax as _jax

    n = bps.size()
    multiproc = _jax.process_count() > 1
    for size in args.sizes:
        # eager contract: multi-process runs pass THIS process's
        # contribution (api.push_pull routes to the multihost path);
        # single-process multi-device runs stack on a leading worker axis
        if multiproc or n == 1:
            x = np.random.rand(size).astype(np.float32)
        else:
            x = np.random.rand(n, size).astype(np.float32)
        for comp, tag in ((Compression.none, "fp32"),
                          (Compression.bf16, "bf16-wire")):
            lat = benchmark(x, f"micro_{size}_{tag}", args.num_iters, comp)
            if bps.rank() == 0:
                nbytes = size * 4
                p50 = float(np.percentile(lat, 50))
                p99 = float(np.percentile(lat, 99))
                # algorithmic bytes moved: 2x payload (reduce + gather)
                gbps = 2 * nbytes / p50 / 1e9
                print(f"{nbytes:>12} {p50 * 1e3:>9.3f} {p99 * 1e3:>9.3f} "
                      f"{gbps:>8.2f}  {tag}")
    bps.shutdown()


if __name__ == "__main__":
    main()
