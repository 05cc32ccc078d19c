"""`dstpu_bench` — collective micro-benchmark.

Analog of the reference's ``ds_bench`` (bin/ds_bench → communication
benchmarks): times all_reduce / all_gather / reduce_scatter / all_to_all
over the active mesh axis and reports algorithmic bandwidth, using the same
busbw conventions as the reference's comms logger
(ref utils/comms_logging.py:34 calc_bw_log).
"""

from __future__ import annotations

import argparse
import json
import time
from typing import List, Optional

import numpy as np


def bw_factor(op: str, n: int) -> float:
    """Algorithmic→bus bandwidth factor (ring algorithms).

    Ref: get_bw (utils/comms_logging.py:34): allreduce 2(n-1)/n, allgather /
    reducescatter / alltoall (n-1)/n.
    """
    if n <= 1:
        return 1.0
    if op == "all_reduce":
        return 2.0 * (n - 1) / n
    return (n - 1) / n


def run_bench(sizes_mb: Optional[List[float]] = None, trials: int = 5,
              axis: str = "data", dtype="float32") -> List[dict]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu import comm
    from deepspeed_tpu.parallel.topology import get_topology

    comm.comm.init_distributed()
    topo = get_topology()
    n = topo.axis_size(axis) if hasattr(topo, "axis_size") else 1
    mesh = topo.mesh
    sizes_mb = sizes_mb or [1.0, 16.0, 64.0]
    results = []

    from deepspeed_tpu.utils.jax_compat import shard_map

    for op in ("all_reduce", "all_gather", "reduce_scatter", "all_to_all"):
        for mb in sizes_mb:
            itemsize = np.dtype(dtype).itemsize
            elems = int(mb * 1e6 / itemsize)
            elems = max(n * n, elems - elems % (n * n))  # divisible for rs/a2a
            x = jnp.ones((elems,), dtype=dtype)
            x = jax.device_put(x, NamedSharding(mesh, P()))

            if op == "all_reduce":
                fn = lambda a: jax.lax.psum(a, axis)
                in_spec, out_spec = P(), P()
            elif op == "all_gather":
                fn = lambda a: jax.lax.all_gather(a, axis, tiled=True)
                in_spec, out_spec = P(axis), P()
            elif op == "reduce_scatter":
                fn = lambda a: jax.lax.psum_scatter(a, axis, tiled=True)
                in_spec, out_spec = P(), P(axis)
            else:
                fn = lambda a: jax.lax.all_to_all(
                    a.reshape(n, -1), axis, split_axis=0, concat_axis=0,
                    tiled=False).reshape(-1)
                in_spec, out_spec = P(axis), P(axis)

            jitted = jax.jit(shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                                       out_specs=out_spec,
                                       check_vma=False))
            out = jitted(x)  # compile + warm
            np.asarray(jax.device_get(out)).ravel()[:1]
            t0 = time.perf_counter()
            for _ in range(trials):
                out = jitted(x)
            np.asarray(jax.device_get(out)).ravel()[:1]
            dt = (time.perf_counter() - t0) / trials

            nbytes = elems * itemsize
            algbw = nbytes / dt / 1e9
            results.append({
                "op": op, "size_mb": round(nbytes / 1e6, 2), "axis": axis,
                "world": n, "time_ms": round(dt * 1e3, 3),
                "algbw_gbps": round(algbw, 2),
                "busbw_gbps": round(algbw * bw_factor(op, n), 2),
            })

    # qgZ row: int8 block-quantized gradient reduce (ZeRO++ transport) vs
    # the fp32 reduce-scatter above — wire traffic is s8 + 1/256 scales,
    # so effective bandwidth should approach 4x (ref qgZ claim; the HLO
    # test pins that the payload really is s8)
    from deepspeed_tpu.comm.coalesced_collectives import (
        all_to_all_quant_reduce)

    for mb in sizes_mb:
        itemsize = 4
        elems = int(mb * 1e6 / itemsize)
        elems = max(n * n * 256, elems - elems % (n * n * 256))
        x = jnp.ones((elems,), jnp.float32)
        x = jax.device_put(x, NamedSharding(mesh, P()))

        def qfn(a):
            shard, _ = all_to_all_quant_reduce(
                {"g": a}, axis, axis, inner_size=n, outer_size=1)
            return shard

        jitted = jax.jit(shard_map(qfn, mesh=mesh, in_specs=(P(),),
                                   out_specs=P(axis), check_vma=False))
        out = jitted(x)
        np.asarray(jax.device_get(out)).ravel()[:1]
        t0 = time.perf_counter()
        for _ in range(trials):
            out = jitted(x)
        np.asarray(jax.device_get(out)).ravel()[:1]
        dt = (time.perf_counter() - t0) / trials
        nbytes = elems * itemsize
        algbw = nbytes / dt / 1e9  # logical fp32 bytes reduced per second
        results.append({
            "op": "qgz_quant_reduce", "size_mb": round(nbytes / 1e6, 2),
            "axis": axis, "world": n, "time_ms": round(dt * 1e3, 3),
            "algbw_gbps": round(algbw, 2),
            "busbw_gbps": round(algbw * bw_factor("reduce_scatter", n), 2),
        })
    return results


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="dstpu_bench")
    p.add_argument("--sizes-mb", type=float, nargs="*", default=[1.0, 16.0])
    p.add_argument("--trials", type=int, default=5)
    p.add_argument("--axis", type=str, default="data")
    args = p.parse_args(argv)
    from deepspeed_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()
    for row in run_bench(args.sizes_mb, args.trials, args.axis):
        print(json.dumps(row))
    return 0


if __name__ == "__main__":  # pragma: no cover
    import sys
    sys.exit(main())
