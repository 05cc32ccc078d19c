"""dstpu_bench CLI (ref bin/ds_bench): runs to completion on the CPU
backend chosen through the JAX_PLATFORMS environment variable."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dstpu_bench_cpu_pin():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "dstpu_bench"),
         "--sizes-mb", "0.25", "--trials", "1"],
        capture_output=True, text=True, timeout=240, env=env)
    assert out.returncode == 0, out.stderr[-500:]
    rows = [json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    ops = {r["op"] for r in rows}
    assert {"all_reduce", "all_gather", "reduce_scatter",
            "all_to_all"} <= ops
    assert all(r["world"] == 4 and r["time_ms"] > 0 for r in rows)
