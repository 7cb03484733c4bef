"""The sharded benchmark cell's driver against the dense reference on four
host devices (``tests/progs/shard_bench.py``), in a subprocess so that the
rest of the suite keeps seeing the one real device."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_sharded_driver_judged_by_the_reference():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "progs",
                                      "shard_bench.py")],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr[-3000:]}"
    assert "SHARD_BENCH_OK" in out.stdout
