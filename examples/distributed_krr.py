"""The paper's workload, distributed: WLSH-KRR on an 8-device mesh (forced
CPU devices), exercising the psum-merged bucket tables and sharded CG that the
multi-pod dry-run lowers for 512 chips.

    python examples/distributed_krr.py      (sets its own XLA_FLAGS)

It runs ``krr_train`` in a child process with 8 fake CPU devices, so it is
refused on a TPU host, where the child would run on the CPU; there
``python chip_smoke.py --chips 4`` runs the sharded fit on the chips.
"""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CMD = [sys.executable, "-m", "repro.launch.krr_train",
       "--dataset", "forest", "--scale", "0.002", "--m", "64",
       "--lam", "0.5", "--cg-iters", "40"]

if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.common import refuse_on_tpu
    refuse_on_tpu("examples/distributed_krr.py")
    env = dict(os.environ)
    env.update({"PYTHONPATH": "src",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    print("+ XLA_FLAGS=--xla_force_host_platform_device_count=8",
          " ".join(CMD))
    raise SystemExit(subprocess.run(CMD, env=env).returncode)
