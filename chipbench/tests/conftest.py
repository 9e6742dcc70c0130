"""The benchmark's own tests run on the CPU: the program and the benchmark
on the path, JAX's persistent compile cache off."""
import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

jax.config.update("jax_enable_compilation_cache", False)
