"""The benchmark's own tests run on the CPU; the repo's tier-1 suite
(``tests/``) does not collect them.

    python -m pytest bench/tests
"""

import os
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# CPU runs keep their compiles out of the checkout's cache directory,
# which runs on the chip read
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="bench-tests-jax-cache-")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
