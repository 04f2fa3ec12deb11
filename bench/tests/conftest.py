"""The benchmark's own tests run on the CPU:
``python -m pytest bench/tests``."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
