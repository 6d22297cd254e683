"""The benchmark's CPU tests: ``python -m pytest bench/tests``.

They run the harness on JAX's CPU backend at a small size; nothing here
needs or touches a chip.
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
