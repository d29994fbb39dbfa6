"""The benchmark's CPU tests: ``python -m pytest chipbench/tests``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for path in (ROOT, os.path.join(ROOT, "src")):
  if path not in sys.path:
    sys.path.insert(0, path)
