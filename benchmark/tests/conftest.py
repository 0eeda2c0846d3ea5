"""The benchmark's own CPU tests: ``python -m pytest benchmark/tests``.
(``pyproject.toml`` has ``testpaths = ["tests"]``; this directory is
found only when named.)"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
