"""Run by hand and in rehearsal: ``python -m pytest benchmark/tests -q``.

The CPU platform is chosen before the first ``import jax``, as
tests/conftest.py does for tier-1; tier-1 itself does not collect this
directory.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for path in (REPO, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
