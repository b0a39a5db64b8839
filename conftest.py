"""Repo-root pytest conftest.

* Guarantees ``src`` is importable even when the ``pythonpath`` ini option
  is unavailable (defensive — pyproject.toml sets it too).
* Derandomizes ``hypothesis``: every run draws the same examples and no
  example database is read or written, so a property test's verdict does
  not change from one run of the suite to the next.
"""
from __future__ import annotations

import os
import sys

from hypothesis import settings

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

settings.register_profile("repro", derandomize=True)
settings.load_profile("repro")
