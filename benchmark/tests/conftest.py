"""The benchmark's own tests run on the CPU, at small sizes:

    python -m pytest benchmark/tests

They import the harness as ``benchmark/run.py`` does (``harness``,
``ref``) and the program from the checkout's root."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))):
    if p not in sys.path:
        sys.path.insert(0, p)
