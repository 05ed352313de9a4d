"""`benchmark` must mean the package at the root of the checkout: this
directory has the same name and no __init__.py, so that it never shadows
it; putting the root first makes that hold however pytest was started."""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if sys.path[0] != _ROOT:
    sys.path.insert(0, _ROOT)
