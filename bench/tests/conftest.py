"""Make the benchmark (``bench``) and the program (``src``) importable
when the harness's tests run as ``pytest bench/tests`` from the root of
the checkout."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (os.path.join(ROOT, "src"), ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
