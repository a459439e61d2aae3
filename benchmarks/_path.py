"""Repo-root import shim for the benchmark scripts.

Run as `python benchmarks/<script>.py`: sys.path[0] is benchmarks/, so
`paddle_tpu` is not importable. Every benchmark does `import _path`
first; the insert happens in-process.
"""
import os
import sys

_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _repo not in sys.path:
    sys.path.insert(0, _repo)
