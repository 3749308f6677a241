"""The benchmark's own tests run on the CPU, at a size a test run holds:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
