"""Test set-up for the benchmark's own tests: the benchmark's modules and
the program's ``src`` are importable, and JAX stays on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
