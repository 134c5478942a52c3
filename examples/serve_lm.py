"""Batched serving example with SME-compressed weights.

    PYTHONPATH=src python examples/serve_lm.py

Runs the one-layer ``--smoke`` scale-down of qwen1.5-0.5b so it finishes
on a CPU (Pallas kernels in interpret mode).  Drop ``--smoke`` to serve
the published widths, which is what ``chip_smoke.py`` does on a TPU.
"""
import subprocess
import sys

if __name__ == "__main__":
    subprocess.run([
        sys.executable, "-m", "repro.launch.serve",
        "--arch", "qwen1.5-0.5b", "--smoke", "--requests", "6",
        "--max-new", "10", "--sme", "--squeeze", "1",
    ], check=True)
