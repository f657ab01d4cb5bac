"""Reproduce the paper's Fig. 1 narrative: MRA vs low-rank vs sparsity.

Builds a representative (structured) attention matrix, approximates it three
ways at the same 10% budget, and prints the error comparison the paper opens
with (MRA 0.30 / low-rank 1.24 / sparse 0.39 on their example).

    PYTHONPATH=src python examples/approx_demo.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.approx_error import fig1_matrix_level  # noqa: E402
from repro.launch.device import enable_compile_cache  # noqa: E402


def main():
    enable_compile_cache()
    print("budget = keep 10% of {MRA block entries | ranks | nonzeros}\n")
    print(f"{'seed':>4} {'MRA':>8} {'SVD(opt)':>9} {'Nystrom':>9} {'sparse*':>8}")
    errs = []
    for seed in range(5):
        e = fig1_matrix_level(np.random.default_rng(seed))
        errs.append(e)
        print(f"{seed:>4} {e[0]:8.3f} {e[1]:9.3f} {e[2]:9.3f} {e[3]:8.3f}")
    mean = np.mean(errs, axis=0)
    print(f"{'mean':>4} {mean[0]:8.3f} {mean[1]:9.3f} {mean[2]:9.3f} {mean[3]:8.3f}")
    print("\npaper Fig. 1: MRA 0.30, low-rank 1.24, sparse 0.39")
    print("(* top-entry sparsity is an O(n^2) oracle, not a practical method;")
    print("   SVD is the optimal low-rank bound; Nystrom is the realizable one)")
    print("claim check — MRA < practical low-rank:", bool(mean[0] < mean[2]))
    print("claim check — MRA < optimal SVD:       ", bool(mean[0] < mean[1]))


if __name__ == "__main__":
    main()
