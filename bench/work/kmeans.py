"""Least work of a Lloyd k-means fit, from its shapes alone.

Each iteration must, for every point, form its distance to every
centroid (2·K·D operations: a multiply and an add per feature and
centroid) and add the point into its centroid's sum (D additions, plus
the subtraction and square of the distance form's own D terms: 3·N·D
in all), and must read every point once (N·D·4 bytes of f32).  How an
implementation schedules that work does not enter, so the count reads
the same whoever implements Lloyd.
"""
from __future__ import annotations


def work(n: int, d: int, k: int, iters: int) -> dict:
    return {
        "flops": float((2 * n * k * d + 3 * n * d) * iters),
        "bytes": float(n * d * 4 * iters),
    }
