"""Least work of an exact ε-self-join, from its shapes and output size.

Any exact join must read its points once (N·D·4 bytes of f32) and
write each of its P pairs (two int32 ids, 8 bytes).  An algorithm that
prunes well does no distance work it can avoid, so no operation count
is a lower bound: the bound is the bytes alone, and no pruning can
push a roofline share computed from it past 100%.
"""
from __future__ import annotations


def work(n: int, d: int, pairs: int) -> dict:
    return {"flops": 0.0, "bytes": float(n * d * 4 + pairs * 8)}
