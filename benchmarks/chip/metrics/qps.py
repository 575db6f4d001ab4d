"""Queries answered per second over the whole window (closed loop)."""


def read(rec):
    w = rec["window"]
    return len(w.qidx) / w.elapsed_s if w.elapsed_s > 0 else None
