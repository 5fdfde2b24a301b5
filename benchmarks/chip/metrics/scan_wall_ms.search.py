"""Milliseconds per question in ``MegaBatch.predict`` on the jax
backend: transfer, the device scan and the host epilogue."""


def read(r):
    n = r.values.get("questions")
    return r.values["scan_wall_s"] / n * 1e3 if n else None
