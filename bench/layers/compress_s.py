"""Guarantee, entropy and container layers: mean host seconds of
``compress_report`` per job (the benchmark's ``bench.compress`` span;
``core/gae.py``, ``core/entropy.py``, ``codec/encode.py``)."""


def read(ctx):
    spans = ctx.spans.get("compress")
    return sum(spans) / len(spans) if spans else None
