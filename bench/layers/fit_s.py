"""Trainer layer: mean host seconds of ``GBATCCodec.fit`` per job (the
benchmark's ``bench.fit`` span; ``train/train_loop.py``,
``core/autoencoder.py``, ``core/correction.py``)."""


def read(ctx):
    spans = ctx.spans.get("fit")
    return sum(spans) / len(spans) if spans else None
